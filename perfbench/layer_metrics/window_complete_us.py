"""A window's completion on the drainer (32 requests completed, the
window log, the breaker): median duration of ``accl.window::complete``
over the windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.duration_us(ctx, window_spans.COMPLETE)
