"""The window's operand globals assembled from the ranks' shards: median
duration of ``accl.ring::assemble`` over the windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.duration_us(ctx, window_spans.ASSEMBLE)
