"""Wake of a batched window: end of ``accl.window::complete`` to the
latest end of the window's ``bench::window`` spans (the drains wake, the
eight ``wait``s return, the outputs are found ready); median over the
windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_window_us(ctx, window_spans.wake)
