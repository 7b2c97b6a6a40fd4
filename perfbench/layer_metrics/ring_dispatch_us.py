"""The window's one program call (the PJRT execute): median duration of
``accl::cmdring[n]`` over the windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.duration_us(ctx, window_spans.CMDRING)
