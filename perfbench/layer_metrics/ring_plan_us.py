"""The ring's gates and its plan a position: median duration of
``accl.ring::plan`` over the windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.duration_us(ctx, window_spans.PLAN)
