"""The hand-over of a launched window to the drainer:
``accl.window::ready``'s start minus ``accl.ring::park``'s end; median over
the windows, us a window.  Not clamped: the drainer may enter ``ready``
before the launching thread's ``park`` span closes."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.window_pickup)
