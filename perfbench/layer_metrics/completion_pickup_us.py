"""The hand-over of a launched gang call to the drainer:
``accl.window::ready``'s start minus ``accl.gang::park``'s end; median over
the gang calls, us.  Not clamped: the drainer may enter ``ready`` before
the launching thread's ``park`` span closes."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_call_us(ctx, runtime_spans.completion_pickup)
