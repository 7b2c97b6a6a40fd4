"""Of the window's program call, the client's own execute: the runtime's
ONE execute event (``runtime_spans.EXECUTE``) inside ``accl.ring::program``
on its thread; median over the windows, us a window.  None where the
trace holds no such event."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.window_execute)
