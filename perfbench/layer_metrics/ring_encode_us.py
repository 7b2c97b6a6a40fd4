"""The wait on operands an in-flight window writes, then the slot rows,
counters, park and introspection under the ring's lock:
``accl.ring::deps`` + ``accl.ring::encode``; median over the windows, us
a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_window_us(ctx, window_spans.deps_encode)
