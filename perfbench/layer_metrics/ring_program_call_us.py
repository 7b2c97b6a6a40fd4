"""The window program's lookup and its one call: median duration of
``accl.ring::program`` (inside ``accl::cmdring[n]``, after the slot
words are put) over the windows, us a window."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.program_call)
