"""Rank rendezvous of a batched window, the program's own part: start of
``accl.ring::batch`` (the gang slot is complete and executes) minus the
LATEST of the four ``accl.batch::submit`` starts; median over the
windows, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_window_us(ctx, window_spans.rendezvous)
