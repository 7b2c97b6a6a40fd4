"""Queueing of a batched window on a rank's calling thread: start of its
``accl.batch::flush`` minus the start of its ``bench::window`` (eight
async collectives taken in and queued); median over every rank thread's
window of the window slice, us a window."""

from perfbench import window_spans


def read(ctx):
    return window_spans.per_rank_window_us(ctx, window_spans.queue)
