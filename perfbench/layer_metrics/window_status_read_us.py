"""After the wait, before the window is known done: ``accl.ring::status``
(the status words' device-to-host read) plus ``accl.ring::settle`` (the
session's ledger under the ring's lock, and the event); median over the
windows, us a window."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.status_read)
