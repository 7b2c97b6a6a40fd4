"""A window's arrival spread: latest minus earliest ``accl.batch::submit``
start of its rank threads (``window_spans.arrival_spread``: the gate's
release, ``batch_gate_spread_us``, and the threads' turns at queueing
eight calls each); median over the windows, us a window."""

from perfbench import runtime_spans, window_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, window_spans.arrival_spread)
