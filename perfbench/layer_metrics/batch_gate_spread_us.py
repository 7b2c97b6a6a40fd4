"""The benchmark's own share of a window's arrival spread: latest minus
earliest ``bench::window`` start of the window's rank threads (each
opens its span as the gate releases it); median over the windows, us a
window."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.gate_spread)
