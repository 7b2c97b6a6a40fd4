"""How long the chips wait for the window's program call: first device
op's start on any chip minus ``accl.ring::program``'s start, on the
profiler's one clock; median over the windows, us a window.  Not
clamped: a negative reading says the device started before the span."""

from perfbench import runtime_spans


def read(ctx):
    return runtime_spans.per_window_us(ctx, runtime_spans.launch_lag)
