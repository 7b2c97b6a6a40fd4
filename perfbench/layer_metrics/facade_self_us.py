"""Facade self time of a small blocking call: the benchmark's span around
the call (intake to output ready) minus the engine's ``accl::<op>`` span
inside it, on the rank thread that ran the device program; median, us."""

from perfbench import trace_reduce
from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "small")
    if sl is None:
        return None
    return _common.median_us(
        trace_reduce.nested_self_ns(sl["reduced"], "bench::small::", "accl::")
    )
