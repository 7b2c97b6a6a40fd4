"""Median duration of the gang engine's ``accl::<op>`` host spans in the
small phase, us."""

from perfbench import trace_reduce
from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "small")
    if sl is None:
        return None
    return _common.median_us(
        trace_reduce.span_durations_ns(sl["reduced"], "accl::")
    )
