"""Device time of the flash kernels over device busy time, traced steps, %."""

from perfbench import trace_reduce
from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "steps")
    if sl is None:
        return None
    busy = _common.busy_ns(sl)
    kernel = trace_reduce.kernel_ns(sl["reduced"], _common.is_flash)
    return 100.0 * kernel / busy if busy > 0 and kernel > 0 else None
