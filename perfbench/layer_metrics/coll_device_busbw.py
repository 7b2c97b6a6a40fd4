"""nccl-tests bus bytes of the large calls that ran inside the trace
slice over the seconds in which an op ran on a device there (union of
the op line's events, averaged over the chips), GB/s.  In the large
phase the devices run nothing but the collective programs."""

from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "large")
    nbytes = ctx["facts"].get("traced_large_bus_bytes")
    if sl is None or not nbytes:
        return None
    busy = _common.busy_ns(sl)
    return nbytes / busy if busy > 0 else None
