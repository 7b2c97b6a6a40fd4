"""Device events of the traced steps whose instruction is named
``flash_fwd...`` over those named ``flash_bwd...`` (the kernels' ``name=``,
which the compiler numbers): how many times a step runs an attention core's
forward for each backward.  2 where a rematerialised block replays the core,
1 where it keeps the core's ``o`` and ``lse``; None (the metric left out)
where the steps ran no such kernel."""

from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "steps")
    if sl is None:
        return None
    names = [
        name for events in sl["reduced"]["devices"].values()
        for name, _, _ in events
    ]
    backward = sum(name.startswith("flash_bwd") for name in names)
    if not backward:
        return None
    return sum(name.startswith("flash_fwd") for name in names) / backward
