"""What the MoE readers share: device time of the traced steps by
``accl.moe::`` scope (``perfbench/scope_ops.py``), and busy time."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common

MOE = "accl.moe::"


def times(ctx):
    """``(ns by scope, busy ns)`` of the traced steps, or None where the
    run has no such slice or the program no such scopes."""
    sl = _common.slice_of(ctx, "steps")
    names = ctx["facts"].get("scope_ops")
    if sl is None or not names:
        return None
    by_scope = scope_ops.scope_ns(sl["reduced"], names)
    busy = _common.busy_ns(sl)
    if busy <= 0 or not any(s.startswith(MOE) for s in by_scope):
        return None
    return by_scope, busy


def share(ctx, stages):
    """Device time under the ``accl.moe::<stage>`` scopes named (all of
    them where ``stages`` is None) over busy time, %."""
    found = times(ctx)
    if found is None:
        return None
    by_scope, busy = found
    ns = sum(
        v for s, v in by_scope.items()
        if s.startswith(MOE) and (stages is None or s[len(MOE):] in stages)
    )
    return 100.0 * ns / busy
