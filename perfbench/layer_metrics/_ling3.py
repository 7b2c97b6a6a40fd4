"""What the Ling-3.0 cell's readers share: the KDA mixer's device scopes
and the device time under one.  The KDA core's scan over the chunks is a
LOOP in the compiled step: its body's instructions run as device events of
their own and the loop may show as an event round them, so the time under a
scope is the UNION of its events' intervals, and the instruction names are
the driver's ``scope_ops_all`` (every computation of the step's text, not
the entry alone as ``scope_ops``).  Under ``remat`` the recomputed forward
carries the same scopes, so a TIME share includes the recomputation; the
roofline shares and ``ling3_mfu`` count what the model does once."""

from perfbench import scope_ops, trace_reduce
from perfbench.layer_metrics import _common

CORE, PROJ = "accl.attn::kda", "accl.attn::kda_proj"
MLA_CORE = "accl.attn::mla"


def scope_time(ctx, scope):
    """``(device ns under ``scope``, busy ns)`` of the traced steps,
    averaged over the devices; None where the run has no such slice or the
    program no such scope."""
    sl = _common.slice_of(ctx, "steps")
    names = (ctx["facts"].get("scope_ops_all") or {}).get(scope)
    if sl is None or not names:
        return None
    names = set(names)
    devices = sl["reduced"]["devices"]
    lo, hi = sl["window"]
    ns = sum(
        min(b, hi) - max(a, lo)
        for events in devices.values()
        for a, b in trace_reduce.merge(
            (start, start + dur) for name, start, dur in events
            if dur > 0 and scope_ops.instruction_name(name) in names
        )
        if b > lo and a < hi
    ) / max(len(devices), 1)
    busy = _common.busy_ns(sl)
    return (ns, busy) if ns > 0 and busy > 0 else None


def share(ctx, scope):
    found = scope_time(ctx, scope)
    return None if found is None else 100.0 * found[0] / found[1]
