"""What the afmoe cell's readers share: the counted held entries and the
flash kernels' device time by attention scope."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common

WINDOW, CORE = "accl.attn::window", "accl.attn::core"


def held_entries_a_step(ctx):
    """Routing entries held on this chip in one step, summed over the
    expert layers: the program's router probe on the first batch at the
    seeded weights (the other batches are drawn alike); None where the
    program counts none."""
    held = (ctx["facts"].get("router") or {}).get("held_entries")
    return float(sum(held)) if held else None


def flash_ns(ctx, scope: str):
    """``(device ns of the flash_* kernels under ``scope``, busy ns)`` of
    the traced steps, or None where the run has no such slice or the
    program no such scope."""
    sl = _common.slice_of(ctx, "steps")
    names = (ctx["facts"].get("scope_ops") or {}).get(scope)
    if sl is None or not names:
        return None
    kernels = [n for n in names if "flash_" in n]
    busy = _common.busy_ns(sl)
    if not kernels or busy <= 0:
        return None
    ns = scope_ops.scope_ns(sl["reduced"], {scope: kernels}).get(scope, 0.0)
    return (ns, busy) if ns > 0 else None
