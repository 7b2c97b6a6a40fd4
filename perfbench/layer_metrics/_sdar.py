"""What the SDAR cell's readers share: the device scopes of the
block-diffusion objective and the device time under some of them."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common

CORE = "accl.attn::blockdiff"
NOISE, LOSS = "accl.diffusion::noise", "accl.loss::diffusion"


def scope_share(ctx, scopes):
    """Device time of the traced steps under ``scopes`` over busy time, %;
    None where the run has no such slice or the program none of them."""
    sl = _common.slice_of(ctx, "steps")
    names = ctx["facts"].get("scope_ops") or {}
    names = {s: names[s] for s in scopes if names.get(s)}
    if sl is None or not names:
        return None
    busy = _common.busy_ns(sl)
    ns = sum(scope_ops.scope_ns(sl["reduced"], names).values())
    return 100.0 * ns / busy if busy > 0 and ns > 0 else None
