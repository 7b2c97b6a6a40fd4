"""Device time under ``accl.attn::latent`` (the latent mixer's five
projections, its two norms and the rope, forward, ``remat``'s second
forward and backward) over device busy time, traced steps, %."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common, _dsv2


def read(ctx):
    sl = _common.slice_of(ctx, "steps")
    names = (ctx["facts"].get("scope_ops") or {}).get(_dsv2.LATENT)
    if sl is None or not names:
        return None
    busy = _common.busy_ns(sl)
    ns = scope_ops.scope_ns(sl["reduced"], {_dsv2.LATENT: names}).get(
        _dsv2.LATENT, 0.0
    )
    return 100.0 * ns / busy if busy > 0 and ns > 0 else None
