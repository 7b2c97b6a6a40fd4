"""Device time under ``accl.attn::gqa_proj`` in the MiMo-V2.5 cell: what is
round the seven attention cores (q, k and v's projections, the heads'
reshape, the value scale, the split into the part that rotates and the part
without position, their rope, the sink's cast, the transpose back and
``wo``; forward, ``remat``'s second forward and backward, with what the
compiler fuses behind them) over device busy time, traced steps, %."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common, _mimo

SCOPE = "accl.attn::gqa_proj"


def read(ctx):
    if not _mimo.layers(ctx, True):
        return None
    sl = _common.slice_of(ctx, "steps")
    names = (ctx["facts"].get("scope_ops") or {}).get(SCOPE)
    if sl is None or not names:
        return None
    busy = _common.busy_ns(sl)
    ns = scope_ops.scope_ns(sl["reduced"], {SCOPE: names}).get(SCOPE, 0.0)
    return 100.0 * ns / busy if busy > 0 and ns > 0 else None
