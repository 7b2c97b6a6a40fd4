"""Device time under ``accl.embed::grad`` (the embedding lookup's
cotangent placed on the table: one matmul against the ids' one-hot, or
XLA's scatter-add, with what the compiler fuses behind either) over
device busy time, traced steps, %."""

from perfbench import scope_ops
from perfbench.layer_metrics import _common

SCOPE = "accl.embed::grad"


def read(ctx):
    sl = _common.slice_of(ctx, "steps")
    names = (ctx["facts"].get("scope_ops") or {}).get(SCOPE)
    if sl is None or not names:
        return None
    busy = _common.busy_ns(sl)
    ns = scope_ops.scope_ns(sl["reduced"], {SCOPE: names}).get(SCOPE, 0.0)
    return 100.0 * ns / busy if busy > 0 and ns > 0 else None
