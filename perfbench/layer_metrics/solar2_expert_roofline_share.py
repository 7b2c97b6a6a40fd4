"""The least time the chip could take for the traced steps' grouped matmuls
over the HELD entries, forward and backward (``flops_solar2.py``: operations
and bytes of the counted held entries, over the bf16 peak or the HBM peak,
whichever is LARGER), over the device time under ``accl.moe::experts``, %.
At about 205 rows an expert of 4096 x 1280 the bytes bound holds: a layer's
40 x 10.5 MB matrices pass once for each of the nine matmuls.  ``remat``'s
second forward (three more matmuls a layer) is in the time and not in the
count."""

from perfbench import flops, flops_solar2
from perfbench.layer_metrics import _afmoe, _moe, _solar2


def read(ctx):
    found = _moe.times(ctx)
    steps = ctx["facts"].get("traced_steps")
    held = _afmoe.held_entries_a_step(ctx)
    if (found is None or not steps or held is None
            or _solar2.layers(ctx, "kda_layers") is None):
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg = ctx["cell"]["config"]
    least, _bound = flops.roofline_seconds(
        steps * flops_solar2.expert_train_flops(cfg, held),
        steps * flops_solar2.expert_train_bytes(cfg, held),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
