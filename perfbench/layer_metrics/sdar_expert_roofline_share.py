"""The least time the chip could take for the traced steps' grouped
matmuls over the HELD entries, forward and backward (``flops_sdar.py``:
operations and bytes of the counted held entries, over the bf16 peak or
the HBM peak, whichever is LARGER), over the device time under
``accl.moe::experts``, %.  At about 1,024 rows an expert of 2048 x 768 the
compute bound holds (2.4 ms a layer against 1.6 ms of bytes)."""

from perfbench import flops, flops_sdar
from perfbench.layer_metrics import _afmoe, _moe


def read(ctx):
    found = _moe.times(ctx)
    steps = ctx["facts"].get("traced_steps")
    held = _afmoe.held_entries_a_step(ctx)
    if (found is None or not steps or held is None
            or "diffusion" not in ctx["facts"]):
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg = ctx["cell"]["config"]
    least, _bound = flops.roofline_seconds(
        steps * flops_sdar.expert_train_flops(cfg, held),
        steps * flops_sdar.expert_train_bytes(cfg, held),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
