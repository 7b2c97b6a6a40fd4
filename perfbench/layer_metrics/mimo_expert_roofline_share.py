"""The least time the chip could take for the traced steps' grouped matmuls
over the HELD entries, forward and backward (``flops_mimo.py``: operations
and bytes of the counted held entries, about 256 rows an expert in a bank
4,096 x 2,048 wide, over the bf16 peak or the HBM peak, whichever is
larger), over the device time under ``accl.moe::experts``, %."""

from perfbench import flops, flops_mimo
from perfbench.layer_metrics import _afmoe, _mimo, _moe


def read(ctx):
    found = _moe.times(ctx)
    steps = ctx["facts"].get("traced_steps")
    held = _afmoe.held_entries_a_step(ctx)
    if found is None or not steps or held is None or not _mimo.layers(ctx, True):
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg = ctx["cell"]["config"]
    layers = ctx["facts"]["mixers"]["expert_layers"]
    least, _bound = flops.roofline_seconds(
        steps * flops_mimo.expert_train_flops(cfg, held),
        steps * flops_mimo.expert_train_bytes(cfg, held, layers),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
