"""The least time the chip could take for the traced steps' grouped
matmuls over the HELD entries, forward and backward (``flops_afmoe.py``:
operations and bytes of the counted held entries, over the bf16 peak or
the HBM peak, whichever is larger), over the device time under
``accl.moe::experts``, %.  ``moe_expert_roofline_share`` would count all
eight experts of every token here and read eight times too high."""

from perfbench import flops, flops_afmoe
from perfbench.layer_metrics import _afmoe, _moe


def read(ctx):
    found = _moe.times(ctx)
    steps = ctx["facts"].get("traced_steps")
    held = _afmoe.held_entries_a_step(ctx)
    if found is None or not steps or held is None:
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg = ctx["cell"]["config"]
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    least, _bound = flops.roofline_seconds(
        steps * flops_afmoe.expert_train_flops(cfg, held),
        steps * flops_afmoe.expert_train_bytes(cfg, held, layers),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
