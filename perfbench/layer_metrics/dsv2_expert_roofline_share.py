"""The least time the chip could take for the traced steps' grouped
matmuls over the HELD entries, forward and backward
(``flops_deepseek_v2.py``: operations and bytes of the counted held
entries, over the bf16 peak or the HBM peak, whichever is LARGER), over
the device time under ``accl.moe::experts``, %.  At 154 rows an expert
the bytes bound holds: an expert's 47 MB of weights pass once for each of
the nine matmuls, 3.9 ms a layer against 2.2 ms of compute.  ``remat``'s
second forward (three more matmuls a layer) is in the time and not in
the count."""

from perfbench import flops, flops_deepseek_v2
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
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least, _bound = flops.roofline_seconds(
        steps * flops_deepseek_v2.expert_train_flops(cfg, held),
        steps * flops_deepseek_v2.expert_train_bytes(cfg, held, layers),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
