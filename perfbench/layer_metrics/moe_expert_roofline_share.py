"""The least time the chip could take for the traced steps' grouped
matmuls, forward and backward (``flops_olmoe.py``: operations over the
bf16 peak or bytes over the HBM peak, whichever is larger: compute, at
1,024 tokens an expert), over the device time under
``accl.moe::experts`` (the grouped-matmul kernels and the gate product),
%."""

from perfbench import flops, flops_olmoe
from perfbench.layer_metrics import _moe


def read(ctx):
    found = _moe.times(ctx)
    steps = ctx["facts"].get("traced_steps")
    if found is None or not steps:
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * cfg["num_hidden_layers"]
    tokens = f["tokens_per_step"]
    least, _bound = flops.roofline_seconds(
        calls * flops_olmoe.expert_train_flops(cfg, tokens),
        calls * flops_olmoe.expert_train_bytes(cfg, tokens),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
