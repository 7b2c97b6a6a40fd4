"""The least time the chip could take for the traced steps' grouped matmuls
over the HELD entries, forward and backward (``flops_nemotron3.py``:
operations and bytes of the counted held entries, two products an expert in
the 1,024-wide latent, over the bf16 peak or the HBM peak, whichever is
LARGER), over the device time under ``accl.moe::experts``, %.  ``remat``'s
second forward (two more matmuls a block) is in the time and not in the
count."""

from perfbench import flops, flops_nemotron3
from perfbench.layer_metrics import _afmoe, _moe


def read(ctx):
    found = _moe.times(ctx)
    f = ctx["facts"]
    steps = f.get("traced_steps")
    held = _afmoe.held_entries_a_step(ctx)
    if (found is None or not steps or held is None
            or "mamba_layers" not in (f.get("mixers") or {})):
        return None
    experts = found[0].get(_moe.MOE + "experts", 0.0)
    if experts <= 0:
        return None
    cfg = ctx["cell"]["config"]
    least, _bound = flops.roofline_seconds(
        steps * flops_nemotron3.expert_train_flops(cfg, held),
        steps * flops_nemotron3.expert_train_bytes(cfg, held),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / experts
