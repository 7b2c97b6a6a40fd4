"""FLOP/s utilization of the Nemotron-3 step ON THIS CHIP: FLOPs its model
does a token from shapes and from the counted held entries
(``flops_nemotron3.py``: 6 x the matmul parameters a token passes here, the
routed experts by the entries held, the SSD core by its chunked form's count
in its five blocks and causal attention in its one; the flash backward's
rebuilt scores and ``remat``'s second forward NOT counted) times this run's
tokens/s over the chip's bf16 peak, %: the share of the whole step."""

from perfbench import flops_nemotron3
from perfbench.layer_metrics import _afmoe


def read(ctx):
    f = ctx["facts"]
    rate = f.get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None or "mamba_layers" not in (f.get("mixers") or {}):
        return None
    per_token = flops_nemotron3.train_flops_per_token(
        ctx["cell"]["config"], f["seq"], held / f["tokens_per_step"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
