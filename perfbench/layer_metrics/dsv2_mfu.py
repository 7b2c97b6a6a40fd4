"""FLOP/s utilization of the DeepSeek-V2 step ON THIS CHIP: FLOPs its
model does a token from shapes and from the counted held entries
(``flops_deepseek_v2.py``: 6 x the matmul parameters a token passes here,
the routed experts by the entries held, the attention core at its real
widths; the flash backward's rebuilt scores and ``remat``'s second
forward NOT counted) times this run's tokens/s over the chip's bf16
peak, %."""

from perfbench import flops_deepseek_v2
from perfbench.layer_metrics import _afmoe


def read(ctx):
    rate = ctx["facts"].get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None:
        return None
    f = ctx["facts"]
    per_token = flops_deepseek_v2.train_flops_per_token(
        ctx["cell"]["config"], f["seq"], held / f["tokens_per_step"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
