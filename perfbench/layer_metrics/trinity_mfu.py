"""FLOP/s utilization of the Trinity step ON THIS CHIP: FLOPs it executes
a token from shapes and from the counted held entries
(``flops_afmoe.py``: 6 x the matmul parameters a token passes here, the
routed experts by the entries held, window-exact attention, no
recomputation) times this run's tokens/s over the chip's bf16 peak, %."""

from perfbench import flops_afmoe
from perfbench.layer_metrics import _afmoe


def read(ctx):
    rate = ctx["facts"].get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None:
        return None
    f = ctx["facts"]
    per_token = flops_afmoe.train_flops_per_token(
        ctx["cell"]["config"], f["seq"], held / f["tokens_per_step"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
