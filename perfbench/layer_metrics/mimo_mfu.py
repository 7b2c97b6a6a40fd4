"""FLOP/s utilization of the MiMo-V2.5 step: FLOPs its model does a token on
this chip from shapes (``flops_mimo.py``: 6 x the resident matmul parameters,
6 x an expert's for each COUNTED held routing entry, attention by the pairs a
query sees, in-window on the sliding layers; the tiles' masked pairs, the
flash backward's rebuilt scores and ``remat``'s second forward NOT counted)
times this run's tokens/s over the chip's bf16 peak, %: the share of the
whole step."""

from perfbench import flops_mimo
from perfbench.layer_metrics import _afmoe, _mimo


def read(ctx):
    f = ctx["facts"]
    rate = f.get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None or _mimo.layers(ctx, True) is None:
        return None
    per_token = flops_mimo.train_flops_per_token(
        ctx["cell"]["config"], f["seq"], held / f["tokens_per_step"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
