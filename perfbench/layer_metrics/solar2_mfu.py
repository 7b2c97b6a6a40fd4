"""FLOP/s utilization of the Solar Open 2 step ON THIS CHIP: FLOPs its model
does a token from shapes and from the counted held entries
(``flops_solar2.py``: 6 x the matmul parameters a token passes here, the
routed experts by the entries held, the KDA core by its chunked form's count
in its three layers and causal attention in its one; the flash backward's
rebuilt scores, ``remat``'s second forward and the products the split by
halving adds NOT counted) times this run's tokens/s over the chip's bf16
peak, %: the share of the whole step."""

from perfbench import flops_solar2
from perfbench.layer_metrics import _afmoe, _solar2


def read(ctx):
    f = ctx["facts"]
    rate = f.get("tokens_per_s")
    held = _afmoe.held_entries_a_step(ctx)
    if not rate or held is None or _solar2.layers(ctx, "kda_layers") is None:
        return None
    per_token = flops_solar2.train_flops_per_token(
        ctx["cell"]["config"], f["seq"], held / f["tokens_per_step"]
    )
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
