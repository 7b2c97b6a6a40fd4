"""FLOP/s utilization of the Olmo Hybrid step: FLOPs its model does a token
from shapes (``flops_olmoh.py``: 6 x the matmul parameters a token passes,
the Gated DeltaNet core by the SCALAR chunked form's count at heads of 96 /
192 in its three layers and causal attention in its one; the flash
backward's rebuilt scores, ``remat``'s second forward and the products that
padded heads and the split by halving add NOT counted) times this run's
tokens/s over the chip's bf16 peak, %: the share of the whole step."""

from perfbench import flops_olmoh
from perfbench.layer_metrics import _olmoh


def read(ctx):
    f = ctx["facts"]
    rate = f.get("tokens_per_s")
    if not rate or _olmoh.layers(ctx, "linear_layers") is None:
        return None
    per_token = flops_olmoh.train_flops_per_token(ctx["cell"]["config"], f["seq"])
    return 100.0 * per_token * rate / ctx["peaks"]["bf16_flops_per_s"]
