"""The least time the chip could take for the traced steps' causal
attention in the full-attention layer, forward and backward
(``flops_olmoh.py``: the pairs ``j <= i`` a head by the product count
``flash_roofline_share`` uses over the bf16 peak, or bytes over the HBM
peak, whichever is larger: compute), over the device time of the flash
kernels under ``accl.attn::core``, %.  ``remat``'s second forward and the
backward's rebuilt scores are in the time and not in the count."""

from perfbench import flops, flops_olmoh
from perfbench.layer_metrics import _afmoe, _olmoh


def read(ctx):
    layers = _olmoh.layers(ctx, "full_layers")
    steps = ctx["facts"].get("traced_steps")
    if not layers or not steps:
        return None
    found = _afmoe.flash_ns(ctx, _olmoh.ATTN_CORE)
    if found is None:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_olmoh.attn_core_train_flops(cfg, f["seq"]),
        calls * flops_olmoh.attn_core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
