"""The least time the chip could take for the traced steps' causal
attention in the softmax layer, forward and backward (``flops_solar2.py``:
the pairs ``j <= i`` a query head by the product count ``flash_roofline_
share`` uses over the bf16 peak, or bytes over the HBM peak, whichever is
larger: compute), over the device time of the flash kernels under
``accl.attn::core``, %.  ``remat``'s second forward and the backward's
rebuilt scores are in the time and not in the count."""

from perfbench import flops, flops_solar2
from perfbench.layer_metrics import _afmoe, _solar2


def read(ctx):
    layers = _solar2.layers(ctx, "gqa_layers")
    steps = ctx["facts"].get("traced_steps")
    if not layers or not steps:
        return None
    found = _afmoe.flash_ns(ctx, _solar2.GQA_CORE)
    if found is None:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_solar2.gqa_core_train_flops(cfg, f["seq"]),
        calls * flops_solar2.gqa_core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
