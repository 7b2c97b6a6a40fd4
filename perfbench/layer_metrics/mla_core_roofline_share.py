"""The least time the chip could take for the traced steps' latent
attention CORE, forward and backward (``flops_deepseek_v2.py``: scores
over 192 columns and values over 128 by ``flops.py``'s product count over
the bf16 peak, or bytes over the HBM peak, whichever is larger: compute),
over the device time of the flash kernels under ``accl.attn::mla``, %.
Padding columns count as nothing (64 rope columns fill a 128-lane tile),
so padding shows; so does ``remat``, whose second ``flash_fwd`` is in the
time and not in the count."""

from perfbench import flops, flops_deepseek_v2
from perfbench.layer_metrics import _afmoe, _dsv2


def read(ctx):
    found = _afmoe.flash_ns(ctx, _dsv2.CORE)
    steps = ctx["facts"].get("traced_steps")
    if found is None or not steps:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * cfg["num_hidden_layers"]
    least, _bound = flops.roofline_seconds(
        calls * flops_deepseek_v2.core_train_flops(cfg, f["seq"]),
        calls * flops_deepseek_v2.core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
