"""The least time the chip could take for the traced steps' attention
core under the block-diffusion layout, forward and backward
(``flops_sdar.py``: the LIVE (query, key) pairs by ``flops.py``'s product
count over the bf16 peak, or bytes over the HBM peak, whichever is larger:
compute), over the device time of the flash kernels under
``accl.attn::blockdiff``, %.  A masked tile is computed whole and counts
for its live pairs only (0.8% of a noisy-on-noisy tile at blocks of 4, half
of the two others), so the dead area of the masked tiles shows."""

from perfbench import flops, flops_sdar
from perfbench.layer_metrics import _afmoe, _sdar


def read(ctx):
    found = _afmoe.flash_ns(ctx, _sdar.CORE)
    steps = ctx["facts"].get("traced_steps")
    if found is None or not steps:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * cfg["num_hidden_layers"]
    least, _bound = flops.roofline_seconds(
        calls * flops_sdar.core_train_flops(cfg, f["seq"]),
        calls * flops_sdar.core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
