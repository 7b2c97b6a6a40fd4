"""The least time the chip could take for the traced steps' latent
attention CORE in the layers that have one (``mla_layers`` of the driver's
facts: ONE of the cell's seven; ``mla_core_roofline_share`` counts every
layer of its cell), forward and backward (``flops_ling3.py``: scores over
192 real columns and values over 128 by ``flops.py``'s product count over
the bf16 peak, or bytes over the HBM peak, whichever is larger: compute),
over the device time of the flash kernels under ``accl.attn::mla``, %.
Padding columns count as nothing, so padding shows; so does ``remat``,
whose second ``flash_fwd`` is in the time and not in the count."""

from perfbench import flops, flops_ling3
from perfbench.layer_metrics import _afmoe, _ling3


def read(ctx):
    found = _afmoe.flash_ns(ctx, _ling3.MLA_CORE)
    f = ctx["facts"]
    steps, layers = f.get("traced_steps"), (f.get("mixers") or {}).get("mla_layers")
    if found is None or not steps or not layers:
        return None
    cfg = ctx["cell"]["config"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_ling3.core_train_flops(cfg, f["seq"]),
        calls * flops_ling3.core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
