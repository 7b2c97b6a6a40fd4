"""The least time the chip could take for the traced steps' sliding-window
attention, forward and backward (``flops_afmoe.py``: the in-window pairs
``T W - W (W - 1) / 2`` a head by ``flops.py``'s product count over the
bf16 peak, or bytes over the HBM peak, whichever is larger: compute),
over the device time of the flash kernels under ``accl.attn::window``, %.
A kernel that only masked the window would read about 70/136 of this."""

from perfbench import flops, flops_afmoe
from perfbench.layer_metrics import _afmoe


def read(ctx):
    found = _afmoe.flash_ns(ctx, _afmoe.WINDOW)
    steps = ctx["facts"].get("traced_steps")
    if found is None or not steps:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    sliding = sum(w is not None for w in flops_afmoe.layer_windows(cfg))
    calls = steps * f["batch"] * sliding
    least, _bound = flops.roofline_seconds(
        calls * flops_afmoe.attention_train_flops(
            cfg, f["seq"], cfg["sliding_window"]
        ),
        calls * flops_afmoe.attention_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
