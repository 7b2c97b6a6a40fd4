"""The least time the chip could take for the traced steps' causal
attention, forward and backward (``flops.py``: operations over the bf16
peak or bytes over the HBM peak, whichever is larger — compute, at these
lengths), over the summed device time of the flash kernels' events, %."""

from perfbench import flops, trace_reduce
from perfbench.layer_metrics import _common


def read(ctx):
    sl = _common.slice_of(ctx, "steps")
    steps = ctx["facts"].get("traced_steps")
    if sl is None or not steps:
        return None
    kernel = trace_reduce.kernel_ns(sl["reduced"], _common.is_flash)
    if kernel <= 0:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * cfg["n_layer"]
    least, _bound = flops.roofline_seconds(
        calls * flops.attention_train_flops(cfg, f["seq"]),
        calls * flops.attention_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / kernel
