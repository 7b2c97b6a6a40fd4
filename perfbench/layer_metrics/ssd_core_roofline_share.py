"""The least time the chip could take for the traced steps' SSD core,
forward and backward (``flops_nemotron3.py``: the chunked form's operations
at the published chunk of 128 over the bf16 peak, or its least bytes over
the HBM peak, whichever is LARGER: bytes, 0.78 GB a sequence a block against
0.13 TFLOP), over the device time under ``accl.attn::ssd``, %.  Defined on
the scope and the mathematics, so that a later kernel is read against the
same work; ``remat``'s second forward is in the time and not in the count."""

from perfbench import flops, flops_nemotron3
from perfbench.layer_metrics import _nemotron3


def read(ctx):
    found = _nemotron3.scope_time(ctx, _nemotron3.CORE)
    f = ctx["facts"]
    steps = f.get("traced_steps")
    layers = (f.get("mixers") or {}).get("mamba_layers")
    if found is None or not steps or not layers:
        return None
    cfg = ctx["cell"]["config"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_nemotron3.ssd_core_train_flops(cfg, f["seq"]),
        calls * flops_nemotron3.ssd_core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
