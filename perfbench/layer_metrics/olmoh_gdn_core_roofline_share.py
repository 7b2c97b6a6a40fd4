"""The least time the chip could take for the traced steps' Gated DeltaNet
core, forward and backward (``flops_olmoh.py``: the SCALAR chunked form's
operations at a chunk of 64 and 30 heads of 96 / 192 over the bf16 peak, or
its least bytes over the HBM peak, whichever is LARGER), over the device
time under ``accl.attn::kda`` (the union of its events), %.  Defined on the
scope and the mathematics, so that whatever implements the core (heads
padded into the KDA kernels today, a kernel of the scalar form later) is
read against the same work; ``remat``'s second forward is in the time and
not in the count."""

from perfbench import flops, flops_olmoh
from perfbench.layer_metrics import _ling3, _olmoh


def read(ctx):
    found = _ling3.scope_time(ctx, _olmoh.GDN_CORE)
    f = ctx["facts"]
    steps, layers = f.get("traced_steps"), _olmoh.layers(ctx, "linear_layers")
    if found is None or not steps or not layers:
        return None
    cfg = ctx["cell"]["config"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_olmoh.gdn_core_train_flops(cfg, f["seq"]),
        calls * flops_olmoh.gdn_core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
