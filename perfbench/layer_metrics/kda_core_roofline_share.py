"""The least time the chip could take for the traced steps' KDA core,
forward and backward (``flops_ling3.py``: the chunked form's operations at
a chunk of 64 over the bf16 peak, or its least bytes over the HBM peak,
whichever is LARGER: bytes, 13.7 GB a step of two sequences through six
KDA layers against 1.31 TFLOP), over the
device time under ``accl.attn::kda``, %.  Defined on the scope and the
mathematics, so that a later kernel is read against the same work;
``remat``'s second forward is in the time and not in the count."""

from perfbench import flops, flops_ling3
from perfbench.layer_metrics import _ling3


def read(ctx):
    found = _ling3.scope_time(ctx, _ling3.CORE)
    f = ctx["facts"]
    steps, layers = f.get("traced_steps"), (f.get("mixers") or {}).get("kda_layers")
    if found is None or not steps or not layers:
        return None
    cfg = ctx["cell"]["config"]
    calls = steps * f["batch"] * layers
    least, _bound = flops.roofline_seconds(
        calls * flops_ling3.kda_core_train_flops(cfg, f["seq"]),
        calls * flops_ling3.kda_core_train_bytes(cfg, f["seq"]),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
