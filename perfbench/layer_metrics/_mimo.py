"""What the MiMo-V2.5 cell's readers share: the test that a run is that
cell's program (its driver's ``mixers`` fact counts sliding and full layers),
the two attention scopes, and a flash core's roofline share by layer kind.
The flash kernels' time under a scope is ``_afmoe.flash_ns``'s.  Under
``remat`` the recomputed forward carries the same scopes, so a TIME share
includes the recomputation; the roofline shares and ``mimo_mfu`` count what
the model does once.  A program without the scopes or the fact (the parent's)
gives the readers nothing to read, and they return None."""

from perfbench import flops, flops_mimo
from perfbench.layer_metrics import _afmoe

SCOPES = {True: _afmoe.WINDOW, False: _afmoe.CORE}


def layers(ctx, swa: bool):
    """How many sliding (``swa``) or full layers the run's program has; None
    where it is not this cell's."""
    mixers = ctx["facts"].get("mixers") or {}
    return mixers.get("swa_layers" if swa else "full_layers")


def core_roofline_share(ctx, swa: bool):
    """The least time the chip could take for the traced steps' attention
    cores of that kind, forward and backward (``flops_mimo.py``: the pairs a
    query SEES at scores over 192 columns and values of 128, over the bf16
    peak, or bytes over the HBM peak, whichever is larger), over the device
    time of the flash kernels under the kind's scope, %."""
    n = layers(ctx, swa)
    steps = ctx["facts"].get("traced_steps")
    if not n or not steps:
        return None
    found = _afmoe.flash_ns(ctx, SCOPES[swa])
    if found is None:
        return None
    cfg, f = ctx["cell"]["config"], ctx["facts"]
    calls = steps * f["batch"] * n
    least, _bound = flops.roofline_seconds(
        calls * flops_mimo.attention_train_flops(cfg, f["seq"], swa),
        calls * flops_mimo.attention_train_bytes(cfg, f["seq"], swa),
        ctx["peaks"],
    )
    return 100.0 * least * 1e9 / found[0]
