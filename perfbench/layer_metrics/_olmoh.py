"""What the Olmo Hybrid cell's readers share: the test that a run is that
cell's program (its driver's ``mixers`` fact counts Gated DeltaNet and
full-attention layers), and the device scopes.  The time under the delta
core's scope is ``_ling3.scope_time``'s (the union of the scope's events
over the driver's ``scope_ops_all``), the flash kernels' under
``accl.attn::core`` ``_afmoe.flash_ns``'s.  Under ``remat`` the recomputed
forward carries the same scopes, so a TIME share includes the
recomputation; the roofline shares and ``olmoh_mfu`` count what the model
does once.  A program without the scopes or the fact (the parent's) gives
the readers nothing to read, and they return None."""

from perfbench.layer_metrics._afmoe import CORE as ATTN_CORE  # noqa: F401
from perfbench.layer_metrics._ling3 import CORE as GDN_CORE  # noqa: F401


def layers(ctx, kind: str):
    """How many ``kind`` (``"linear_layers"``, ``"full_layers"``) layers the
    run's program has; None where it is not this cell's."""
    mixers = ctx["facts"].get("mixers") or {}
    return mixers.get(kind) if "linear_layers" in mixers else None
