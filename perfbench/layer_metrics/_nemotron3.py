"""What the Nemotron-3 cell's readers share: its device scopes.  The time
under one is ``_ling3.scope_time``'s, the UNION of the scope's events'
intervals over the driver's ``scope_ops_all`` (every computation of the
step's text: the SSD core's scan over the chunks is a LOOP whose body's
instructions are device events of their own).  Under ``remat`` the
recomputed forward carries the same scopes, so a TIME share includes the
recomputation; the roofline shares and ``nemotron3_mfu`` count what the
model does once.  A program without the scopes (the parent's) gives the
readers nothing to read, and they return None."""

from perfbench.layer_metrics._ling3 import scope_time, share  # noqa: F401

CORE, PROJ = "accl.attn::ssd", "accl.attn::mamba_proj"
LATENT = "accl.moe::latent"
