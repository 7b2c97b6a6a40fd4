"""What the DeepSeek-V2 cell's readers share: the device scopes of the
latent mixer.  Under ``remat`` the recomputed forward carries the same
scopes, so a TIME share includes the recomputation; the roofline shares
and ``dsv2_mfu`` count what the model does once and leave it out."""

LATENT, CORE = "accl.attn::latent", "accl.attn::mla"
