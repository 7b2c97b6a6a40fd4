"""The one name under which a rematerialised block keeps a value.

``models.transformer._layer_blocks`` rematerialises a block (``cfg.remat``)
under ``save_only_these_names(KEPT_UNDER_REMAT)``; what a mixer, or a core's
``custom_vjp`` forward rule, passes through :func:`kept_under_remat` is saved
where it is first made and everything else of the block's forward is run
again on the backward pass.  The name lives here, below ``models/`` and
``ops/`` alike, because both name values: the mixers their projections, the
flash core's forward rule its two outputs."""

from jax.ad_checkpoint import checkpoint_name

#: the name (``jax.ad_checkpoint.checkpoint_name``) of what a block keeps of
#: its forward when it is rematerialised: the two recurrent mixers' bf16
#: input projections, a softmax mixer's q, k, v as its core takes them, and
#: the flash core's ``o`` and ``lse``.  Without ``remat`` the name is the
#: identity.
KEPT_UNDER_REMAT = "accl.remat::mixer_proj"


def kept_under_remat(value):
    """``value`` under the name ``_layer_blocks``' checkpoint policy saves:
    the identity but inside a rematerialised block, whose backward then reads
    this array where it would have computed it again."""
    return checkpoint_name(value, KEPT_UNDER_REMAT)
