"""A layer's mixer, one module each, chosen ONCE by name: ``MIXERS`` maps
what ``LayerKind.mixer`` (or ``TransformerConfig.mixer``) names to the module
that holds that mixer's description and the same five functions,

* ``check(cfg, kind, i)``: what a configuration must hold for it (``kind``
  None: its description alone, before any layer; else layer ``i``);
* ``specs(cfg, kind)`` and ``init(key, cfg, kind)``: its leaves of a layer's
  tree, their partition specs and their seeded values (``key``: the layer's
  two mixer keys);
* ``bind(cfg, kind, tp_axis, tp_size)``: the function ``(h, lp) ->
  (partial_o, kv)`` of a layer of ``kind``, everything the shapes do not say
  bound from ``cfg`` (``kv``: what a prefill would cache, or ``None``);
* ``plain(cfg, kind)``: ``None`` where the decode, context- and
  sequence-parallel blocks, the encoder and the pipelines have a form for
  it, else the name they refuse it by.

``"none"`` is no entry: the absence of a mixer is the block's business
(``ln1`` / ``ln1_post`` in the tree).  ``models/transformer.py`` asks this
table and nothing else; a mixer imports ``ops/``, ``utils/`` and
``models/layers.py``, never ``transformer``.  A new mixer is one module
here and one entry below."""

from . import attention, kda, latent, mamba2

MIXERS = {
    "attention": attention, "latent": latent, "kda": kda, "mamba2": mamba2,
}
