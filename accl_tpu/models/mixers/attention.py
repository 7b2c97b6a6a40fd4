"""The softmax mixer (MHA / MQA / GQA): ``MIXERS["attention"]``, every layer's
where a kind and the configuration do not say otherwise.  A window, a sink in
the softmax, a head geometry of its own (:class:`HeadGeometry`), the channel
gate, QK-norm of both kinds and block diffusion's layout are its.  What a
configuration must hold for it (:func:`check`), its leaves (:func:`specs`,
:func:`init`), its function for a layer (:func:`bind`, :func:`_attn_partial`)
and what the paths beside train and forward refuse of it (:func:`plain`)."""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...utils.profiling import device_scope
from ...utils.remat import kept_under_remat
from ..layers import (
    _attention,
    _check_rotation,
    _normal,
    _own_heads,
    _qk_norm,
    _rmsnorm,
    _rope_rotate,
    _rope_tables,
    _tp_specs,
)


@dataclasses.dataclass(frozen=True)
class HeadGeometry:
    """The geometry of an ``"attention"`` mixer's heads where it is not the
    plain one (``LayerKind.heads``): ``rope_dim``, the FIRST columns of a q
    or k head that rotate, the rest carrying no position (a published
    ``partial_rotary_factor``; ``None``: every column; the two parts go to
    the attention lowerings apart, the rotating one as the scores' second
    part, so a head of 128 + 64 is two operands of whole lanes to the flash
    kernels and not one of 192 padded to 256); ``v_dim``, the width of a v
    head, of the output a head and of ``wo``'s rows a head where it is not
    q's and k's (``None``: theirs); ``v_scale`` multiplies v (a published
    ``attention_value_scale``)."""

    rope_dim: Optional[int] = None
    v_dim: Optional[int] = None
    v_scale: float = 1.0


def check(cfg, kind, i) -> None:
    """What a configuration must hold for this mixer.  ``kind`` None: the
    configuration alone, whichever layers the pattern has; else layer ``i``
    of ``kind``: what a kind says of its OWN attention heads (``kv_heads``,
    ``rope_base``, ``sink``, ``heads``) is this mixer's."""
    if kind is None:
        if cfg.attn_gate == "head" and cfg.latent is None:
            raise ValueError(
                "attn_gate='head' is the latent mixer's gate; the attention "
                "mixer's is a value a channel (attn_gate=True)"
            )
        return
    if cfg.latent is not None:
        raise ValueError(
            f"layer {i}: the attention mixer in a stack whose "
            "TransformerConfig.latent is set (a "
            "stack holds the latent mixer or attention, beside "
            "KDA and Mamba-2 layers)"
        )
    _check_rotation(cfg, kind, i)
    if not _own_heads(kind):
        return
    if cfg.diffusion is not None:
        raise ValueError(
            f"layer {i}: kv_heads, rope_base, sink and heads are the "
            "attention mixer's under a causal mask, not the attention "
            "mixer's or block diffusion's"
        )
    cfg.kv_heads(kind)
    g = kind.heads
    if g is None:
        return
    hd = cfg.head_size()
    if (
        cfg.qk_norm or cfg.attn_gate or g.v_scale <= 0.0
        or (g.v_dim is not None and g.v_dim < 1)
        or (g.rope_dim is not None
            and not (0 < g.rope_dim <= hd and g.rope_dim % 2 == 0))
    ):
        raise ValueError(
            f"layer {i}: a head geometry (LayerKind.heads) rotates an "
            f"even number of a head's {hd} columns, has a v_dim of at "
            "least 1 and a v_scale above 0, and is not built beside "
            f"QK-norm or the attention gate; got {g}"
        )


def plain(cfg, kind) -> Optional[str]:
    """``None`` where the decode, context- and sequence-parallel blocks,
    the encoder and the pipelines have a form for a layer of ``kind`` (one
    cache of every earlier key on one head count, causal or bidirectional),
    else what they refuse it as.  A kind's window and rotation are the
    PATTERN's, which ``TransformerConfig.plain`` refuses whole."""
    if cfg.diffusion is not None:
        return "block diffusion (TransformerConfig.diffusion)"
    if _own_heads(kind):
        return (
            "attention heads of a layer kind's own (LayerKind.kv_heads, "
            ".rope_base), a sink in the softmax (LayerKind.sink) and a head "
            "geometry (LayerKind.heads: partial rotary, a v width of its "
            "own, a value scale)"
        )
    return None


def specs(cfg, kind) -> Dict:
    col, row, heads = _tp_specs(cfg)
    layer = {
        "wq": col,  # (d_model, heads * head_size / tp): heads sharded
        "wk": col,
        "wv": col,
        "wo": row,  # (heads * head_size / tp, d_model)
    }
    if kind.sink:
        layer["sink"] = P(heads)  # a scalar a query head
    if cfg.attn_gate:
        layer["wg"] = col  # the gate's columns follow q's heads
    if cfg.qk_norm == "head":
        # one scale of head_size for every head: replicated
        layer["q_norm"] = P(None)
        layer["k_norm"] = P(None)
    elif cfg.qk_norm:
        # scales of the whole projected q and k: sharded like the
        # projections' output columns
        layer["q_norm"] = P(heads)
        layer["k_norm"] = P(heads)
    return layer


def init(key, cfg, kind) -> Dict:
    """q, k and v from the first of the layer's two keys, ``wo`` and the
    gate from the second (normal, 0.02); the sink 0, QK-norm's scales 1.
    The kind's own K/V heads and v width where it has them."""
    normal = partial(_normal, dtype=cfg.dtype)
    hd = cfg.head_size()
    d_q = cfg.n_heads * hd
    n_kv = cfg.kv_heads(kind)
    dv = (kind.heads and kind.heads.v_dim) or hd
    layer = {
        "wq": normal(key[0], (cfg.d_model, d_q)),
        "wk": normal(
            jax.random.fold_in(key[0], 1), (cfg.d_model, n_kv * hd)
        ),
        "wv": normal(
            jax.random.fold_in(key[0], 2), (cfg.d_model, n_kv * dv)
        ),
        "wo": normal(key[1], (cfg.n_heads * dv, cfg.d_model)),
    }
    if kind.sink:
        layer["sink"] = jnp.zeros((cfg.n_heads,), jnp.float32)
    if cfg.attn_gate:
        layer["wg"] = normal(
            jax.random.fold_in(key[1], 1), (cfg.d_model, d_q)
        )
    if cfg.qk_norm == "head":
        layer["q_norm"] = jnp.ones((hd,), cfg.dtype)
        layer["k_norm"] = jnp.ones((hd,), cfg.dtype)
    elif cfg.qk_norm:
        layer["q_norm"] = jnp.ones((d_q,), cfg.dtype)
        layer["k_norm"] = jnp.ones((n_kv * hd,), cfg.dtype)
    return layer


def bind(cfg, kind, tp_axis, tp_size, causal=True, positions=None,
         attention_fn=None):
    """``(h, lp) -> (partial_o, (k, v))`` for an attention layer of
    ``kind``: tp splits the heads, query and K/V; the layer's own window,
    rotation and head geometry; the configuration's QK-norm and, under
    ``cfg.diffusion``, its block layout.  ``causal=False`` is the
    bidirectional (encoder) form; ``positions`` (rows -> their global
    positions) and ``attention_fn`` are the context-parallel block's, whose
    weights are whole (``tp_axis`` None): its shard's positions and the
    striped ring in the dense lowerings' place."""
    n_kv = cfg.kv_heads(kind)
    if tp_size > 1 and n_kv % tp_size:
        raise ValueError(
            f"n_kv_heads ({n_kv}) must be divisible "
            f"by tp ({tp_size}) so every chip owns whole kv heads"
        )
    return partial(
        _attn_partial, n_heads_local=cfg.n_heads // tp_size,
        attn_impl=cfg.attention, causal=causal,
        rope_base=(kind.rope_base or cfg.rope_base) if kind.rope else None,
        positions=positions, attention_fn=attention_fn, tp_axis=tp_axis,
        window=kind.window, head_norm=cfg.qk_norm == "head",
        qk_eps=cfg.norm_eps,
        diffusion_block=cfg.diffusion and cfg.diffusion.block,
        beside_kda=cfg.kda is not None, geometry=kind.heads,
    )


def _attn_partial(h, lp, n_heads_local, attn_impl="naive", causal=True,
                  rope_base=None, positions=None, attention_fn=None,
                  tp_axis=None, window=None, head_norm=False, qk_eps=1e-5,
                  diffusion_block=None, beside_kda=False, geometry=None):
    """Column-parallel attention on a full-sequence activation: returns
    the row-parallel PARTIAL output (pre-reduction) and the (k, v) head
    tensors (B, Hkv_local, T, hd) for KV-cache prefill.  The kv head
    count comes from the wk shard's width (GQA: fewer kv heads than q
    heads; every attention lowering groups q heads onto kv head h//G).
    With ``rope_base`` set, q/k rotate by absolute position BEFORE
    attention (and before the kv tensors are returned, so the prefill
    cache stores rotated keys — decode appends consistently).

    ``positions`` (a function of the rows' count) overrides the rope
    positions (context parallelism passes its shard's global token
    positions); ``attention_fn`` replaces the dense :func:`_attention`
    lowering (context parallelism passes the striped ring).  ``tp_axis`` is
    for :func:`_qk_norm`.

    What the layer's tree holds picks the rest: under ``head_norm`` the
    ``q_norm`` / ``k_norm`` scales are one head wide and norm each head
    AFTER the split (:func:`_qk_norm` is the whole projection's);
    a ``wg`` gates the attention output, ``attn * sigmoid(h wg)``, before
    ``wo``.  ``window`` is the sliding window,
    run under the device scope ``accl.attn::window`` (full attention stays
    ``accl.attn::core``; where the stack has KDA layers, ``beside_kda``, or
    the layer a head geometry, what is round the core runs under
    ``accl.attn::gqa_proj``).  A ``sink`` leaf (a float32 scalar a query
    head) stands in every row's softmax as a key without a value; v's width
    is ``wv``'s over the K/V heads that ``wk``'s gives.  ``geometry``
    (:class:`HeadGeometry`) is what the shapes cannot say: of a head's
    columns the FIRST ``rope_dim`` rotate and go to the core as the scores'
    second part (``q_rope`` / ``k_rope`` on the K/V heads), the others carry
    no position; v is times ``v_scale``.  ``qk_eps`` is QK-norm's epsilon.
    ``diffusion_block`` (block diffusion's ``B``): ``h`` is ``[noisy ;
    clean]``, ``2 L`` rows of whole blocks that rotate at positions ``0..L``
    twice, and the core runs under that layout in the device scope
    ``accl.attn::blockdiff``.

    Under ``cfg.remat`` the block keeps q, k and v AS THE CORE TAKES THEM
    (``KEPT_UNDER_REMAT``: head-major, after the norms, the value scale, the
    rope and the split, ``q_rope`` / ``k_rope`` beside them where a head
    splits) and, where the core is the flash kernels, the core's ``o`` and
    ``lse`` (named in its forward rule): all that ``flash_bwd`` reads, so
    the backward's replay runs none of the three products, no transpose, no
    rope and no ``flash_fwd``; ``wo``'s product and a gate's are replayed."""
    B, T, _ = h.shape
    block_diffusion = None
    if diffusion_block is not None:
        if T % 2 or (T // 2) % diffusion_block:
            raise ValueError(
                f"block diffusion runs [noisy ; clean], 2 L rows of whole "
                f"blocks of {diffusion_block}; got {T} rows"
            )
        block_diffusion = (T // 2, diffusion_block)
    # a softmax layer BESIDE KDA layers (a gated
    # grouped-query layer without position among linear-attention ones)
    # runs what is round its core under a device scope of its own, as the
    # KDA layers do; every other stack's program is what it was
    proj_scope = (
        device_scope("accl.attn::gqa_proj")
        if beside_kda or geometry is not None
        else contextlib.nullcontext()
    )
    with proj_scope:
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]  # column-parallel
    # a head geometry's own work between the projections and the core (the
    # value scale, the split into the part that rotates and the part without
    # position, the sink's cast) goes under the same scope; without one the
    # lines below trace outside any scope, as they always did
    with proj_scope if geometry is not None else contextlib.nullcontext():
        if not head_norm:
            q, k = _qk_norm(q, k, lp, tp_axis, qk_eps)
        hd = q.shape[-1] // n_heads_local
        n_kv_local = k.shape[-1] // hd
        # v's heads are as wide as ``wv`` makes them
        heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
        q, k, v = (
            heads(q, n_heads_local), heads(k, n_kv_local), heads(v, n_kv_local)
        )
        if geometry is not None and geometry.v_scale != 1.0:
            v = v * geometry.v_scale
        # the scores' second part and the sink, where the layer has them
        more = {}
        if "sink" in lp:
            # inside a shard_map: the sink varying over every axis q varies
            # over (the batch's too), so that its cotangent is summed over
            # them by the cast's transpose, as the latent mixer's one rope
            # key is
            more["sink"] = sink = lp["sink"]
            if missing := tuple(jax.typeof(q).vma - jax.typeof(sink).vma):
                more["sink"] = jax.lax.pcast(sink, missing, to="varying")
        if head_norm and "q_norm" in lp:
            q = _rmsnorm(q, lp["q_norm"], eps=qk_eps)
            k = _rmsnorm(k, lp["k_norm"], eps=qk_eps)
        if rope_base is not None:
            if block_diffusion is not None:
                pos = jnp.tile(jnp.arange(block_diffusion[0]), 2)
            else:
                pos = jnp.arange(T) if positions is None else positions(T)
            dr = hd if geometry is None else geometry.rope_dim or hd
            tables = _rope_tables(pos, dr // 2, rope_base)
            if dr == hd:
                q = _rope_rotate(q, tables)
                k = _rope_rotate(k, tables)
            else:
                rotated = lambda t: kept_under_remat(
                    _rope_rotate(t[..., :dr], tables)
                )
                more.update(q_rope=rotated(q), k_rope=rotated(k))
                q, k = q[..., dr:], k[..., dr:]
        # what the core's backward reads of the mixer's work, AS THE CORE
        # TAKES IT (the two rotated parts above with it): a rematerialised
        # block keeps these, so that its backward runs neither the products
        # nor the relayouts above a second time
        q, k, v = (kept_under_remat(t) for t in (q, k, v))
    if block_diffusion is not None:
        with device_scope("accl.attn::blockdiff"):
            attn = _attention(
                q, k, v, impl=attn_impl, block_diffusion=block_diffusion
            )
    elif window is None:
        with device_scope("accl.attn::core"):
            if attention_fn is not None:
                attn = attention_fn(q, k, v)
            else:
                attn = _attention(
                    q, k, v, impl=attn_impl, causal=causal, **more
                )
    else:
        with device_scope("accl.attn::window"):
            attn = _attention(
                q, k, v, impl=attn_impl, causal=causal, window=window, **more
            )
    with proj_scope:
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, -1)
        if "wg" in lp:
            gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
            attn = attn * gate.astype(attn.dtype)
        return attn @ lp["wo"], (k, v)
