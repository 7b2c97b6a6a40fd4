"""The latent mixer (multi-head latent attention, MLA): ``MIXERS["latent"]``,
every layer's where ``TransformerConfig.latent`` is set and a kind does not say
otherwise.  Its description (:class:`LatentAttention`), YaRN's rescaling of its
rotary frequencies (:class:`YarnScaling`, ``TransformerConfig.rope_yarn``),
what a configuration must hold for it (:func:`check`), its leaves
(:func:`specs`, :func:`init`), its function for a layer (:func:`bind`,
:func:`_latent_attn_partial`) and what the paths beside train and forward
call it (:func:`plain`)."""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...utils.profiling import device_scope
from ..layers import (
    _attention,
    _check_rotation,
    _normal,
    _own_heads,
    _rmsnorm,
    _rope_rotate,
    _rope_tables,
    _tp_specs,
)


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The sizes of a latent mixer (multi-head latent attention, MLA;
    ``TransformerConfig.latent``): q comes up from a normed latent of
    ``q_rank`` (``None``: straight from the hidden state, one matrix
    ``wq`` and no q norm, a published ``q_lora_rank`` null), k's content
    part and v from a normed latent of
    ``kv_rank``; a head's q and k are ``nope_dim`` columns without
    position beside ``rope_dim`` that rotate (k's rotating part is ONE
    head, projected straight from the input and shared by every query
    head); v and the output are ``v_dim`` a head."""

    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rescaling of the rotary frequencies (the published
    ``rope_scaling`` of type ``yarn``; ``TransformerConfig.rope_yarn``):
    :func:`yarn_inv_freq` has the formula."""

    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn: YarnScaling):
    """The ``dim // 2`` inverse frequencies of a rotary embedding over
    ``dim`` columns under YaRN, float32 (numpy): pair ``i`` keeps
    ``base ** (-2 i / dim)`` where it turns more than ``beta_fast`` times
    in the original context, is divided by ``factor`` where fewer than
    ``beta_slow``, and is blended linearly between the two corrections'
    pair indices ``low`` and ``high``."""
    import numpy as np

    half = dim // 2
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor

    def corr(turns):
        return (
            dim * math.log(yarn.original_max_seq / (turns * 2 * math.pi))
            / (2 * math.log(base))
        )

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / span, 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def check(cfg, kind, i) -> None:
    """What a configuration must hold for this mixer.  ``kind`` None: the
    description alone (``cfg.latent``), whichever layers the pattern has;
    else layer ``i`` of ``kind``."""
    if kind is None:
        if cfg.latent is not None and (
            cfg.pos_embedding != "rope" or cfg.n_kv_heads is not None
            or cfg.head_dim is not None or cfg.qk_norm
            or cfg.attn_gate is True
        ):
            raise ValueError(
                "a latent mixer rotates (pos_embedding='rope') and has no "
                "n_kv_heads, head_dim, qk_norm or attn_gate of its own "
                "(its gate is a value a head: attn_gate='head')"
            )
        return
    if cfg.latent is None:
        raise ValueError(
            f"layer {i}: the latent mixer in a stack whose "
            "TransformerConfig.latent is None (a "
            "stack holds the latent mixer or attention, beside "
            "KDA and Mamba-2 layers)"
        )
    _check_rotation(cfg, kind, i)
    if _own_heads(kind):
        raise ValueError(
            f"layer {i}: kv_heads, rope_base, sink and heads are the "
            "attention mixer's under a causal mask, not the latent "
            "mixer's or block diffusion's"
        )


def plain(cfg, kind) -> Optional[str]:
    """The decode, context- and sequence-parallel blocks, the encoder and
    the pipelines have no form for this mixer (its cache is the latent and
    the rope key, not k and v): what they refuse it as."""
    return "the latent mixer (MLA, TransformerConfig.latent)"


def specs(cfg, kind) -> Dict:
    col, row, _ = _tp_specs(cfg)
    layer = {
        # the down-projections and their norms are every chip's; the
        # up-projections' columns are heads, sharded as wq's are
        "wkv_a": P(None, None), "kv_a_norm": P(None), "wkv_b": col,
        "wo": row,  # (heads * v_dim / tp, d_model)
    }
    if cfg.latent.q_rank is None:
        layer["wq"] = col
    else:
        layer.update(wq_a=P(None, None), q_a_norm=P(None), wq_b=col)
    if cfg.attn_gate:
        layer["wg"] = col  # a value a head: the gate's columns are heads
    return layer


def init(key, cfg, kind) -> Dict:
    """The matrices from splits of the first of the layer's two keys, the
    gate from the second (normal, 0.02); the latents' norms 1."""
    la = cfg.latent
    normal = partial(_normal, dtype=cfg.dtype)
    ks = jax.random.split(key[0], 5)
    H = cfg.n_heads
    d_q = H * (la.nope_dim + la.rope_dim)
    if la.q_rank is None:
        q = {"wq": normal(ks[0], (cfg.d_model, d_q))}
    else:
        q = {
            "wq_a": normal(ks[0], (cfg.d_model, la.q_rank)),
            "q_a_norm": jnp.ones((la.q_rank,), cfg.dtype),
            "wq_b": normal(ks[1], (la.q_rank, d_q)),
        }
    layer = {
        **q,
        "wkv_a": normal(ks[2], (cfg.d_model, la.kv_rank + la.rope_dim)),
        "kv_a_norm": jnp.ones((la.kv_rank,), cfg.dtype),
        "wkv_b": normal(ks[3], (la.kv_rank, H * (la.nope_dim + la.v_dim))),
        "wo": normal(ks[4], (H * la.v_dim, cfg.d_model)),
    }
    if cfg.attn_gate:
        layer["wg"] = normal(jax.random.fold_in(key[1], 1), (cfg.d_model, H))
    return layer


def bind(cfg, kind, tp_axis, tp_size):
    """``(h, lp) -> (partial_o, None)`` for a latent layer: tp splits the
    heads (``wq_b``, ``wkv_b`` column-parallel, ``wo`` row-parallel, the
    ``_a`` matrices and their norms replicated); the layer's own window and
    rotation; no cache to return yet."""
    if cfg.n_heads % tp_size:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must be divisible by tp ({tp_size}) "
            "so every chip owns whole heads of the latent mixer"
        )
    latent = {
        "scale": cfg.attn_scale(), "inv_freq": cfg.rope_inv_freq(),
        "table_scale": cfg.rope_table_scale(), "eps": cfg.norm_eps,
    }
    core = partial(
        _latent_attn_partial, n_heads_local=cfg.n_heads // tp_size,
        attn_impl=cfg.attention, causal=True,
        rope_base=cfg.rope_base if kind.rope else None, window=kind.window,
        latent=latent,
    )
    return lambda h, lp: (core(h, lp), None)


def _latent_attn_partial(h, lp, n_heads_local, attn_impl, causal, rope_base,
                         window, latent):
    """The latent mixer (``TransformerConfig.latent``) on a full-sequence
    activation, heads column-parallel: the row-parallel PARTIAL output.
    The sizes are the tree's (``wq_b`` and ``wkv_b`` hold this chip's
    heads); ``latent`` carries what the shapes do not say: the softmax
    ``scale``, the rotary ``inv_freq`` and ``table_scale``, the norms'
    ``eps``.  Projections, norms and rope run under the device scope
    ``accl.attn::latent``, the score/softmax/value core under
    ``accl.attn::mla``; the rope key goes to the core as ONE head.  What
    the tree holds picks the rest: a ``wq`` in place of ``wq_a`` is q
    straight from the hidden state (no q latent, no q norm), and a ``wg``
    ``(d_model, heads)`` gates each head's output by ``sigmoid(h wg)``
    before ``wo``.  Under ``cfg.remat`` the block keeps the flash core's
    ``o`` and ``lse`` (its forward rule names them) and nothing of this
    mixer's own: q, k and v are expanded from the latents on every head
    (0.4 GB more a layer at Ling-3.0's widths) and are replayed."""
    B, T, _ = h.shape
    H = n_heads_local
    rank = lp["wkv_b"].shape[0]
    dr = lp["wkv_a"].shape[1] - rank
    norm = partial(_rmsnorm, eps=latent["eps"])
    heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
    with device_scope("accl.attn::latent"):
        if "wq_a" in lp:
            q = heads(norm(h @ lp["wq_a"], lp["q_a_norm"]) @ lp["wq_b"], H)
        else:
            q = heads(h @ lp["wq"], H)
        dn = q.shape[-1] - dr
        ckv = h @ lp["wkv_a"]
        kv = heads(norm(ckv[..., :rank], lp["kv_a_norm"]) @ lp["wkv_b"], H)
        q_n, q_r = q[..., :dn], q[..., dn:]
        k_n, v = kv[..., :dn], kv[..., dn:]
        k_r = heads(ckv[..., rank:], 1)
        if rope_base is not None:
            tables = _rope_tables(
                jnp.arange(T), dr // 2, rope_base, latent["inv_freq"],
                latent["table_scale"],
            )
            q_r = _rope_rotate(q_r, tables)
            k_r = _rope_rotate(k_r, tables)
        # inside a shard_map: the one rope key varying over the axes the
        # heads vary over (tp), so that its cotangent is summed over the
        # chips' heads by the cast's transpose
        if missing := tuple(jax.typeof(q_r).vma - jax.typeof(k_r).vma):
            k_r = jax.lax.pcast(k_r, missing, to="varying")
    with device_scope("accl.attn::mla"):
        attn = _attention(
            q_n, k_n, v, impl=attn_impl, causal=causal, window=window,
            scale=latent["scale"], q_rope=q_r, k_rope=k_r,
        )
    with device_scope("accl.attn::latent"):
        if "wg" in lp:
            gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
            attn = attn * gate.astype(attn.dtype).transpose(0, 2, 1)[..., None]
        return attn.transpose(0, 2, 1, 3).reshape(B, T, -1) @ lp["wo"]
