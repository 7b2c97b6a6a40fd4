"""The Mamba-2 mixer (state-space duality): ``MIXERS["mamba2"]``, the layers
whose ``LayerKind.mixer`` is ``"mamba2"``.  Its description (:class:`Mamba2`,
``TransformerConfig.mamba``), what a configuration must hold for it
(:func:`check`), its leaves (:func:`specs`, :func:`init`), its function for a
layer (:func:`bind`, :func:`_mamba2_partial`) and what the paths beside train
and forward call it (:func:`plain`)."""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...utils.profiling import device_scope
from ...utils.remat import kept_under_remat
from ..layers import _normal, _tp_specs


@dataclasses.dataclass(frozen=True)
class Mamba2:
    """The sizes of a Mamba-2 mixer (state-space duality, arXiv:2405.21060;
    ``TransformerConfig.mamba``; the layers whose ``LayerKind.mixer`` is
    ``"mamba2"``): ``n_heads`` heads (its own count, not the attention's)
    of ``head_dim`` columns with a state of ``head_dim x state`` each, in
    ``groups`` groups of consecutive heads that share the state's input and
    output directions B and C.  ``[z | x | B | C | dt] = u W_in``; x, B and
    C through ``silu(conv(.) + bias)``, a causal depthwise convolution of
    ``conv`` taps; ``dt = softplus(dt + dt_bias)`` and a rate ``A =
    -exp(a_log)``, scalars a head, into ``S_t = exp(dt_t A) S_{t-1} + dt_t
    x_t B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops.ssd``, in chunks of
    ``chunk``); ``y silu(z)`` RMS-normed a group (the gate BEFORE the norm)
    before ``wo``.  ``dt_min``, ``dt_max`` and ``dt_floor`` are
    INITIALISATION (``dt_bias`` is the inverse softplus of a log-uniform
    draw between the first two, floored): the step has no clamp."""

    n_heads: int
    head_dim: int
    state: int
    groups: int
    conv: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


def check(cfg, kind, i) -> None:
    """What a configuration must hold for this mixer.  ``kind`` None: the
    description alone (``cfg.mamba``), whichever layers the pattern has;
    else layer ``i`` of ``kind``."""
    if kind is not None:
        if cfg.mamba is None or kind.window is not None:
            raise ValueError(
                f"layer {i}: a Mamba-2 layer needs "
                "TransformerConfig.mamba and has no window"
            )
        return
    if cfg.mamba is None:
        return
    m = cfg.mamba
    if (
        "mamba2" not in {cfg.mixer(k) for k in cfg.layers or ()}
        or min(m.n_heads, m.head_dim, m.state, m.groups, m.conv,
               m.chunk) < 1
        or m.n_heads % m.groups
        or not 0.0 < m.dt_floor <= m.dt_min <= m.dt_max
    ):
        raise ValueError(
            "a Mamba-2 mixer (TransformerConfig.mamba) is some "
            "layer's of the pattern (LayerKind.mixer='mamba2'), with "
            "heads in whole groups, sizes of at least 1 and 0 < "
            f"dt_floor <= dt_min <= dt_max; got {m}"
        )


def plain(cfg, kind) -> Optional[str]:
    """The decode, context- and sequence-parallel blocks, the encoder and
    the pipelines have no form for this mixer (the cache would be a
    recurrent state and a convolution's last inputs): what they refuse it
    as."""
    return "the Mamba-2 mixer (a state-space layer, TransformerConfig.mamba)"


def specs(cfg, kind) -> Dict:
    col, row, heads = _tp_specs(cfg)
    return {
        # [z | x | B | C | dt] = u W_in as five matrices, so that tp
        # splits the heads (z, x, dt) AND the groups (B, C) together:
        # a chip's heads keep their own groups; the taps, the biases,
        # the scalars a head and the grouped norm's scale follow
        "wz": col, "wx": col, "wb": col, "wc": col, "wdt": col,
        "conv_x": col, "conv_b": col, "conv_c": col,
        "bias_x": P(heads), "bias_b": P(heads), "bias_c": P(heads),
        "dt_bias": P(heads), "a_log": P(heads), "d_skip": P(heads),
        "y_norm": P(heads), "wo": row,
    }


def init(key, cfg, kind) -> Dict:
    """From the first of the layer's two keys.  The matrices as every
    other (normal, 0.02); taps and the
    convolution's bias normal at ``conv ** -0.5``; ``a_log`` the log of
    a uniform draw from [1, 16) a head, ``dt_bias`` the inverse
    softplus of a log-uniform draw from [dt_min, dt_max) floored at
    dt_floor, ``d_skip`` 1 (the family's conventions)."""
    m = cfg.mamba
    normal = partial(_normal, dtype=cfg.dtype)
    ks = jax.random.split(key[0], 14)
    inner, bc = m.n_heads * m.head_dim, m.groups * m.state
    matrix = lambda key, n: normal(key, (cfg.d_model, n))
    taps = lambda key, n: (
        jax.random.normal(key, (m.conv, n), cfg.dtype) * m.conv ** -0.5
    )
    bias = lambda key, n: (
        jax.random.normal(key, (n,), cfg.dtype) * m.conv ** -0.5
    )
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        ks[12], (m.n_heads,), jnp.float32, math.log(m.dt_min),
        math.log(m.dt_max),
    )), m.dt_floor)
    return {
        "wz": matrix(ks[0], inner), "wx": matrix(ks[1], inner),
        "wb": matrix(ks[2], bc), "wc": matrix(ks[3], bc),
        "wdt": matrix(ks[4], m.n_heads),
        "conv_x": taps(ks[5], inner), "conv_b": taps(ks[6], bc),
        "conv_c": taps(ks[7], bc),
        "bias_x": bias(ks[8], inner), "bias_b": bias(ks[9], bc),
        "bias_c": bias(ks[10], bc),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(
            ks[11], (m.n_heads,), jnp.float32, 1.0, 16.0
        )),
        "d_skip": jnp.ones((m.n_heads,), jnp.float32),
        "y_norm": jnp.ones((inner,), cfg.dtype),
        "wo": normal(ks[13], (inner, cfg.d_model)),
    }


def bind(cfg, kind, tp_axis, tp_size):
    """``(h, lp) -> (partial_o, None)`` for a Mamba-2 layer: tp splits the
    heads AND their B/C groups together, and the state is no cache to
    return yet."""
    m = cfg.mamba
    if tp_size > 1 and m.groups % tp_size:
        raise ValueError(
            f"the Mamba-2 mixer's groups ({m.groups}) must be divisible "
            f"by tp ({tp_size}) so every chip owns whole groups of heads"
        )
    mamba = {
        "head_dim": m.head_dim, "state": m.state, "chunk": m.chunk,
        "eps": cfg.norm_eps,
    }
    return lambda h, lp: (_mamba2_partial(h, lp, mamba), None)


def _mamba2_partial(h, lp, mamba):
    """The Mamba-2 mixer (:class:`Mamba2`) on a full-sequence activation,
    heads and groups column-parallel: the row-parallel PARTIAL output.  The
    sizes are the tree's but what no shape says, which ``mamba`` carries:
    a head's width, the state's, the chunk and the norm's ``eps``.  The
    matmuls take the activations' type; the convolution, SiLU, softplus,
    the core, the gate and the grouped norm are float32.  Everything but
    the core runs under the device scope ``accl.attn::mamba_proj``: the
    matmuls, ``dt``'s softplus and the two float32 chains, ``ops.ssd``'s
    ``conv_silu`` (x, B and C) and ``gated_group_norm``, each of which
    picks from the shapes the Mosaic kernels of ``ops/pallas/
    mamba_mixer.py`` (``mamba_in_fwd`` / ``mamba_in_bwd``, ``mamba_out_fwd``
    / ``mamba_out_bwd``: one pass over HBM a chain, forward and backward)
    or XLA's fusions.  The core (from x, B, C and dt to y, all TOKEN-MAJOR,
    as the convolutions leave them and the norm takes them:
    ``ops.ssd.ssd_mixer``, which picks from the shapes the Mosaic kernels
    ``ssd_fwd`` / ``ssd_bwd`` that keep a chunk's decay squares and the
    running state in VMEM, or the XLA form round its head-major
    transposes) runs under ``accl.attn::ssd``.

    Under ``cfg.remat`` the block keeps the five bf16 projections (``h
    wz``, ``h wx``, ``h wb``, ``h wc``, ``h wdt``: ``KEPT_UNDER_REMAT``;
    B T (2 d_inner + 2 G N + H) x 2 bytes, 304,087,040 a block at 8,192 x
    18,560): the backward replays the chains and the core from them and
    multiplies none of them out twice; ``wo``'s product is replayed."""
    from ...ops.ssd import conv_silu, gated_group_norm, ssd_mixer

    N = mamba["state"]
    G = lp["wb"].shape[1] // N
    f32 = jnp.float32
    with device_scope("accl.attn::mamba_proj"):
        proj = lambda w: kept_under_remat(h @ lp[w])
        z = proj("wz")
        x = conv_silu(proj("wx"), lp["conv_x"], lp["bias_x"])
        b = conv_silu(proj("wb"), lp["conv_b"], lp["bias_b"])
        c = conv_silu(proj("wc"), lp["conv_c"], lp["bias_c"])
        dt = jax.nn.softplus(
            proj("wdt").astype(f32) + lp["dt_bias"].astype(f32)
        )                                                 # (B, T, H)
        a = -jnp.exp(lp["a_log"].astype(f32))
    with device_scope("accl.attn::ssd"):
        y = ssd_mixer(x, b, c, dt, a, lp["d_skip"], G, mamba["chunk"])
    with device_scope("accl.attn::mamba_proj"):
        y = gated_group_norm(y, z, lp["y_norm"], G, mamba["eps"], h.dtype)
        return y @ lp["wo"]
