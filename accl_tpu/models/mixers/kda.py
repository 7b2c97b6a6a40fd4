"""The KDA mixer (Kimi Delta Attention; Gated DeltaNet under ``head_decay``):
``MIXERS["kda"]``, the layers whose ``LayerKind.mixer`` is ``"kda"``.  Its
description (:class:`DeltaAttention`, ``TransformerConfig.kda``), what a
configuration must hold for it (:func:`check`), its leaves (:func:`specs`,
:func:`init`), its function for a layer (:func:`bind`, :func:`_kda_partial`)
and what the paths beside train and forward call it (:func:`plain`)."""

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
class DeltaAttention:
    """The sizes of a KDA mixer (Kimi Delta Attention, arXiv:2510.26692;
    ``TransformerConfig.kda``; the layers whose ``LayerKind.mixer`` is
    ``"kda"``): ``n_heads`` heads whose q, k and v are ``head_dim`` wide,
    each ``silu(conv(h w))`` under a causal depthwise convolution of
    ``conv`` taps; q and k L2-normalised a head; a log-decay a CHANNEL
    ``lower_bound * sigmoid(exp(a_log) (h wf + dt_bias))`` and a write
    strength a head ``beta_scale * sigmoid(h wbeta)`` into the gated delta
    rule (``ops.kda``); the output RMS-normed a head and gated a channel by
    ``sigmoid(h wg)`` before ``wo``.  A ``lower_bound`` must stay within
    what ``ops.kda``'s sub-blocks keep inside float32 (``-80 / SUB``);
    ``None`` is the PUBLISHED gate without a bound, ``-exp(a_log)
    softplus(h wf + dt_bias)``, any value below 0, under which the core
    splits its decays by halving (``ops.kda``, ANY ``g <= 0``; chosen here,
    statically, so a bounded model's program is untouched).  ``beta_scale``
    2 lets the transition's eigenvalue along k be negative (the family's
    ``allow_neg_eigval``).  ``gate_rank``: the two gate projections ``wf``
    and ``wg`` through that rank, ``wf_a wf_b`` and ``wg_a wg_b`` in the
    tree (``None``: full rank, one matrix each).

    GATED DELTANET (arXiv:2412.06464) is the same rule with a decay a
    HEAD, three more properties of this description: ``v_dim`` is the width
    of a head's v, output gate and ``wo`` rows where it is not ``head_dim``
    (the state is ``head_dim x v_dim``; q and k stay ``head_dim``);
    ``head_decay`` makes the log-decay ONE value a head a token, ``-exp(a_log)
    softplus(h wa + dt_bias)`` with ``wa`` ``(d_model, n_heads)`` and
    ``dt_bias`` a head in ``wf``'s place (no bound to set: ``lower_bound``
    None, ``gate_rank`` None; ``ops.kda`` runs a gate of one column through
    the same core, its heads padded to whole lanes where that costs at most
    half again); ``out_gate`` ``"silu"`` gates the normed output by ``SiLU(h
    wg)`` in the sigmoid's place."""

    head_dim: int
    conv: int = 4
    lower_bound: Optional[float] = -5.0
    beta_scale: float = 1.0
    gate_rank: Optional[int] = None
    v_dim: Optional[int] = None
    head_decay: bool = False
    out_gate: str = "sigmoid"

    def value_dim(self) -> int:
        return self.head_dim if self.v_dim is None else self.v_dim


#: the softplus values :func:`init` draws a head's ``dt_bias`` for under
#: ``DeltaAttention.head_decay``, log-uniform (the family's initial range)
KDA_HEAD_DECAY_DT = (1e-3, 0.1)

#: the softplus values :func:`init` draws the unbounded KDA gate's
#: ``dt_bias`` for, log-uniform a channel (its docstring there says why)
KDA_UNBOUNDED_DT = (1e-3, 16.0)


def check(cfg, kind, i) -> None:
    """What a configuration must hold for this mixer.  ``kind`` None: the
    description alone (``cfg.kda``), whichever layers the pattern has;
    else layer ``i`` of ``kind``."""
    if kind is not None:
        if cfg.kda is None or kind.window is not None:
            raise ValueError(
                f"layer {i}: a KDA layer needs "
                "TransformerConfig.kda and has no window"
            )
        return  # no position encoding: ``rope`` says nothing
    if cfg.kda is None:
        return
    from ...ops.kda import SUB

    d = cfg.kda
    if (
        "kda" not in {cfg.mixer(k) for k in cfg.layers or ()}
        or d.head_dim < 1 or d.conv < 1
        or not (
            d.lower_bound is None
            or -80.0 / SUB <= d.lower_bound < 0.0
        )
        or d.beta_scale not in (1.0, 2.0)
        or (d.gate_rank is not None and d.gate_rank < 1)
        or d.value_dim() < 1
        or d.out_gate not in ("sigmoid", "silu")
        or (d.head_decay and (
            d.lower_bound is not None or d.gate_rank is not None
        ))
    ):
        raise ValueError(
            "a KDA mixer (TransformerConfig.kda) is some layer's of "
            "the pattern (LayerKind.mixer='kda'), with a head_dim "
            f"and a convolution of at least 1, a lower_bound in "
            f"[{-80.0 / SUB}, 0) or None (the gate without a bound), "
            "a beta_scale of 1 or 2, a gate_rank of at least 1 or "
            "None, a v_dim of at least 1 or None, an out_gate "
            "'sigmoid' or 'silu' and, under head_decay, neither a "
            f"lower_bound nor a gate_rank; got {d}"
        )


def plain(cfg, kind) -> Optional[str]:
    """The decode, context- and sequence-parallel blocks, the encoder and
    the pipelines have no form for this mixer (prefill/generate would need
    the recurrent state as a cache, the ring would hand a state from rank
    to rank): what they refuse it as."""
    return (
        "the KDA mixer (linear attention, TransformerConfig.kda: its "
        "state is no cache yet, under either gate, at either rank of "
        "the gate projections, with a decay a channel or a head)"
    )


def specs(cfg, kind) -> Dict:
    col, row, heads = _tp_specs(cfg)
    layer = {
        # every projection's columns are heads (``wbeta``'s one a
        # head), and so are the channels of the taps and of ``dt_bias``;
        # the output norm's scale is one head wide, every head's
        "wq": col, "wk": col, "wv": col,
        "wbeta": col, "conv_q": col, "conv_k": col, "conv_v": col,
        "a_log": P(heads), "dt_bias": P(heads), "o_norm": P(None),
        "wo": row,
    }
    if cfg.kda.head_decay:
        # a decay a head: ``wa``'s columns are the heads themselves
        layer.update(wa=col, wg=col)
    elif cfg.kda.gate_rank is None:
        layer.update(wf=col, wg=col)
    else:
        # the way down to the rank is every chip's, the way up has the
        # heads' columns
        layer.update(
            wf_a=P(None, None), wf_b=col, wg_a=P(None, None), wg_b=col
        )
    return layer


def init(key, cfg, kind) -> Dict:
    """From the first of the layer's two keys.  The matrices as every
    other (normal, 0.02); the taps normal at
    ``conv ** -0.5`` (a Conv1d's default range); ``a_log`` the log of
    a uniform draw from [1, 16) a head (the family's convention);
    ``dt_bias`` standard normal a channel, so that channels differ in
    how fast they forget.  Under the gate without a bound ``dt_bias``
    is the inverse softplus of a log-uniform draw from
    :data:`KDA_UNBOUNDED_DT` (the family's own range, [0.001, 0.1],
    would leave every seeded channel remembering for hundreds of
    tokens; a trained gate does not): log-decays from -0.001 to under
    -100 a token, channels on both sides of a bound of -5.  A
    ``gate_rank`` splits ``wf`` and ``wg`` in two, normal alike."""
    kda = cfg.kda
    normal = partial(_normal, dtype=cfg.dtype)
    ks = jax.random.split(key[0], 12)
    wide = cfg.n_heads * kda.head_dim
    wide_v = cfg.n_heads * kda.value_dim()
    matrix = lambda key, n=wide: normal(key, (cfg.d_model, n))
    taps = lambda key, n=wide: (
        jax.random.normal(key, (kda.conv, n), cfg.dtype)
        * kda.conv ** -0.5
    )
    if kda.head_decay:
        gates = {
            "wa": matrix(ks[3], cfg.n_heads), "wg": matrix(ks[4], wide_v),
        }
    elif kda.gate_rank is None:
        gates = {"wf": matrix(ks[3]), "wg": matrix(ks[4])}
    else:
        down = lambda key: normal(key, (cfg.d_model, kda.gate_rank))
        up = lambda key: normal(
            jax.random.fold_in(key, 1), (kda.gate_rank, wide)
        )
        gates = {
            "wf_a": down(ks[3]), "wf_b": up(ks[3]),
            "wg_a": down(ks[4]), "wg_b": up(ks[4]),
        }
    if kda.lower_bound is None:
        low, high = KDA_HEAD_DECAY_DT if kda.head_decay else KDA_UNBOUNDED_DT
        dt = jnp.exp(jax.random.uniform(
            ks[10], (cfg.n_heads if kda.head_decay else wide,),
            jnp.float32, math.log(low), math.log(high),
        ))
        dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    else:
        dt_bias = jax.random.normal(ks[10], (wide,), jnp.float32)
    return {
        "wq": matrix(ks[0]), "wk": matrix(ks[1]),
        "wv": matrix(ks[2], wide_v),
        **gates,
        "wbeta": normal(ks[5], (cfg.d_model, cfg.n_heads)),
        "conv_q": taps(ks[6]), "conv_k": taps(ks[7]),
        "conv_v": taps(ks[8], wide_v),
        "a_log": jnp.log(jax.random.uniform(
            ks[9], (cfg.n_heads,), jnp.float32, 1.0, 16.0
        )),
        "dt_bias": dt_bias,
        "o_norm": jnp.ones((kda.value_dim(),), cfg.dtype),
        "wo": normal(ks[11], (wide_v, cfg.d_model)),
    }


def bind(cfg, kind, tp_axis, tp_size):
    """``(h, lp) -> (partial_o, None)`` for a KDA layer: tp splits the
    heads, and the state is no cache to return yet."""
    heads_local = cfg.n_heads // tp_size
    kda = {
        "lower_bound": cfg.kda.lower_bound, "eps": cfg.norm_eps,
        "beta_scale": cfg.kda.beta_scale, "out_gate": cfg.kda.out_gate,
    }
    return lambda h, lp: (_kda_partial(h, lp, heads_local, kda), None)


def _kda_partial(h, lp, n_heads_local, kda):
    """The KDA mixer (:class:`DeltaAttention`) on a full-sequence
    activation, heads column-parallel: the row-parallel PARTIAL output.
    The sizes are the tree's (gate projections through a rank where it
    holds ``wf_a`` / ``wf_b`` and ``wg_a`` / ``wg_b``); ``kda`` carries what
    the shapes do not say, the gate's ``lower_bound`` (``None``: the gate
    without a bound, and the core's split by halving), the write strength's
    ``beta_scale`` and the output norm's ``eps``.  The matmuls
    take the activations' type; the convolutions, SiLU, the L2 norms, the
    gate, beta, the core and the output norm are float32, in either
    lowering of the three chains round the core (``ops.kda``: ``conv_in``
    from a projection to q, k or v, ``decay_in`` to the log-decay,
    ``gated_out`` from ``o`` to what ``wo`` takes; at heads of whole lanes
    each is one Mosaic kernel forward and one backward that keep the
    chain's float32 values in VMEM and save only the projection, at any
    other shape XLA's fusions).  Everything but the core runs under the
    device scope ``accl.attn::kda_proj``, the core (from normalised q, k,
    v, the log-decay and beta to ``o``: ``ops.kda.kda_chunked``) under
    ``accl.attn::kda``.

    Under ``cfg.remat`` the block keeps the five bf16 projections the
    chains read (``h wq``, ``h wk``, ``h wv``, the decay gate's and the
    output gate's, the FINAL product where a gate goes through a rank:
    ``KEPT_UNDER_REMAT``; 5 x B T d_inner x 2 bytes, 671,088,640 a layer at
    2 x 8,192 x 4,096 or 1 x 8,192 x 8,192): the backward replays the
    chains and the core from them and multiplies none of them out twice.
    Nothing else is named: ``o`` and the core's saved set (1.07 GB a layer)
    do not fit six layers, ``wo``'s product and beta's are replayed.

    A tree with ``wa`` (``DeltaAttention.head_decay``: Gated DeltaNet) has
    ONE log-decay a head a token, ``g`` (B, H, T, 1), from a product of H
    columns that is replayed like beta's (four projections are kept: 8,192 x
    17,280 x 2 bytes at heads of 96 / 192); v, the output gate and ``wo``'s
    rows are as wide as ``wv`` says, and ``kda["out_gate"]`` ``"silu"`` gates
    the normed output by a SiLU.  The core then runs ``ops.kda``'s padded
    path and the chains their XLA forms (heads of 96 and 192 are no whole
    lanes)."""
    from ...ops.kda import conv_in, decay_in, gated_out, kda_chunked

    H = n_heads_local
    f32 = jnp.float32
    with device_scope("accl.attn::kda_proj"):
        proj = lambda w: kept_under_remat(h @ lp[w])
        q = conv_in(proj("wq"), lp["conv_q"], H, unit=True,
                    scale=(lp["wq"].shape[1] // H) ** -0.5)
        k = conv_in(proj("wk"), lp["conv_k"], H, unit=True)
        v = conv_in(proj("wv"), lp["conv_v"], H, unit=False)
        # a gate through a rank keeps its FINAL product
        through = lambda w: kept_under_remat(
            h @ lp[w] if w in lp else (h @ lp[w + "_a"]) @ lp[w + "_b"]
        )
        bound = kda["lower_bound"]
        # a decay a head (``wa``, H columns): one log-decay a head a token,
        # (B, H, T, 1), a product too small to keep
        gate = h @ lp["wa"] if "wa" in lp else through("wf")
        g = decay_in(gate, lp["dt_bias"], lp["a_log"], bound)
        beta = jax.nn.sigmoid((h @ lp["wbeta"]).astype(f32)).transpose(0, 2, 1)
        if kda.get("beta_scale", 1.0) != 1.0:
            beta = beta * kda["beta_scale"]
    with device_scope("accl.attn::kda"):
        # (B, H, T, dv) f32; without a bound, the split by halving
        o = kda_chunked(q, k, v, g, beta, safe=bound is None)
    with device_scope("accl.attn::kda_proj"):
        o = gated_out(o, through("wg"), lp["o_norm"], kda["eps"], h.dtype,
                      silu=kda.get("out_gate") == "silu")
        return o @ lp["wo"]
