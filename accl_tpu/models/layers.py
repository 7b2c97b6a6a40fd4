"""The small pieces a block and every mixer share, below both:
``models/transformer.py`` and the modules of ``models/mixers/`` import them
from here (a mixer never imports ``transformer``).  The norms, the rotary
tables and their rotation, whole-projection QK-norm, how a weight's columns,
rows and heads shard over tp, a matrix's seeded draw, what both softmax mixers
check of a layer kind, and the softmax core with its three lowerings
(``resolve_attention`` picks one from the shapes)."""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..constants import ReduceFunction
from ..ops import collectives


def _tp_specs(cfg):
    """``(col, row, heads)``: the specs of a column-parallel matrix (output
    dim on tp), a row-parallel one (input dim on tp) and a vector over
    heads.  Under context parallelism the tp axis carries the SEQUENCE
    ring, so every weight is replicated over it (dp still shards the
    batch)."""
    if cfg.context_parallel:
        return P(None, None), P(None, None), None
    return P(None, "tp"), P("tp", None), "tp"


def _normal(key, shape, dtype):
    """A matrix's seeded values: normal at 0.02."""
    return jax.random.normal(key, shape, dtype) * 0.02


def _own_heads(kind) -> bool:
    """Whether a ``LayerKind`` says anything of its OWN attention heads
    (``kv_heads``, ``rope_base``, ``sink``, ``heads``)."""
    own = (kind.kv_heads, kind.rope_base, kind.heads)
    return kind.sink or any(x is not None for x in own)


def _check_rotation(cfg, kind, i) -> None:
    """A softmax layer rotates only where the model does."""
    if kind.rope and cfg.pos_embedding != "rope":
        raise ValueError(
            f"layer {i} rotates but pos_embedding is "
            f"{cfg.pos_embedding!r}"
        )


def _layernorm(x, scale, eps: float = 1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale


def _rmsnorm(x, scale, tp_axis=None, eps: float = 1e-5):
    """RMSNorm, statistics in f32 and the scale applied in the input's
    type.  ``tp_axis``: the last dim is a tp shard of the normed width
    (QK-norm over head-sharded projections), so the mean square is taken
    over the whole width with one allreduce of a scalar a row."""
    x32 = x.astype(jnp.float32)
    ss = jnp.sum(x32 * x32, axis=-1, keepdims=True)
    width = x.shape[-1]
    if tp_axis is not None:
        ss = collectives.allreduce(ss, tp_axis, ReduceFunction.SUM)
        width = width * jax.lax.axis_size(tp_axis)
    return (x32 * jax.lax.rsqrt(ss / width + eps)).astype(x.dtype) * scale


_NORMS = {"layernorm": _layernorm, "rmsnorm": _rmsnorm}


def _norm_fn(cfg):
    """The config's norm at its ``norm_eps``."""
    fn = _NORMS[cfg.norm]
    return fn if cfg.norm_eps == 1e-5 else partial(fn, eps=cfg.norm_eps)


def _qk_norm(q, k, lp, tp_axis, eps: float = 1e-5):
    """A layer with ``q_norm``/``k_norm`` scales RMS-normalises the whole
    projected q and k (every head at once, over ``tp_axis`` where the
    heads are sharded) before the split into heads, at the
    configuration's ``norm_eps``."""
    if "q_norm" not in lp:
        return q, k
    return (
        _rmsnorm(q, lp["q_norm"], tp_axis, eps),
        _rmsnorm(k, lp["k_norm"], tp_axis, eps),
    )


def _rope_tables(positions, half: int, base: float, inv_freq=None,
                 table_scale: float = 1.0):
    """cos/sin tables for rotary embedding at the given absolute
    ``positions`` (shape (T,); traced values fine — decode passes its
    dynamic cursor).  Computed once per attention site and shared by
    the q and k rotations (and across layers on the decode path), so
    scanned/rematerialized blocks don't rebuild the pow/cos/sin chain
    per layer.  ``inv_freq`` (``half`` of them) are the configuration's
    own inverse frequencies (YaRN, ``TransformerConfig.rope_inv_freq``)
    in place of ``base``'s; ``table_scale`` multiplies both tables."""
    if inv_freq is None:
        freqs = jnp.asarray(base, jnp.float32) ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half
        )
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, half)
    if table_scale != 1.0:
        return jnp.cos(ang) * table_scale, jnp.sin(ang) * table_scale
    return jnp.cos(ang), jnp.sin(ang)


def _rope_rotate(x, tables):
    """Rotary position embedding [RoFormer]: rotate each (i, i+half)
    feature pair of every head by position*freq_i.  ``x`` is
    (B, H, T, hd) with hd even; ``tables`` from :func:`_rope_tables`.
    Rotation runs in f32, the result is cast back so bf16 activations
    stay bf16 (the dtype-discipline rule everywhere in this file)."""
    cos, sin = tables
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# measured crossover on v5e (see TransformerConfig.attention): with the
# block=512 flash kernel the fused form wins the full train step from
# T=1024 up (75.4% vs 69.5% MFU at T=1024; at T=4096 it is the only
# form that fits HBM), so auto resolves to a fused form at/above this
# and to naive only below it (tiny-T padding-overhead regime)
_AUTO_FUSED_MIN_T = 1024
# flash holds whole K/V in VMEM per batch-head (and its one backward
# kernel whole Q, dO, the dq block and an f32 dq accumulator: six times
# K's bytes plus T x hd x 4, which that call computes from the shapes
# and passes as its own VMEM limit — 24 MiB at this gate's edge): auto
# uses it only while K+V fit this budget (4 MiB = T 8192 at hd<=128
# bf16; the gate scales with the PADDED head dim and dtype width, so
# wide-head or f32 configs fall back to the streaming XLA fold instead
# of failing Mosaic's VMEM allocation).  A head whose rotating part is
# passed APART (``q_rope`` / ``k_rope``: the latent mixer's, and a
# ``HeadGeometry.rope_dim``'s) is gated by each part's own padded width:
# the gate sees the first part alone (``_attention``), the second rides
# beside it on its own lanes.  The MiMo-V2.5 cell is the case: a head of
# 192 as ONE operand pads to 256 lanes and stops ``auto`` at T = 4,096;
# as 128 columns without position beside a rotating 64 (padded to 128)
# it runs the kernels at 8,192
_AUTO_FLASH_KV_BYTES = 4 * 2**20


def _auto_flash_fits(q) -> bool:
    import jax.numpy as jnp

    if q.dtype == jnp.float16:
        # Mosaic's TPU lowering rejects f16 matmul operands (ValueError
        # at compile), so auto must never route f16 into the flash
        # kernel — it falls through to the XLA blockwise fold instead.
        # Explicit
        # attention="flash" still surfaces the kernel's own f16 error.
        return False
    Dp = -(-q.shape[-1] // 128) * 128  # lane-padded head dim
    return 2 * q.shape[2] * Dp * q.dtype.itemsize <= _AUTO_FLASH_KV_BYTES


def resolve_attention(impl: str, q) -> str:
    """The lowering ``impl`` names for a per-device ``(B, H, T, hd)``
    query (anything with ``shape`` and ``dtype``).  ``"auto"`` resolves
    by sequence length and backend: naive under ``_AUTO_FUSED_MIN_T``;
    at/above it the Pallas flash kernel on TPU while its K/V tiles fit
    VMEM (:func:`_auto_flash_fits`), and the XLA blockwise fold off TPU
    or past that gate.  Callers that must know which one ran (the chip
    smoke) ask here instead of guessing."""
    if impl != "auto":
        return impl
    if q.shape[2] < _AUTO_FUSED_MIN_T:
        return "naive"
    if jax.default_backend() == "tpu" and _auto_flash_fits(q):
        return "flash"  # Mosaic-compiled; trainable via custom_vjp
    return "blockwise"


def _attention(q, k, v, impl: str = "naive", causal: bool = True,
               window: Optional[int] = None, scale: Optional[float] = None,
               q_rope=None, k_rope=None, block_diffusion=None, sink=None):
    """Attention; q,k,v: (B, H, T, hd); ``causal=False`` is the
    bidirectional (encoder) form; ``window`` (causal only) keeps a
    query's last ``window`` keys, its own among them, in every lowering.
    v (and the result) may be another width than q and k; ``scale``
    multiplies the scores (``hd ** -0.5`` where not given); ``q_rope``
    (B, H, T, dr) and ``k_rope`` (B, fewer heads, T, dr) are a second
    part of q and k whose product is added to the scores: the flash
    kernels take them as they are (the few key heads shared through the
    index map), the XLA forms get them joined onto q and k.
    ``block_diffusion=(L, B)`` is the block-diffusion layout of ``T = 2 L``
    rows in place of ``causal``: tile lists in the flash kernels, a dense
    mask in the XLA forms (``ops.attention.block_diffusion_visible``).
    ``sink`` (H,): one learned scalar a query head that stands in every
    row's softmax as a key without a value, in every lowering (the carry's
    first term in the two folds, one more column here).

    ``impl="auto"`` resolves through :func:`resolve_attention`;
    ``"blockwise"`` runs the fused online-softmax fold (no (T, T) score
    matrix in HBM); ``"naive"`` is the materialized-scores baseline."""
    # with a second score part the flash kernels hold, a head, k's first
    # part and v as they do without one, and beside them the second part
    # of as few heads as it has (one under MLA): ``auto`` is decided on the
    # first part's width
    impl = resolve_attention(impl, q)
    if q_rope is not None:
        if scale is None:
            scale = (q.shape[-1] + q_rope.shape[-1]) ** -0.5
        if impl != "flash":
            # k's second part on as many heads as k has (all of q's under
            # the latent mixer, whose ``expand(k)`` is then the identity)
            B, H, T = q.shape[0], k.shape[1], q.shape[2]
            expand = lambda t: jnp.broadcast_to(
                t[:, :, None], (B, t.shape[1], H // t.shape[1], T, t.shape[-1])
            ).reshape(B, H, T, t.shape[-1])
            q = jnp.concatenate([q, q_rope], axis=-1)
            k = jnp.concatenate([expand(k), expand(k_rope)], axis=-1)
            q_rope = k_rope = None
    # no window, no scale: each lowering is called as it was before there
    # was one (tests put a spy of the old signature in a lowering's place)
    windowed = {} if window is None else {"window": window}
    if scale is not None:
        windowed["scale"] = scale
    if block_diffusion is not None:
        if window is not None:
            raise ValueError("block diffusion has no window")
        windowed["block_diffusion"] = block_diffusion
    if sink is not None and impl != "naive":
        windowed["sink"] = sink
    if impl == "blockwise":
        from ..ops.attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, **windowed)
    if impl == "flash":
        # the Pallas kernel owns the fold schedule; its custom_vjp
        # backward kernel makes it trainable (rebuilds probability tiles
        # from the saved logsumexp — no (T, T) residual)
        from ..ops.pallas.attention import flash_attention

        if q_rope is not None:
            windowed.update(q_rope=q_rope, k_rope=k_rope)
        return flash_attention(q, k, v, causal=causal, **windowed)
    if impl != "naive":
        raise ValueError(f"unknown attention impl {impl!r}")
    B, H, T, hd = q.shape
    Hkv = k.shape[1]
    # grouped-query attention folds the group into the einsum (each kv
    # head broadcasts across its G query heads; k/v are never expanded)
    qg = q.reshape(B, Hkv, H // Hkv, T, hd)
    # matmuls stay in the input dtype (bf16 on the MXU's fast path) with
    # f32 accumulation; softmax statistics run in f32 and the probs cast
    # back down for the second matmul.  The scale is a PYTHON float — a
    # NumPy scalar (np.sqrt) is strongly typed and would silently promote
    # bf16 activations to f32 through the rest of the block.
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(hd) if scale is None else scale)
    if window is not None and not causal:
        raise ValueError("a window is causal")
    if block_diffusion is not None:
        from ..ops.attention import block_diffusion_visible

        pos = jnp.arange(T)
        mask = block_diffusion_visible(
            pos[:, None], pos[None, :], *block_diffusion
        )
        scores = jnp.where(mask, scores, -1e30)
    elif causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), bool), -window)
        scores = jnp.where(mask, scores, -1e30)
    if sink is not None:
        # one more column a row, the head's scalar; it takes its share of
        # the row's probability and has no value
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, H // Hkv, 1, 1),
            (*scores.shape[:-1], 1),
        )
        scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :-1].astype(v.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, T, v.shape[-1])
