"""Blockwise online-softmax attention — the flagship's fused path.

The naive form materializes the (T, T) score matrix per head through
``jax.nn.softmax``: at the flagship bench shape (B=8, H=32, T=1024,
bf16) that is ~0.5 GB of HBM score traffic per layer, pure bandwidth
with no MXU work — the memory ceiling the reference's datapath never
pays because its reduce pipeline streams.  This module computes the
same attention as a scan of (block_q x block_k) tiles with the running
(max, denominator, numerator) state of online softmax [Milakov &
Gimelshein; FlashAttention]: per-tile intermediates stay in registers/
VMEM-sized values, HBM sees only q/k/v/o.

Fully differentiable (the scans are plain lax control flow) and
remat-annotated per q-block, so the backward recomputes tiles instead
of storing them — the same FLOPs-for-HBM trade ``jax.checkpoint`` makes
everywhere else in the stack.

The Pallas form of the same fold (hand-scheduled DMAs, the ring
variant) lives in ``ops/pallas/attention.py``; this XLA form is the
trainable default — every op fuses under jit on any backend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .pallas.attention import _mxu_precision

_NEG = -1e30


def block_diffusion_visible(q_pos, k_pos, L: int, block: int):
    """Whether query position ``q_pos`` sees key position ``k_pos`` under
    the block-diffusion layout ``(L, block)``, elementwise: the sequence
    is ``2 L`` rows, a NOISY copy at ``0..L`` and the CLEAN sequence at
    ``L..2L``, both in blocks of ``block``.  Noisy sees noisy of the same
    block and clean of the blocks strictly before; clean sees clean of
    the blocks up to and including its own; nothing sees a noisy key
    outside its block (BD3-LM's training mask)."""
    q_noisy, k_noisy = q_pos < L, k_pos < L
    qb = jnp.where(q_noisy, q_pos, q_pos - L) // block
    kb = jnp.where(k_noisy, k_pos, k_pos - L) // block
    return jnp.where(
        k_noisy, q_noisy & (qb == kb),
        jnp.where(q_noisy, kb < qb, kb <= qb),
    )


def _attend_single(q, k, v, causal: bool, bq: int, bk: int, t_real: int,
                   window=None, scale=None, block_diffusion=None, sink=None):
    """One head, q and k (T, D) and v (T, Dv): scan q blocks; fold k
    blocks with online softmax.

    ``t_real`` masks padded key positions (T may be padded to block
    multiples by the wrapper); ``window`` keeps a query's last ``window``
    keys, its own among them (every tile is still folded: this is the
    CPU tier and the fallback past the flash kernels' VMEM gate);
    ``sink``: this head's scalar, the fold's first term (``m = sink, l =
    1``: a key without a value in every row's softmax)."""
    T, D = q.shape
    nq, nk = T // bq, T // bk
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def per_q_block(iq, qb):
        q_pos = iq * bq + jnp.arange(bq)

        def fold(carry, jk):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, jk * bk, bk)
            vb = lax.dynamic_slice_in_dim(v, jk * bk, bk)
            # both matmuls run in the INPUT dtype (bf16 hits the MXU's
            # fast path — an f32 upcast here quarters matmul throughput
            # on v5e) with f32 accumulation; the softmax state (m, l,
            # acc) stays f32 for numerical fidelity and the probs cast
            # back down for the p @ v matmul
            s = lax.dot_general(
                qb, kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_mxu_precision(qb.dtype),
            ) * scale  # (bq, bk)
            k_pos = jk * bk + jnp.arange(bk)
            mask = k_pos[None, :] < t_real
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            if block_diffusion is not None:
                mask &= block_diffusion_visible(
                    q_pos[:, None], k_pos[None, :], *block_diffusion
                )
            s = jnp.where(mask, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            acc_new = acc * alpha + lax.dot_general(
                p.astype(vb.dtype), vb,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=_mxu_precision(vb.dtype),
            )
            return (m_new, l_new, acc_new), None

        # derive the init from the operand (full_like/zeros_like) so its
        # varying-manual-axes type matches the fold output under
        # shard_map — fresh constants would be axis-invariant and fail
        # the scan carry check
        init = (
            jnp.full_like(qb[:, :1], _NEG, dtype=jnp.float32),
            jnp.zeros_like(qb[:, :1], dtype=jnp.float32),
            jnp.zeros_like(v[:bq], dtype=jnp.float32),
        )
        if sink is not None:
            init = (
                init[1] + sink.astype(jnp.float32), init[1] + 1.0, init[2]
            )
        (m, l, acc), _ = lax.scan(fold, init, jnp.arange(nk))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    # remat per q-block: the backward re-folds the tiles instead of
    # keeping every (bq, bk) p matrix alive
    per_q_block = jax.checkpoint(per_q_block, static_argnums=())
    out = jax.vmap(per_q_block)(
        jnp.arange(nq), q.reshape(nq, bq, D)
    )
    return out.reshape(T, v.shape[-1])


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    window: int | None = None,
    scale: float | None = None,
    block_diffusion: tuple | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Causal (or full) attention over ``(B, H, T, Dh)`` operands without
    materializing the (T, T) score matrix.  Exact (not approximate):
    matches the naive softmax form to float tolerance.  v, and so the
    result, may be another width than q and k; ``scale`` multiplies the
    scores (``Dh ** -0.5`` where not given).

    ``window=W`` (causal only): query ``i`` sees keys ``0 <= i - j < W``,
    as ``ops.pallas.flash_attention`` has it.  ``block_diffusion=(L, B)``
    is its block-diffusion layout (:func:`block_diffusion_visible`) in
    place of both, every tile folded under the dense mask.  ``sink``
    ``(H,)`` is its scalar a query head in each row's softmax, a key
    without a value (causal only).

    Block sizes clamp to the (padded) sequence length; T is padded to a
    block multiple internally and the pad keys are masked out.

    Grouped-query attention: k/v may carry fewer heads (``H % Hkv == 0``);
    they are expanded logically (broadcast per group) before the fold —
    the XLA form pays the expansion in activation reads, the Pallas flash
    kernel's index-map sharing avoids it."""
    B, H, T, Dh = q.shape
    Hkv = k.shape[1]
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"a window ({window}) is causal and at least 1 key wide"
        )
    if block_diffusion is not None:
        if window is not None or T != 2 * block_diffusion[0]:
            raise ValueError(
                f"block_diffusion=(L, B) lays out T = 2 L rows and has no "
                f"window, got T={T}, {block_diffusion}, window={window}"
            )
        causal = False
    if sink is not None and (sink.shape != (H,) or not causal):
        raise ValueError(
            f"sink is one scalar a query head, ({H},), of a causal softmax; "
            f"got {sink.shape}, causal={causal}"
        )
    if Hkv != H:
        if Hkv <= 0 or H % Hkv:
            raise ValueError(
                f"q heads ({H}) must be a multiple of kv heads ({Hkv})"
            )
        G = H // Hkv
        k = jnp.broadcast_to(
            k[:, :, None], (B, Hkv, G, T, Dh)
        ).reshape(B, H, T, Dh)
        v = jnp.broadcast_to(
            v[:, :, None], (B, Hkv, G, T, v.shape[-1])
        ).reshape(B, H, T, v.shape[-1])
    bq = min(block_q, T) if T > 0 else block_q
    bk = min(block_k, T) if T > 0 else block_k
    pad = (-T) % max(bq, bk)
    # one common padded length keeps both block counts integral
    Tp = T + pad
    bq = min(bq, Tp)
    bk = min(bk, Tp)
    if Tp % bq:
        bq = Tp  # tiny sequences: single block
    if Tp % bk:
        bk = Tp
    if pad:
        padding = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q = jnp.pad(q, padding)
        k = jnp.pad(k, padding)
        v = jnp.pad(v, padding)
    single = functools.partial(
        _attend_single, causal=causal, bq=bq, bk=bk, t_real=T, window=window,
        scale=scale, block_diffusion=block_diffusion,
    )
    if sink is None:
        out = jax.vmap(jax.vmap(single))(q, k, v)
    else:
        a_head = lambda q, k, v, s: single(q, k, v, sink=s)
        out = jax.vmap(jax.vmap(a_head), in_axes=(0, 0, 0, None))(q, k, v, sink)
    return out[:, :, :T]
