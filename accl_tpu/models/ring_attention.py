"""Ring attention: sequence parallelism on the framework's ring substrate.

Long-context attention with the sequence sharded over a mesh axis: K/V
blocks rotate around the ring (one ``ppermute`` hop per step — neighbor DMA
on ICI) while each device folds the visiting block into its local queries'
online-softmax state.  Compute overlaps the wire exactly the way the
reference's segmented ring pipelines overlap recv/reduce/send hops
(``ccl_offload_control.c:1888-2071``); SURVEY.md §5 calls that machinery the
substrate such strategies sit on — this is the strategy, sitting on it.

Causal masking is handled per-visiting-block via the block's origin rank:
origin > self  -> fully masked (future), origin < self -> unmasked (past),
origin == self -> triangular.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def _fold_block(q, k_blk, v_blk, o, m, l, block_mask):
    """Online-softmax accumulation of one K/V block.

    q: (B,H,Tq,D); k_blk/v_blk: (B,Hkv,Tk,D) with ``Hkv`` dividing ``H``
    — under grouped-query attention the K/V blocks carry only the kv
    heads (query head ``h`` reads kv head ``h // (H//Hkv)``, the same
    kv-major grouping as the dense lowerings), which is what lets the
    ring rotate the UNEXPANDED tensors: G = H/Hkv times less ICI traffic
    per hop.  o: (B,H,Tq,D) f32 running numerator; m: (B,H,Tq,1) f32
    running max; l: (B,H,Tq,1) f32 running denominator.  block_mask:
    (Tq,Tk) bool, True = attend.

    Matmuls stay in the operand dtype (bf16 on the MXU fast path) with
    f32 accumulation; the online-softmax state is f32."""
    B, H, Tq, D = q.shape
    Hkv = k_blk.shape[1]
    if H == Hkv:
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        )
    else:
        G = H // Hkv
        scores = jnp.einsum(
            "bhgqd,bhkd->bhgqk",
            q.reshape(B, Hkv, G, Tq, D),
            k_blk,
            preferred_element_type=jnp.float32,
        ).reshape(B, H, Tq, -1)
    scores = scores * (1.0 / math.sqrt(D))
    scores = jnp.where(block_mask[None, None], scores, -jnp.inf)
    m_blk = scores.max(axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked blocks (m_new == -inf): contribute nothing
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe)
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
    pv = p.astype(v_blk.dtype)
    if H == Hkv:
        acc = jnp.einsum(
            "bhqk,bhkd->bhqd", pv, v_blk,
            preferred_element_type=jnp.float32,
        )
    else:
        G = H // Hkv
        acc = jnp.einsum(
            "bhgqk,bhkd->bhgqd",
            pv.reshape(B, Hkv, G, Tq, -1),
            v_blk,
            preferred_element_type=jnp.float32,
        ).reshape(B, H, Tq, D)
    o = o * alpha + acc
    l = l * alpha + p.sum(axis=-1, keepdims=True)
    return o, m_new, l


def _fold_visiting(q, k_blk, v_blk, o, m, l, mask, block_k):
    """Fold one visiting K/V block, optionally in ``block_k``-sized
    sub-chunks so the per-hop score tile is (Tq, block_k) instead of
    (Tq, T_local) — the within-hop analogue of the blockwise/flash
    lowerings' memory contract (the fold is already incremental, so
    chunking is just more folds)."""
    Tk = k_blk.shape[2]
    if block_k is None or block_k >= Tk:
        return _fold_block(q, k_blk, v_blk, o, m, l, mask)
    if Tk % block_k:
        raise ValueError(
            f"block_k ({block_k}) must divide the local K length ({Tk})"
        )

    def chunk(c, carry):
        o, m, l = carry
        ks = lax.dynamic_slice_in_dim(k_blk, c * block_k, block_k, axis=2)
        vs = lax.dynamic_slice_in_dim(v_blk, c * block_k, block_k, axis=2)
        mk = lax.dynamic_slice_in_dim(mask, c * block_k, block_k, axis=1)
        return _fold_block(q, ks, vs, o, m, l, mk)

    return lax.fori_loop(0, Tk // block_k, chunk, (o, m, l))


def _ring_scan(q, k, v, axis_name, mask_for, block_k=None):
    """The shared rotation: fold the own block, then rotate K/V around
    the ring P-1 times, folding each visiting block under
    ``mask_for(origin)``.  Both sequence layouts (contiguous and
    striped) are this scan with different mask functions."""
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]

    o = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), -jnp.inf, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)

    o, m, l = _fold_visiting(q, k, v, o, m, l, mask_for(idx), block_k)

    def body(s, carry):
        o, m, l, k_cur, v_cur = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        origin = jnp.mod(idx - 1 - s, size)  # whose block just arrived
        o, m, l = _fold_visiting(
            q, k_cur, v_cur, o, m, l, mask_for(origin), block_k
        )
        return o, m, l, k_cur, v_cur

    if size > 1:
        o, m, l, _, _ = lax.fori_loop(0, size - 1, body, (o, m, l, k, v))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    block_k: int | None = None,
) -> jax.Array:
    """Attention over the full (sharded) sequence.  q: (B,H,T_local,D)
    per device; k,v: (B,Hkv,T_local,D) with Hkv dividing H (GQA rotates
    the unexpanded kv heads — G× less ICI per hop); returns
    (B,H,T_local,D) — this device's query rows attended over every
    device's keys."""
    idx = lax.axis_index(axis_name)
    Tq, Tk = q.shape[2], k.shape[2]
    tri = jnp.tril(jnp.ones((Tq, Tk), bool))
    full = jnp.ones((Tq, Tk), bool)

    def mask_for(origin):
        if not causal:
            return full
        return jnp.where(
            origin == idx, tri, jnp.where(origin < idx, full, jnp.zeros_like(full))
        )

    return _ring_scan(q, k, v, axis_name, mask_for, block_k)


def reference_attention(q, k, v, causal: bool = True) -> jax.Array:
    """Single-device ground truth for tests: q,k,v (B,H,T,D) full sequence."""
    T = q.shape[2]
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(q.shape[-1]))
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# striped layout: load-balanced causal ring attention
# ---------------------------------------------------------------------------


def stripe_sequence(x: jax.Array, size: int, axis: int = 2) -> jax.Array:
    """Reorder a full sequence so CONTIGUOUS sharding over ``size`` ranks
    yields the STRIPED (round-robin) assignment: shard ``r``'s local
    position ``t`` holds global token ``t * size + r``.

    Under causal masking the striped layout makes every (rank, visiting
    block) pair's mask triangular — each ring hop does equal work on
    every rank, where the contiguous layout leaves rank 0 idle for all
    but its own block (the Striped Attention load-balance argument).

    Implemented as reshape+transpose (not a gather): XLA lowers it to a
    pure layout change, and it stays well-defined on explicitly-sharded
    operands (gather's output sharding is ambiguous there)."""
    T = x.shape[axis]
    if T % size:
        raise ValueError(f"sequence length {T} must divide by ring size {size}")
    Tl = T // size
    x = jnp.moveaxis(x, axis, -1)
    x = x.reshape(x.shape[:-1] + (Tl, size))  # (..., t, r): token t*size+r
    x = jnp.swapaxes(x, -2, -1)  # (..., r, t): shard r position t
    return jnp.moveaxis(x.reshape(x.shape[:-2] + (T,)), -1, axis)


def unstripe_sequence(x: jax.Array, size: int, axis: int = 2) -> jax.Array:
    """Inverse of :func:`stripe_sequence` (restore token order)."""
    T = x.shape[axis]
    if T % size:
        raise ValueError(f"sequence length {T} must divide by ring size {size}")
    Tl = T // size
    x = jnp.moveaxis(x, axis, -1)
    x = x.reshape(x.shape[:-1] + (size, Tl))  # (..., r, t): token t*size+r
    x = jnp.swapaxes(x, -2, -1)  # (..., t, r): flat index t*size+r
    return jnp.moveaxis(x.reshape(x.shape[:-2] + (T,)), -1, axis)


def striped_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    block_k: int | None = None,
) -> jax.Array:
    """Ring attention over STRIPED sequence shards (see
    :func:`stripe_sequence`): same rotation, same online-softmax fold,
    but the causal mask for a block from ``origin`` is triangular for
    every (rank, origin) pair —

        global q pos = tq * P + idx,  global k pos = tk * P + origin
        attend  <=>  tq > tk  or  (tq == tk and idx >= origin)

    so no rank ever folds a fully-masked (wasted) or fully-dense
    (bottleneck) block: the causal work is balanced across the ring,
    ~2x effective throughput at large P versus the contiguous layout.
    q: (B, H, T_local, D) striped shards; k, v may carry only the kv
    heads (B, Hkv, T_local, D) under GQA — they rotate unexpanded.
    Returns striped shards (B, H, T_local, D).
    """
    idx = lax.axis_index(axis_name)
    Tq, Tk = q.shape[2], k.shape[2]
    tri = jnp.tril(jnp.ones((Tq, Tk), bool))
    tri_strict = jnp.tril(jnp.ones((Tq, Tk), bool), k=-1)
    full = jnp.ones((Tq, Tk), bool)

    def mask_for(origin):
        if not causal:
            return full
        # diagonal ties break by rank order: idx >= origin attends
        return jnp.where(idx >= origin, tri, tri_strict)

    return _ring_scan(q, k, v, axis_name, mask_for, block_k)


# ---------------------------------------------------------------------------
# command-ring opt-in: attention hops as sequencer slots (FUSED_ATTN_HOP)
# ---------------------------------------------------------------------------


def fused_hop_partial(accl, kv_block, q_block, hop, scale=1.0,
                      comm=None, timeout_s=60.0):
    """One ring-attention hop issued as a command-ring slot
    (``FUSED_ATTN_HOP``): this rank's K/V block relays around the ring
    while the epilogue computes ``scale * q * kv_src`` against the
    block arriving from ``hop`` positions behind — the hop's partial
    score, produced inside the sequencer window instead of a ppermute
    + host-side fold round trip.

    ``kv_block`` and ``q_block`` are equal-width 1-D float blocks (a
    flattened head tile); ``hop`` is SPMD-uniform.  Returns the partial
    block, a host-side copy.  The shard_map ``ring_attention`` path
    above stays the jit-compiled form; this surface is for pipelines
    already driving collectives through the ACCL facade.
    """
    import numpy as np

    kv = np.asarray(kv_block, np.float32).ravel()
    q = np.asarray(q_block, np.float32).ravel()
    if kv.size != q.size:
        raise ValueError(
            f"kv block ({kv.size}) and q block ({q.size}) must be "
            "equal width — FUSED_ATTN_HOP packs them as one operand row"
        )
    send = accl.create_buffer_from(np.concatenate([kv, q]))
    out = accl.create_buffer(q.size, np.float32)
    with accl.batch():
        req = accl.fused_attn_hop(
            send, out, hop=hop, count=q.size, scale=scale, comm=comm,
            run_async=True,
        )
    if not req.wait(timeout_s):
        raise TimeoutError("fused attention hop timed out")
    req.check()
    out.sync_from_device()
    return out.data[:out.count].copy()
