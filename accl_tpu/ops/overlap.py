"""Communication/compute overlap primitives.

The reference's segmented ring pipelines overlap the wire with the
reduction per chunk (firmware hot loop, ccl_offload_control.c:1940-1982
— recv/reduce/send in flight simultaneously).  At the model layer the
TPU-native form of that idea is the *ring-scheduled matmul*: a
row-parallel matmul whose cross-rank reduction is decomposed into ring
hops interleaved with the matmul's own output chunks, so XLA can hide
each ``ppermute`` behind the next chunk's MXU work instead of waiting
for one monolithic matmul before one monolithic collective.

``matmul_reduce_scatter`` is the fused form of
``reduce_scatter(x @ w, axis)`` (the Megatron-SP row-parallel exit);
``matmul_allreduce`` adds the allgather leg.  Both are exact — the
decomposition reorders a sum — and both run anywhere ``shard_map``
runs; the overlap benefit appears on real ICI where the compiler
schedules the permute DMA concurrently with the MXU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives


def matmul_reduce_scatter(
    x: jax.Array, w: jax.Array, axis_name: str
) -> jax.Array:
    """``reduce_scatter(x @ w, axis_name)`` with the reduction ring
    interleaved into the matmul's output chunks.

    ``x``: (..., K_local), ``w``: (K_local, N) — the row-parallel layout
    (K sharded over the axis).  N must divide by the axis size; rank r
    returns chunk r of the summed product, shape (..., N/size).

    Schedule: at step s every rank computes the PARTIAL product for the
    chunk that is ``size-1-s`` hops upstream of its own, adds the
    accumulator arriving from its neighbor, and forwards — after
    ``size`` steps the accumulator holds the fully-summed home chunk.
    Each ppermute is independent of the next chunk's matmul, which is
    what lets the scheduler overlap wire and MXU.
    """
    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    N = w.shape[-1]
    if N % size:
        raise ValueError(f"N ({N}) must divide by axis size ({size})")
    blk = N // size
    perm = [(i, (i + 1) % size) for i in range(size)]

    acc = jnp.zeros(x.shape[:-1] + (blk,), jnp.promote_types(x.dtype, w.dtype))
    for s in range(size):
        # chunk index this rank contributes to at step s: after the
        # remaining (size-1-s) forward hops it lands on its home rank
        c = jnp.mod(me + (size - 1 - s), size)
        w_c = lax.dynamic_slice_in_dim(w, c * blk, blk, axis=-1)
        partial = x @ w_c
        if s:
            acc = lax.ppermute(acc, axis_name, perm)
        acc = acc + partial
    # result dtype matches reduce_scatter(x @ w): the matmul's natural
    # promoted dtype, NOT a downcast to the activation dtype
    return acc


def matmul_allreduce(
    x: jax.Array, w: jax.Array, axis_name: str
) -> jax.Array:
    """``allreduce(x @ w, axis_name)`` as the ring-scheduled
    reduce-scatter above plus an allgather of the chunks — the fused
    row-parallel matmul+allreduce of tensor parallelism."""
    scattered = matmul_reduce_scatter(x, w, axis_name)
    # invariant form: the allreduce result is replicated by construction,
    # and callers may legitimately claim so in their out_specs
    return collectives.allgather_invariant(
        scattered, axis_name, axis=scattered.ndim - 1
    )
