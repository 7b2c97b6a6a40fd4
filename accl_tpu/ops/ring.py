"""Algorithm-faithful ring collectives: explicit ppermute pipelines.

The reference's headline allreduce is a *segmented ring reduce-scatter +
ring allgather* executed by the firmware against the FPGA dataplane
(``ccl_offload_control.c:1888-2071``, with block/tail handling at
:1900-1912 and fused recv-reduce-send hops).  XLA's built-in collectives
normally make this choice for us; this module exposes the same algorithm as
an explicit ``lax.ppermute`` pipeline so the reference's tuning surface
(block layout, segment count, hop structure) stays programmable — the basis
for overlap-style schedules (ring attention et al.) layered on top.

All functions run inside ``shard_map`` over a named axis.  Every hop is a
static-permutation ``collective-permute``, which on TPU maps to neighbor
DMAs over ICI.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..constants import ReduceFunction


def _combine(function: ReduceFunction):
    if function == ReduceFunction.SUM:
        return jnp.add
    if function == ReduceFunction.MAX:
        return jnp.maximum
    raise ValueError(f"unsupported reduce function {function}")


def _next_perm(size: int):
    return [(i, (i + 1) % size) for i in range(size)]


def _pad_to_blocks(x: jax.Array, size: int):
    n = x.shape[0]
    block = -(-n // size)
    pad = block * size - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x.reshape((size, block) + x.shape[1:]), block, pad


def ring_reduce_scatter(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
) -> jax.Array:
    """Ring reduce-scatter: P-1 hops, each a fused recv-reduce-send
    (ref c:1782-1851).  Input: the full local operand (same shape on every
    rank).  Output: this rank's reduced block (padded size n/P)."""
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    op = _combine(function)
    blocks, block, _ = _pad_to_blocks(x, size)
    perm = _next_perm(size)

    def take(b, c):
        return lax.dynamic_slice_in_dim(b, (c % size) * block, block, axis=0)

    # step 1 sends own block (idx-1); step s accumulates chunk (idx-1-s)
    send = take(blocks.reshape((-1,) + x.shape[1:]), idx - 1)

    def body(s, send):
        recv = lax.ppermute(send, axis_name, perm)
        c = idx - 1 - s
        return op(recv, take(blocks.reshape((-1,) + x.shape[1:]), c))

    acc = lax.fori_loop(1, size, body, send) if size > 1 else send
    return acc  # rank idx holds reduced block idx


def ring_allgather(x: jax.Array, axis_name: str) -> jax.Array:
    """Ring allgather: store-and-relay around the ring (ref c:1402-1500).
    Input: this rank's block; output: all blocks concatenated."""
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    block = x.shape[0]
    perm = _next_perm(size)
    out = jnp.zeros((size * block,) + x.shape[1:], x.dtype)
    out = lax.dynamic_update_slice_in_dim(out, x, idx * block, axis=0)

    def body(s, carry):
        out, send = carry
        recv = lax.ppermute(send, axis_name, perm)
        origin = jnp.mod(idx - 1 - s, size)
        out = lax.dynamic_update_slice_in_dim(out, recv, origin * block, axis=0)
        return out, recv

    if size > 1:
        out, _ = lax.fori_loop(0, size - 1, body, (out, x))
    return out


def ring_allreduce(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
    num_segments: int = 1,
) -> jax.Array:
    """Segmented ring allreduce = ring reduce-scatter + ring allgather
    (ref allreduce c:1888-2071).

    ``num_segments`` splits every block transfer into independent segment
    pipelines (the reference's eager segmentation / dm_seg tuning knob):
    segment pipelines interleave across hops, overlapping wire time with
    reduce time.  With 1 segment this is the classic 2(P-1)-hop ring."""
    n = x.shape[0]
    size = lax.axis_size(axis_name)
    if size == 1:
        return x
    if num_segments > 1:
        segs = _pad_to_blocks(x, num_segments)[0]
        out = jax.vmap(
            lambda seg: ring_allreduce(seg, axis_name, function, 1),
            spmd_axis_name=axis_name,
        )(segs)
        return out.reshape(-1)[:n]
    acc = ring_reduce_scatter(x, axis_name, function)
    full = ring_allgather(acc, axis_name)
    return full[:n]


def ring_pipeline(
    x: jax.Array,
    axis_name: str,
    step_fn,
    steps: int,
) -> jax.Array:
    """Generic ring schedule: repeatedly shift a buffer to the next neighbor
    and fold it with ``step_fn(carry, received, step)`` — the composable
    substrate for overlap patterns (ring attention-style consumers build on
    this the way the reference exposes its segmented ring machinery)."""
    size = lax.axis_size(axis_name)
    perm = _next_perm(size)

    def body(s, carry):
        state, send = carry
        recv = lax.ppermute(send, axis_name, perm)
        state = step_fn(state, recv, s)
        return state, recv

    state, _ = lax.fori_loop(0, steps, body, (x, x))
    return state
