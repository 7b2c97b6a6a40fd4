"""SPMD collective primitives with the reference op vocabulary.

These functions run *inside* ``shard_map`` (or any SPMD context with a named
mesh axis) and lower to single XLA collectives over ICI — the TPU-native
replacement for the reference's CCLO offload engine: where ACCL's firmware
dispatches ring/tree programs onto the FPGA dataplane
(``ccl_offload_control.c``), here XLA's collective scheduler owns the wire
and we express only the semantics.

Reduction functions mirror ``reduceFunction`` (constants.hpp:218-221):
SUM and MAX, extended with MIN/PROD which fall out naturally on TPU.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..constants import ReduceFunction

_REDUCERS = {
    ReduceFunction.SUM: lax.psum,
    ReduceFunction.MAX: lax.pmax,
}


def axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def rank(axis_name: str):
    return lax.axis_index(axis_name)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def allreduce(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
) -> jax.Array:
    """ref ``ACCL::allreduce`` (accl.hpp) — every rank gets the reduction."""
    try:
        return _REDUCERS[function](x, axis_name)
    except KeyError:
        raise ValueError(f"unsupported reduce function {function}") from None


def reduce(
    x: jax.Array,
    axis_name: str,
    root: int = 0,
    function: ReduceFunction = ReduceFunction.SUM,
) -> jax.Array:
    """ref ``ACCL::reduce`` — full result on ``root``, zeros elsewhere.

    SPMD programs have no 'no result' rank, so non-roots get zeros (the
    analog of the reference's DummyBuffer operand on non-roots)."""
    full = allreduce(x, axis_name, function)
    return jnp.where(lax.axis_index(axis_name) == root, full, jnp.zeros_like(full))


def reduce_scatter(
    x: jax.Array,
    axis_name: str,
    function: ReduceFunction = ReduceFunction.SUM,
    tiled: bool = False,
    axis: int = 0,
) -> jax.Array:
    """ref ``ACCL::reduce_scatter`` — rank i gets block i of the reduction
    along ``axis``.

    SUM lowers to a single XLA reduce-scatter (``psum_scatter``); MAX is
    composed as pmax + local slice (XLA fuses the slice)."""
    if function == ReduceFunction.SUM:
        return lax.psum_scatter(
            x, axis_name, scatter_dimension=axis, tiled=tiled
        )
    full = allreduce(x, axis_name, function)
    size = lax.axis_size(axis_name)
    if not tiled:
        # match psum_scatter(tiled=False): the scatter dimension must
        # equal the axis size and is squeezed from the result
        if x.shape[axis] != size:
            raise ValueError(
                f"reduce_scatter: tiled=False requires axis {axis} length "
                f"{x.shape[axis]} == axis size {size}"
            )
        out = lax.dynamic_slice_in_dim(
            full, lax.axis_index(axis_name), 1, axis=axis
        )
        return lax.squeeze(out, (axis,))
    if x.shape[axis] % size != 0:
        raise ValueError(
            f"reduce_scatter: axis {axis} length {x.shape[axis]} is not "
            f"divisible by axis size {size} (non-SUM path has no padding; "
            "pad the operand or use a divisible count)"
        )
    block = x.shape[axis] // size
    start = lax.axis_index(axis_name) * block
    return lax.dynamic_slice_in_dim(full, start, block, axis=axis)


# ---------------------------------------------------------------------------
# data movement
# ---------------------------------------------------------------------------


def allgather(
    x: jax.Array, axis_name: str, tiled: bool = True, axis: int = 0
) -> jax.Array:
    """ref ``ACCL::allgather`` — concatenation of every rank's block
    along ``axis``."""
    return lax.all_gather(x, axis_name, tiled=tiled, axis=axis)


try:  # Varying -> Invariant allgather: a PRIVATE symbol (jax 0.9.0 does
    # not re-export it), so a later jax may move it — the tested fallback
    # below keeps the semantics if it does
    from jax._src.lax.parallel import all_gather_invariant as _ag_invariant
except ImportError:  # pragma: no cover - private symbol moved
    _ag_invariant = None


def allgather_invariant(
    x: jax.Array, axis_name: str, axis: int = 0, tiled: bool = True
) -> jax.Array:
    """Allgather whose output shard_map's replication checker accepts as
    axis-invariant — required whenever the gathered value flows to a
    replicated (``P(None)``) output.  Falls back to a psum of scattered
    slices (provably invariant, 2x the wire bytes) should jax's private
    ``all_gather_invariant`` move."""
    if _ag_invariant is not None:
        return _ag_invariant(x, axis_name, axis=axis, tiled=tiled)
    return _allgather_invariant_fallback(x, axis_name, axis=axis, tiled=tiled)


def _allgather_invariant_fallback(
    x: jax.Array, axis_name: str, axis: int = 0, tiled: bool = True
) -> jax.Array:
    """Psum-of-scattered-slices allgather: provably axis-invariant, at
    2x the wire bytes.  Kept directly testable (tests force
    ``_ag_invariant=None``) so a jax upgrade that drops the private op
    cannot silently change semantics."""
    size = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    block = x.shape[axis]
    full_shape = list(x.shape)
    full_shape[axis] = block * size
    contrib = lax.dynamic_update_slice_in_dim(
        jnp.zeros(tuple(full_shape), x.dtype), x, idx * block, axis=axis
    )
    out = lax.psum(contrib, axis_name)
    if tiled:
        return out
    return out.reshape(
        x.shape[:axis] + (size, block) + x.shape[axis + 1:]
    )


def bcast(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """ref ``ACCL::bcast`` — root's block everywhere.

    Expressed as a masked psum, which XLA lowers to a broadcast-shaped
    collective; avoids materializing an allgather of world size."""
    masked = jnp.where(lax.axis_index(axis_name) == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def scatter(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """ref ``ACCL::scatter`` — rank i gets block i of root's array.

    ``x`` is the full (size*count) array on root (don't-care elsewhere)."""
    size = lax.axis_size(axis_name)
    block = x.shape[0] // size
    full = bcast(x, axis_name, root)
    start = lax.axis_index(axis_name) * block
    return lax.dynamic_slice_in_dim(full, start, block, axis=0)


def gather(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """ref ``ACCL::gather`` — concatenation on root, zeros elsewhere."""
    full = lax.all_gather(x, axis_name, tiled=True)
    return jnp.where(
        lax.axis_index(axis_name) == root, full, jnp.zeros_like(full)
    )


def alltoall(x: jax.Array, axis_name: str) -> jax.Array:
    """ref ``ACCL::alltoall`` — block-transpose across the axis.

    ``x`` has leading dim size*count; rank r's output block p is rank p's
    input block r — one XLA all-to-all on ICI."""
    size = lax.axis_size(axis_name)
    blocks = x.reshape((size, -1) + x.shape[1:])
    out = lax.all_to_all(blocks, axis_name, split_axis=0, concat_axis=0)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# point-to-point (SPMD style)
# ---------------------------------------------------------------------------


def sendrecv(
    x: jax.Array, axis_name: str, distance: int = 1
) -> jax.Array:
    """Shift along the ring: every rank sends to rank+distance and receives
    from rank-distance — the SPMD form of matched ``send``/``recv`` pairs,
    one ``collective-permute`` on ICI (the reference's eager send/recv pair
    collapses into this under a synchronous schedule)."""
    size = lax.axis_size(axis_name)
    perm = [(i, (i + distance) % size) for i in range(size)]
    return lax.ppermute(x, axis_name, perm)


def send_to(
    x: jax.Array, axis_name: str, src: int, dst: int
) -> jax.Array:
    """Single directed transfer src -> dst (other ranks receive zeros)."""
    return lax.ppermute(x, axis_name, [(src, dst)])


def barrier(axis_name: str) -> jax.Array:
    """ref ``ACCL::barrier`` — a zero-payload allreduce; XLA's collective
    already synchronizes the axis, we return the token-like scalar."""
    return lax.psum(jnp.zeros((), jnp.int32), axis_name)


# ---------------------------------------------------------------------------
# wire compression (ref hp_compression plugin + ETH_COMPRESSED flag)
# ---------------------------------------------------------------------------


def compressed_allreduce(
    x: jax.Array,
    axis_name: str,
    wire_dtype: jnp.dtype = jnp.bfloat16,
    function: ReduceFunction = ReduceFunction.SUM,
) -> jax.Array:
    """Allreduce with operands cast to a narrow dtype before crossing the
    wire — the TPU-native form of the reference's fp32->fp16 'ethernet
    compression' (hp_compression kernels + ETH_COMPRESSED): reduce-scatter
    in wire dtype, accumulate locally in the original dtype, allgather the
    narrow result.  Counts that don't divide the axis size are padded
    (statically) around the scatter/gather pair.

    Sub-byte-precision lanes (fp8) and the scaled int8 lane round each
    CONTRIBUTION through the wire once and then reduce at the original
    dtype — accumulating AT 2-3 mantissa bits (or across differently
    scaled int8 blocks) is numerically meaningless, and single-rounding
    is exactly the command-ring decode loop's semantic, so warm (ring)
    and cold (this program) compressed calls agree."""
    orig = x.dtype
    n = x.shape[0]
    size = lax.axis_size(axis_name)
    pad = (-n) % size
    from ..constants import numpy_to_dtype
    from ..wire import dropped_mantissa_bits, is_scaled

    _dt = numpy_to_dtype(jnp.dtype(wire_dtype))
    if is_scaled(_dt) or (dropped_mantissa_bits(_dt) or 0) >= 20:
        from . import wire as devwire

        rounded = devwire.wire_lane_roundtrip(x, jnp.dtype(wire_dtype))
        if function == ReduceFunction.SUM:
            return lax.psum(rounded, axis_name)
        return _REDUCERS[function](rounded, axis_name)
    narrow = x.astype(wire_dtype)
    if pad:
        narrow = jnp.concatenate(
            [narrow, jnp.zeros((pad,) + x.shape[1:], wire_dtype)]
        )
    if function == ReduceFunction.SUM:
        partial = lax.psum_scatter(
            narrow, axis_name, scatter_dimension=0, tiled=True
        ).astype(orig)
    else:
        partial_full = _REDUCERS[function](narrow, axis_name).astype(orig)
        block = (n + pad) // size
        partial = lax.dynamic_slice_in_dim(
            partial_full, lax.axis_index(axis_name) * block, block, axis=0
        )
    gathered = lax.all_gather(partial.astype(wire_dtype), axis_name, tiled=True)
    return gathered[:n].astype(orig)
