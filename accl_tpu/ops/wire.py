"""Device-side twin of the host wire codec (:mod:`accl_tpu.wire`).

Bit-identical jnp forms of the quantized wire lanes — the command
ring's decode loop, the compressed-allreduce program and the
dist tier's in-program wire rounding all call THESE, and
tests/test_wire.py holds them to byte equality against the numpy codec
(same input, same seed -> same wire bytes).  Bit identity is why every
operation here is integer arithmetic or IEEE-exact float arithmetic
(division, floor, rint, absmax): nothing depends on accumulation order
or platform-specific rounding.

Seeds are int32 SCALARS (traced values, typically read from a command-
ring slot's ``flags`` word) — programs never recompile on seed churn.
Rank mixing (:func:`rank_seed`) runs on device from ``axis_index`` so
one rank-identical slot encoding still gives every rank an independent
SR stream.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..constants import (
    WIRE_SEGMENT_ELEMS,
    DataType,
)
from ..wire import dropped_mantissa_bits, is_scaled, lane_tiny, seg_count

__all__ = [
    "dequantize_int8",
    "quantize_int8",
    "rank_seed",
    "sr_bits",
    "wire_lane_roundtrip",
]


def rank_seed(seed, rank):
    """jnp twin of :func:`accl_tpu.wire.rank_seed` (scalar uint32
    arithmetic; seed 0 stays 0 = deterministic)."""
    seed = jnp.asarray(seed).astype(jnp.uint32)
    h = seed ^ (jnp.asarray(rank).astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    h = jnp.where(h == 0, jnp.uint32(1), h)
    return jnp.where(seed == 0, jnp.uint32(0), h)


def sr_bits(n: int, seed) -> jax.Array:
    """jnp twin of :func:`accl_tpu.wire.sr_bits`: ``n`` uniform uint32
    draws from the Murmur3 finalizer of ``(index, seed)``."""
    h = (
        jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
    ) ^ jnp.asarray(seed).astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _cast_lane(x, wire_dtype, seed):
    """f32 -> narrow float wire values, SR when ``seed`` is nonzero
    (the numpy codec's bit-trick, traced: mask-add-truncate on the
    dropped mantissa bits, deterministic fallback for non-finite /
    subnormal-of-target values).  ``seed == 0`` is a plain RTNE cast —
    the branch is traced on VALUES via where, so one program serves
    both (seed is data)."""
    wire_dtype = jnp.dtype(wire_dtype)
    from ..constants import numpy_to_dtype

    dt = numpy_to_dtype(wire_dtype)
    drop = dropped_mantissa_bits(dt)
    x32 = x.astype(jnp.float32)
    mask = jnp.uint32((1 << drop) - 1)
    bits = sr_bits(x32.size, seed).reshape(x32.shape) & mask
    u = lax.bitcast_convert_type(x32, jnp.uint32)
    rounded = lax.bitcast_convert_type((u + bits) & ~mask, jnp.float32)
    use_sr = (
        jnp.isfinite(x32)
        & (jnp.abs(x32) >= jnp.float32(lane_tiny(dt)))
        & (jnp.asarray(seed).astype(jnp.uint32) != 0)
    )
    return jnp.where(use_sr, rounded, x32).astype(wire_dtype)


def quantize_int8(x, seed) -> Tuple[jax.Array, jax.Array]:
    """jnp twin of the scaled int8 lane encode: ``(q int8, scales
    f32)`` with one absmax/127 scale per WIRE_SEGMENT_ELEMS block.
    ``seed`` nonzero: ``floor(x/scale + u)``; zero: ``rint`` — traced
    as data through where, like the cast lane."""
    x32 = x.astype(jnp.float32).reshape(-1)
    n = x32.shape[0]
    nseg = seg_count(n)
    pad = nseg * WIRE_SEGMENT_ELEMS - n
    if pad:
        x32 = jnp.concatenate([x32, jnp.zeros((pad,), jnp.float32)])
    m = x32.reshape(nseg, WIRE_SEGMENT_ELEMS)
    scales = jnp.maximum(
        jnp.max(jnp.abs(m), axis=1) / jnp.float32(127.0),
        jnp.float32(1e-30),
    )
    q_real = m / scales[:, None]
    u = (
        sr_bits(m.size, seed).reshape(m.shape).astype(jnp.float32)
        * jnp.float32(1.0 / 4294967296.0)
    )
    q_sr = jnp.floor(q_real + u)
    q_det = jnp.round(q_real)  # half-to-even, = np.rint
    stochastic = jnp.asarray(seed).astype(jnp.uint32) != 0
    q = jnp.where(stochastic, q_sr, q_det)
    q = jnp.clip(q, -127, 127).astype(jnp.int8).reshape(-1)[:n]
    return q, scales


def dequantize_int8(q, scales, n: int, out_dtype=jnp.float32) -> jax.Array:
    """jnp twin of the scaled int8 lane decode."""
    nseg = scales.shape[0]
    pad = nseg * WIRE_SEGMENT_ELEMS - n
    qf = q.astype(jnp.float32)
    if pad:
        qf = jnp.concatenate([qf, jnp.zeros((pad,), jnp.float32)])
    out = (qf.reshape(nseg, WIRE_SEGMENT_ELEMS) * scales[:, None]).reshape(
        -1
    )[:n]
    return out.astype(out_dtype)


def wire_lane_roundtrip(x, wire_dtype, seed=0):
    """One in-program wire rounding lane: narrow to ``wire_dtype`` (SR
    when ``seed`` is a nonzero traced scalar), widen back to ``x``'s
    dtype — the single-rounding semantic the decode loops and the
    compressed-allreduce program run per contribution, covering EVERY
    registered lane (cast lanes by dtype, the scaled int8 lane by
    blockwise quantization).  THE shared lane helper: the command
    ring's decode loop must route its wire casts through this module
    (the acclint ``cmdring-slot-layout`` wire cross-check enforces
    it)."""
    wire_np = jnp.dtype(wire_dtype)
    orig = x.dtype
    from ..constants import numpy_to_dtype

    dt = numpy_to_dtype(wire_np)
    if is_scaled(dt):
        shape = x.shape
        q, scales = quantize_int8(x, seed)
        return dequantize_int8(
            q, scales, int(x.size), out_dtype=orig
        ).reshape(shape)
    # The barrier keeps the narrow value a value: XLA on a TPU removes a
    # narrow -> wide convert pair it can see through (its "excess
    # precision" licence), and then the lane rounds nothing — measured on
    # the v5e (chip run, PR 21): max |roundtrip(x) - x| was 0.0 for the
    # fp8, bf16 and f16 lanes inside one program, 0.249 / 0.0153 / 0.0019
    # with encode and decode in programs apart (and on the CPU).
    if dropped_mantissa_bits(dt) is not None:
        narrow = _cast_lane(x, wire_np, seed)
    else:
        narrow = x.astype(wire_np)
    return lax.optimization_barrier(narrow).astype(orig)


#: lane-kind table for the registered wire dtypes (numpy-name keyed):
#: "cast" lanes narrow by dtype, "scaled" lanes quantize blockwise.
#: Parsed by the acclint wire cross-check against
#: constants.WIRE_LANE_DTYPES — a registered lane missing here is a
#: finding before it is a workload fallback.
WIRE_LANES = {
    "float16": "cast",
    "bfloat16": "cast",
    "float8_e4m3fn": "cast",
    "float8_e5m2": "cast",
    "int8": "scaled",
}
