"""Quantized wire protocols: the ONE host-side codec every tier speaks.

Role model: the reference's ``hp_compression`` plugin casts fp32<->fp16
on 512-bit stream lanes before/after the wire
(``kernels/plugins/hp_compression/hp_compression.cpp``).  This module
grows that single fixed lane into a measured protocol family:

* **cast lanes** (f16 / bf16 / fp8 e4m3 / fp8 e5m2) — elementwise dtype
  narrowing, with **stochastic rounding** for the fp8 lanes (at 2-3
  mantissa bits, deterministic round-to-nearest biases repeated
  compressed reductions; SR keeps them unbiased in expectation);
* **scaled lanes** (int8) — blockwise absmax quantization: one fp32
  scale per :data:`~accl_tpu.constants.WIRE_SEGMENT_ELEMS` elements
  rides the wire beside the int8 payload (``q = round(x/scale)``,
  ``scale = absmax/127``), stochastic by default.

Every consumer reads THIS codec — the emulator's eager chunk lanes, the
dist tier's staging path, the native engine's host-side mirror, the
facade's error-feedback residual accounting — and the device-side twin
(:mod:`accl_tpu.ops.wire`) implements bit-identical jnp forms for the
command ring's decode loop, so "same seed -> same wire bytes" holds across
tiers (tested bit-level by tests/test_wire.py).

Stochastic rounding is **counter-based and seedable**: random bits are
a Murmur3-finalizer hash of ``(element index, seed)`` — no RNG state,
so any tier (numpy or XLA, any thread schedule) derives the identical
bit stream from the call's seed.  Seeds are derived SPMD-uniformly per
call by the facade (:func:`call_seed`) and mixed per rank
(:func:`rank_seed`) so ranks draw independent streams while slot
encodings stay rank-identical.  Seed 0 means deterministic rounding
(round-to-nearest-even) — the f16/bf16 lanes' default, preserving the
reference hp_compression semantics.

Module scope stays numpy-free (lazy imports, the ``constants.py``
pattern): this module is in the acclint jax-free closure so socket-rank
processes and the analysis tooling can import it without the numeric
stack.
"""

from __future__ import annotations

import zlib
from typing import Optional, Tuple

from .constants import (
    DataType,
    SCALED_WIRE_DTYPES,
    STOCHASTIC_WIRE_DTYPES,
    WIRE_LANE_DTYPES,
    WIRE_SEGMENT_ELEMS,
    dtype_size,
    dtype_to_numpy,
)

__all__ = [
    "call_seed",
    "decode_bytes",
    "dropped_mantissa_bits",
    "encode_bytes",
    "is_scaled",
    "is_stochastic",
    "is_wire_dtype",
    "lane_tiny",
    "options_rank_seed",
    "rank_seed",
    "roundtrip",
    "seg_count",
    "sr_bits",
    "wire_lane_dtypes",
    "wire_nbytes",
]

#: f32 mantissa bits DROPPED per float wire lane (23 - target mantissa
#: bits): the stochastic-rounding mask width of the bit-trick SR — add
#: uniform random bits below the kept mantissa, truncate, then the
#: final cast is exact for normal values.  f16:10m, bf16:7m, e4m3:3m,
#: e5m2:2m.
_DROPPED_MANTISSA = {
    DataType.FLOAT16: 13,
    DataType.BFLOAT16: 16,
    DataType.FLOAT8_E4M3: 20,
    DataType.FLOAT8_E5M2: 21,
}

#: smallest NORMAL magnitude per float wire lane (2^(1-bias)): below
#: it the f32 mantissa-bit SR trick misaligns with the target's
#: subnormal spacing, so those elements take the deterministic cast.
#: A static table (not np.finfo) — numpy's finfo rejects ml_dtypes
#: scalars on some versions, and bit-identity with the jnp twin wants
#: one literal constant anyway.
_LANE_TINY = {
    DataType.FLOAT16: 2.0 ** -14,
    DataType.BFLOAT16: 2.0 ** -126,
    DataType.FLOAT8_E4M3: 2.0 ** -6,
    DataType.FLOAT8_E5M2: 2.0 ** -14,
}


def lane_tiny(dt) -> Optional[float]:
    """Smallest normal magnitude of a float cast lane (None for scaled
    lanes) — the SR-applicability floor both codecs share."""
    return _LANE_TINY.get(DataType(dt))

_WIRE_SET = frozenset(DataType[n] for n in WIRE_LANE_DTYPES)
_SCALED_SET = frozenset(DataType[n] for n in SCALED_WIRE_DTYPES)
_STOCHASTIC_SET = frozenset(DataType[n] for n in STOCHASTIC_WIRE_DTYPES)


def wire_lane_dtypes() -> Tuple[DataType, ...]:
    """The registered wire lanes, as DataType members (sorted by value)."""
    return tuple(sorted(_WIRE_SET))


def is_wire_dtype(dt) -> bool:
    try:
        return DataType(dt) in _WIRE_SET
    except ValueError:
        return False


def is_scaled(dt) -> bool:
    """True for lanes carrying a per-segment absmax scale sidecar."""
    return DataType(dt) in _SCALED_SET


def is_stochastic(dt) -> bool:
    """True for lanes that round stochastically by default (the facade
    derives a nonzero call seed for them)."""
    return DataType(dt) in _STOCHASTIC_SET


def dropped_mantissa_bits(dt) -> Optional[int]:
    """SR mask width for a float cast lane; None for scaled lanes."""
    return _DROPPED_MANTISSA.get(DataType(dt))


def seg_count(n: int) -> int:
    """Scale blocks covering ``n`` elements (scaled lanes)."""
    return max(1, -(-int(n) // WIRE_SEGMENT_ELEMS))


def wire_nbytes(n: int, dt) -> int:
    """Bytes ON THE WIRE for ``n`` elements in lane ``dt``: the narrow
    payload plus, for scaled lanes, the fp32 scale sidecar.  The ONE
    sizing rule — the emulator's eager receive posts, the telemetry
    bytes-saved counters and the bench's effective-bandwidth sweep all
    read it (divergent copies would let the evidence lie about the
    protocol)."""
    dt = DataType(dt)
    nb = int(n) * dtype_size(dt)
    if dt in _SCALED_SET:
        nb += seg_count(n) * 4  # fp32 scale per segment
    return nb


# ---------------------------------------------------------------------------
# seeds: counter-based, SPMD-uniform, rank-mixed
# ---------------------------------------------------------------------------


def call_seed(comm_id: int, epoch: int, counter: int, wire: int) -> int:
    """Per-call SR seed, derived from SPMD-uniform facts only (the
    contract-fingerprint discipline: crc32, never process-salted
    ``hash``) so every rank of the collective derives the SAME seed
    with zero wire bytes.  Nonzero by construction — 0 means
    'deterministic rounding'."""
    data = f"wire|{comm_id}|{epoch}|{counter}|{int(wire)}".encode()
    return (zlib.crc32(data) & 0x7FFFFFFF) or 1


def options_rank_seed(options) -> int:
    """THE per-rank seed derivation for one engine call: the call's
    SPMD-uniform ``wire_seed`` mixed with its comm-local rank (0 =
    deterministic — unseeded calls and comm-less ops).  One definition
    for every tier's encode path (emulator chunk lanes, dist host
    staging, native mirror, gang host-staged casts) — divergent copies
    would let tiers draw different SR streams for the same call."""
    seed = getattr(options, "wire_seed", 0)
    comm = getattr(options, "comm", None)
    if not seed or comm is None:
        return 0
    return rank_seed(seed, comm.local_rank)


def rank_seed(seed: int, rank: int) -> int:
    """Mix a rank into a call seed so ranks draw independent SR streams
    while the slot encoding (which carries only ``seed``) stays
    rank-identical.  Pure 32-bit arithmetic — the jnp twin computes the
    same value on device."""
    if not seed:
        return 0
    h = (int(seed) ^ ((int(rank) * 0x9E3779B9) & 0xFFFFFFFF)) & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h or 1


#: cached ``arange(n) * Knuth`` bases for sr_bits — the index ramp is
#: seed-independent and hot (every SR encode of a warm bucket reuses
#: it); bounded, cleared wholesale on overflow
_SR_BASE: dict = {}


def sr_bits(n: int, seed: int):
    """``n`` uniform uint32 draws: the Murmur3 finalizer over
    ``(element index * Knuth) ^ seed`` — stateless, so any tier
    recomputes the identical stream.  numpy form (in-place passes over
    one scratch buffer — this sits on the per-hop encode path); the
    jnp twin in :mod:`accl_tpu.ops.wire` is bit-identical (uint32
    wraparound is well-defined in both)."""
    import numpy as np

    base = _SR_BASE.get(n)
    if base is None:
        if len(_SR_BASE) > 64:
            _SR_BASE.clear()
        base = _SR_BASE[n] = (
            np.arange(n, dtype=np.uint32) * np.uint32(2654435761)
        )
    h = base ^ np.uint32(seed & 0xFFFFFFFF)
    tmp = np.empty_like(h)
    np.right_shift(h, 16, out=tmp)
    h ^= tmp
    h *= np.uint32(0x85EBCA6B)
    np.right_shift(h, 13, out=tmp)
    h ^= tmp
    h *= np.uint32(0xC2B2AE35)
    np.right_shift(h, 16, out=tmp)
    h ^= tmp
    return h


# ---------------------------------------------------------------------------
# the lanes (numpy)
# ---------------------------------------------------------------------------


def _cast_lane_encode(x, dt: DataType, seed: int):
    """f32 -> narrow float wire values.  ``seed`` nonzero rounds
    stochastically: add uniform random bits to the dropped f32 mantissa
    bits, truncate, cast (exact for normals; non-finite values and
    exponent under/overflow fall back to the deterministic cast, whose
    saturation semantics the target dtype owns)."""
    import numpy as np

    npdt = dtype_to_numpy(dt)
    x32 = np.ascontiguousarray(np.asarray(x, np.float32))
    if not seed:
        return x32.astype(npdt)
    drop = _DROPPED_MANTISSA[dt]
    mask = np.uint32((1 << drop) - 1)
    # in-place passes over the sr_bits scratch (per-hop encode path):
    # bits = (bits & mask) + x_bits, truncated below the kept mantissa
    bits = sr_bits(x32.size, seed).reshape(x32.shape)
    bits &= mask
    bits += x32.view(np.uint32)
    bits &= ~mask
    rounded = bits.view(np.float32)
    # SR is exact only where the truncated value is a NORMAL of the
    # target (the f32 mantissa-bit trick misaligns on target
    # subnormals) — elsewhere keep the deterministic cast.
    use_sr = np.isfinite(x32)
    use_sr &= np.abs(x32) >= np.float32(_LANE_TINY[dt])
    return np.where(use_sr, rounded, x32).astype(npdt)


def _scaled_lane_encode(x, seed: int):
    """f32 -> (int8 values, per-segment fp32 scales): blockwise absmax
    quantization.  ``seed`` nonzero: ``q = floor(x/scale + u)`` with
    ``u`` uniform in [0,1) (unbiased); 0: ``q = rint(x/scale)``
    (round-half-even).  Division / floor / rint are IEEE-exact, so the
    jnp twin bit-matches."""
    import numpy as np

    x32 = np.asarray(x, np.float32).reshape(-1)
    n = x32.size
    nseg = seg_count(n)
    pad = nseg * WIRE_SEGMENT_ELEMS - n
    xp = np.concatenate([x32, np.zeros(pad, np.float32)]) if pad else x32
    m = xp.reshape(nseg, WIRE_SEGMENT_ELEMS)
    scales = np.maximum(
        np.max(np.abs(m), axis=1) / np.float32(127.0), np.float32(1e-30)
    ).astype(np.float32)
    q_real = m / scales[:, None]
    if seed:
        # SR in-place on the q_real scratch (the per-hop encode path):
        # q = floor(x/scale + u), u uniform in [0,1)
        u = sr_bits(m.size, seed).reshape(m.shape).astype(np.float32)
        u *= np.float32(1.0 / 4294967296.0)
        q_real += u
        q = np.floor(q_real, out=q_real)
    else:
        q = np.rint(q_real, out=q_real)
    q = np.clip(q, -127, 127, out=q).astype(np.int8).reshape(-1)[:n]
    return q, scales


def _scaled_lane_decode(q, scales, out_npdt):
    import numpy as np

    n = q.shape[0]
    nseg = scales.shape[0]
    pad = nseg * WIRE_SEGMENT_ELEMS - n
    qf = q.astype(np.float32)
    if pad:
        qf = np.concatenate([qf, np.zeros(pad, np.float32)])
    out = (
        qf.reshape(nseg, WIRE_SEGMENT_ELEMS) * scales[:, None]
    ).reshape(-1)[:n]
    return out.astype(out_npdt)


# ---------------------------------------------------------------------------
# wire frames (the emulator/dist/native byte protocol)
# ---------------------------------------------------------------------------


def encode_bytes(data, dt, seed: int = 0) -> bytes:
    """One logical chunk as wire bytes: the narrow payload, then (for
    scaled lanes) the fp32 scale sidecar.  ``data`` is a numpy array in
    the uncompressed dtype; the frame is self-describing given ``(n,
    dt)`` — exactly what the receive side knows from its own call."""
    import numpy as np

    dt = DataType(dt)
    if dt in _SCALED_SET:
        q, scales = _scaled_lane_encode(data, seed)
        return q.tobytes() + scales.tobytes()
    if dt in _DROPPED_MANTISSA:
        return _cast_lane_encode(data, dt, seed).tobytes()
    # identity / widening lanes (the uncompressed wire): plain cast
    return np.asarray(data).astype(dtype_to_numpy(dt)).tobytes()


def decode_bytes(raw: bytes, dt, n: int, out_npdt):
    """Inverse of :func:`encode_bytes` for ``n`` elements (seed-free:
    SR is an encode-side property)."""
    import numpy as np

    dt = DataType(dt)
    if dt in _SCALED_SET:
        vals = np.frombuffer(raw[: n], np.int8)[:n]
        scales = np.frombuffer(
            raw[n: n + seg_count(n) * 4], np.float32
        ).copy()
        return _scaled_lane_decode(vals, scales, out_npdt)
    arr = np.frombuffer(raw, dtype=dtype_to_numpy(dt))[: int(n)]
    return arr.astype(out_npdt)


def roundtrip(data, dt, seed: int = 0):
    """``decode(encode(x))`` without the byte shuffle: the single-
    rounding wire semantic the error-feedback plane accounts against
    (``residual = x - roundtrip(x + residual)``) and the gang tiers
    execute in-program."""
    import numpy as np

    dt = DataType(dt)
    x = np.asarray(data)
    out_npdt = x.dtype if x.dtype.kind == "f" else np.float32
    if dt in _SCALED_SET:
        q, scales = _scaled_lane_encode(x, seed)
        return _scaled_lane_decode(q, scales, out_npdt).reshape(x.shape)
    if dt in _DROPPED_MANTISSA:
        return (
            _cast_lane_encode(x, dt, seed).astype(out_npdt).reshape(x.shape)
        )
    return x.astype(dtype_to_numpy(dt)).astype(out_npdt)
