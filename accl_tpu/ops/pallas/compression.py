"""Wire-compression kernels — the ``hp_compression`` plugin as TPU kernels.

The reference casts fp32<->fp16 on 512-bit stream lanes before/after the
wire (/root/reference/kernels/plugins/hp_compression/hp_compression.cpp:
30-80; three instances cover two operand lanes and the result lane).  The
TPU-native equivalents:

* ``cast`` — dtype conversion as a tiled VPU pass, with optional
  **stochastic rounding** (pltpu.stochastic_round + on-chip PRNG) so
  repeated compressed reductions stay unbiased — a capability the FPGA
  plugin lacks.
* ``quantize_int8`` / ``dequantize_int8`` — blockwise int8 wire format
  with per-tile scales, extending the compression surface beyond the
  reference's half-precision-only lane.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (
    LANES,
    InterpretArg,
    block_rows,
    default_interpret,
    mosaic_rejects,
    pack_lanes,
    unpack_lanes,
)


def _cast_kernel(out_dtype):
    def kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:].astype(out_dtype)

    return kernel


def _stochastic_cast_kernel(out_dtype):
    # f32 -> bf16 stochastic rounding by hand (portable to the interpreter):
    # add uniform random bits to the 16 mantissa bits that truncation drops,
    # then keep the top half-word.  Non-finite values fall back to the
    # deterministic cast.
    def kernel(seed_ref, x_ref, o_ref):
        # mix the grid position into the seed so every block draws
        # independent bits (one seed stream per tile, not one reused one)
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        x = x_ref[:]
        rand = pltpu.bitcast(pltpu.prng_random_bits(x.shape), jnp.uint32)
        u = pltpu.bitcast(x, jnp.uint32)
        rounded = u + (rand & jnp.uint32(0xFFFF))
        bf = pltpu.bitcast(
            (rounded >> 16).astype(jnp.uint16), jnp.bfloat16
        )
        o_ref[:] = jnp.where(jnp.isfinite(x), bf, x.astype(out_dtype))

    return kernel


def cast(
    x: jax.Array,
    dtype,
    *,
    stochastic: bool = False,
    seed: int = 0,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Convert ``x`` to ``dtype`` in a tiled kernel pass.

    ``stochastic=True`` (fp32 -> bfloat16 only) rounds stochastically using
    the per-core PRNG, keeping compressed-reduction pipelines unbiased.
    (Note: the Pallas TPU *interpreter* stubs ``prng_random_bits`` to
    zeros, so off-TPU the stochastic path degenerates to truncation —
    randomness is a hardware-tier property.)

    float16 endpoints never reach Mosaic: the TPU mosaic dialect has no
    ``f16`` (measured on v5e: the compile rejects the kernel), so
    compiled-mode f16 casts ride XLA's convert instead — numerically identical
    (both round to nearest even), and fp16 is a wire/storage format here,
    not a compute one.  The interpreter tier still runs the kernel.
    """
    dtype = jnp.dtype(dtype)
    interp = default_interpret(interpret)
    if not stochastic and mosaic_rejects(interp, x.dtype, dtype):
        return x.astype(dtype)
    xp, n = pack_lanes(x)
    rows = xp.shape[0]
    br = block_rows(rows)
    grid = (rows // br,)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((rows, LANES), dtype)

    if stochastic:
        if x.dtype != jnp.float32 or dtype != jnp.bfloat16:
            raise ValueError(
                "stochastic rounding supports float32 -> bfloat16"
            )
        # index maps under scalar prefetch also receive the scalar ref
        pspec = pl.BlockSpec(
            (br, LANES), lambda i, seed_ref: (i, 0), memory_space=pltpu.VMEM
        )
        out = pl.pallas_call(
            _stochastic_cast_kernel(dtype),
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[pspec],
                out_specs=pspec,
            ),
            interpret=interp,
        )(jnp.asarray([seed], jnp.int32), xp)
    else:
        out = pl.pallas_call(
            _cast_kernel(dtype),
            out_shape=out_shape,
            grid=grid,
            in_specs=[spec],
            out_specs=spec,
            interpret=interp,
        )(xp)
    return unpack_lanes(out, n, x.shape)


def _quantize_kernel(scales_ref, x_ref, values_ref):
    # per-tile scale arrives via scalar prefetch (SMEM): the kernel never
    # writes scales, an XLA pre-pass computes them, so the tile pass
    # stays one read and one write.  (A whole-array SMEM output written
    # one slot per grid step does lower and run under fori_loop on the
    # attached v5e — chip run, PR 21 — so this is a choice, not a limit.)
    scale = scales_ref[pl.program_id(0)]
    values_ref[:] = jnp.clip(
        jnp.round(x_ref[:] / scale), -127, 127
    ).astype(jnp.int8)


def _dequantize_kernel(scales_ref, values_ref, o_ref):
    o_ref[:] = (
        values_ref[:].astype(jnp.float32) * scales_ref[pl.program_id(0)]
    )


def _tile_specs(br: int):
    # index maps under scalar prefetch also receive the scalar ref
    return pl.BlockSpec(
        (br, LANES), lambda i, s_ref: (i, 0), memory_space=pltpu.VMEM
    )


def quantize_int8(
    x: jax.Array, *, interpret: InterpretArg = None
):
    """Blockwise int8 quantization: returns ``(values, scales, n)`` where
    each grid tile carries one fp32 scale (absmax / 127).

    The scales are an XLA reduction pass over the tiles; the Pallas kernel
    consumes them as scalar-prefetch operands and emits only the lane-
    aligned int8 payload."""
    xp, n = pack_lanes(x.astype(jnp.float32))
    rows = xp.shape[0]
    br = block_rows(rows)
    nblk = rows // br
    scales = jnp.maximum(
        jnp.max(jnp.abs(xp.reshape(nblk, br * LANES)), axis=1) / 127.0,
        1e-30,
    )
    values = pl.pallas_call(
        _quantize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblk,),
            in_specs=[_tile_specs(br)],
            out_specs=_tile_specs(br),
        ),
        interpret=default_interpret(interpret),
    )(scales, xp)
    return values, scales.reshape(nblk, 1), n


def dequantize_int8(
    values: jax.Array,
    scales: jax.Array,
    n: int,
    shape,
    dtype=jnp.float32,
    *,
    interpret: InterpretArg = None,
) -> jax.Array:
    """Inverse of :func:`quantize_int8`.  ``dtype`` restores the original
    operand dtype (quantization always computes in float32)."""
    rows = values.shape[0]
    nblk = scales.shape[0]
    br = rows // nblk
    out = pl.pallas_call(
        _dequantize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblk,),
            in_specs=[_tile_specs(br)],
            out_specs=_tile_specs(br),
        ),
        interpret=default_interpret(interpret),
    )(scales.reshape(-1), values)
    return unpack_lanes(out, n, shape, dtype=dtype)
