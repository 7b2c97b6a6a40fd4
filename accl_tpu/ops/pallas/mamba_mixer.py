"""The Mamba-2 mixer's two float32 chains round the SSD core (``ops/ssd.py``
``conv_silu``, ``gated_group_norm``) as four Mosaic kernels, each ONE pass
over HBM: a chain's intermediates stay in VMEM, every operand and result is
TOKEN-MAJOR ``(B, T, columns)`` as the projections leave it and the core
(``ops/pallas/ssd.py``) takes and gives it, and a ``custom_vjp`` saves
nothing but the chain's own inputs (the projections the matmuls made, the
core's ``y``).

``ops.ssd`` picks them from the shapes (:func:`takes`: columns in whole
column blocks, a group of whole lanes that a grid step holds, taps that a
halo block holds); the XLA forms there stay the oracle they are tested
against and what every other shape runs.

THE CHAINS, forward | backward (everything float32 but the projections, the
norm's output and their cotangents, which keep the matmuls' type):

* ``mamba_in_fwd`` | ``mamba_in_bwd`` (x, B and C): projection -> causal
  depthwise convolution (zero left padding, the last tap the current
  token's) -> a bias a channel -> SiLU | the projection's cotangent, the
  taps' and the bias's gradients, the pre-SiLU values rebuilt from the
  projection.
* ``mamba_out_fwd`` | ``mamba_out_bwd``: the core's ``y`` and the gate's
  projection ``z`` -> ``y SiLU(z)`` -> RMS norm over each group's columns
  -> times a scale a channel, in the matmuls' type | ``y``'s and ``z``'s
  cotangents, the scale's gradient.  No ``(B, T, G, C / G)`` view is taken
  or made: a group is a run of a row's columns.

THE GRID is ``kda_mixer``'s, ``(column blocks, B, row tiles)``, with its
tiles, halo blocks and resident column-gradient block (that module's
docstring; its geometry is imported as it stands).  A step of the
convolution holds ``TILE`` rows of ``WIDTH`` columns and walks them a LANE
TILE and ``ROWS`` rows at a time; a step of the norm holds the whole groups
that ``WIDTH`` columns hold (the cell's: one group of 1,024) and walks a
group ``ROWS`` rows at a time in two sweeps over its lane tiles: the first
makes ``y SiLU(z)`` (kept in a VMEM scratch) and its squares' sum a lane,
ONE sum over lanes a row gives the norm, the second scales and stores.  The
backward's first sweep keeps the sigmoid too and sums ``<d normed, gated>``
beside the squares.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES, InterpretArg, default_interpret, out_struct, vary_together
from .grouped_matmul import _run, _settled
from .kda_mixer import (
    EDGE, HALO, WIDTH, _Specs, _add_rows, _chunks, _fold, _head_cols, _pad_rows,
    _row_sum, _shifted, _zero_on_first_step,
)

IN_FWD, IN_BWD = "mamba_in_fwd", "mamba_in_bwd"
OUT_FWD, OUT_BWD = "mamba_out_fwd", "mamba_out_bwd"
#: the widest group the norm's kernels take (a grid step holds a tile of
#: whole groups: its blocks and scratch are 16 KB a column backward)
GROUP = 2 * WIDTH

_f32 = jnp.float32


def takes(wide: int, groups: int = 0, taps: int = 1) -> bool:
    """The shape rule: the kernels take ``wide`` columns in whole column
    blocks (:data:`WIDTH`, what a grid step holds), normed (``groups`` not
    0) in runs of whole lanes no wider than :data:`GROUP`, and convolutions
    whose reach a halo block holds (their gradients and the bias's are rows
    of ONE block of :data:`EDGE`); any row count (padded to whole tiles).
    Any other shape is the XLA form's."""
    if wide % WIDTH or not 1 <= taps < EDGE:
        return False
    return not groups or (
        wide % groups == 0 and (wide // groups) % LANES == 0
        and wide // groups <= GROUP
    )


class _Flat(_Specs):
    """``kda_mixer``'s specs for a token-major chain: ``flat_after`` the
    :data:`EDGE` rows of a float32 ``(B, Tp, columns)`` array after a tile
    (clamped at the end: the kernel zeroes what lies past it)."""

    def __init__(self, B, T, H, d):
        super().__init__(B, T, H, d)
        tt, Tp = self.tt, self.Tp
        self.flat_after = pl.BlockSpec(
            (1, EDGE, self.cw),
            lambda c, b, t: (b, jnp.minimum((t + 1) * (tt // EDGE), Tp // EDGE - 1), c),
        )


def _lane_tiles(cols):
    """The lane tiles of a run ``cols`` of a block's columns."""
    return [slice(c, c + LANES) for c in range(cols.start, cols.stop, LANES)]


# -- x, B, C in ----------------------------------------------------------------------


def _conv(shifted, w, bias):
    """``(pre-SiLU, its sigmoid)`` of the row windows ``shifted``."""
    u = sum(x * tap for x, tap in zip(shifted, w)) + bias
    return u, jax.nn.sigmoid(u)


def _in_fwd_kernel(n, sp):
    def kernel(x_ref, before_ref, cols_ref, out_ref, xs_ref):
        first = pl.program_id(2) == 0
        for _, cols in _head_cols(sp):
            w = [cols_ref[i:i + 1, cols] for i in range(n)]
            bias = cols_ref[n:n + 1, cols]
            xs_ref[:HALO] = jnp.where(first, 0.0, before_ref[0, :, cols].astype(_f32))
            xs_ref[HALO:] = x_ref[0, :, cols].astype(_f32)

            def chunk(r0, _):
                u, sig = _conv(_shifted(xs_ref, HALO - (n - 1) + r0, sp.rows, n), w, bias)
                out_ref[0, pl.ds(r0, sp.rows), cols] = u * sig

            _chunks(sp, chunk)

    return kernel


def _in_bwd_kernel(n, sp):
    tt, p = sp.tt, n - 1

    def kernel(x_ref, before_ref, after_ref, cols_ref, do_ref, do_after_ref,
               dx_ref, dcols_ref, xs_ref, du_ref):
        first = pl.program_id(2) == 0
        last = pl.program_id(2) == pl.num_programs(2) - 1
        _zero_on_first_step(dcols_ref)
        for _, cols in _head_cols(sp):
            w = [cols_ref[i:i + 1, cols] for i in range(n)]
            bias = cols_ref[n:n + 1, cols]
            xs_ref[:HALO] = jnp.where(first, 0.0, before_ref[0, :, cols].astype(_f32))
            xs_ref[HALO:HALO + tt] = x_ref[0, :, cols].astype(_f32)
            xs_ref[HALO + tt:] = after_ref[0, :, cols].astype(_f32)

            def du_of(r0, rows, do):
                """The cotangent of the convolution's output (bias in) at
                ``rows`` rows from ``r0``, and the windows its taps
                multiplied."""
                shifted = _shifted(xs_ref, HALO - p + r0, rows, n)
                u, sig = _conv(shifted, w, bias)
                return do * (sig * (1.0 + u * (1.0 - sig))), shifted

            def chunk(r0, acc):
                du, shifted = du_of(r0, sp.rows, do_ref[0, pl.ds(r0, sp.rows), cols])
                du_ref[pl.ds(r0, sp.rows), :] = du
                return tuple(a + _fold(du * x) for a, x in zip(acc, shifted)) + (
                    acc[n] + _fold(du),
                )

            zero = jnp.zeros((EDGE, LANES), _f32)
            for i, a in enumerate(_chunks(sp, chunk, (zero,) * (n + 1))):
                _add_rows(dcols_ref, i, cols, a)
            # the next tile's first rows: their taps reach back into this one
            du_ref[tt:] = du_of(tt, EDGE, jnp.where(last, 0.0, do_after_ref[0, :, cols]))[0]

            def place(r0, _):
                dx = sum(
                    du_ref[pl.ds(r0 + p - i, sp.rows), :] * tap for i, tap in enumerate(w)
                )
                dx_ref[0, pl.ds(r0, sp.rows), cols] = dx.astype(dx_ref.dtype)

            _chunks(sp, place)

    return kernel


@partial(jax.jit, static_argnames=("interpret",))
def _in_forward(x, cols, *, interpret):
    B, T, wide = x.shape
    sp = _Flat(B, T, wide // LANES, LANES)
    n = cols.shape[0] - 1
    x = _pad_rows(x, sp)
    call = pl.pallas_call(
        _in_fwd_kernel(n, sp),
        grid=sp.grid,
        out_shape=out_struct(x.shape, _f32, x, cols),
        in_specs=[sp.flat, sp.before, sp.cols(n + 1)],
        out_specs=sp.flat,
        scratch_shapes=[pltpu.VMEM((HALO + sp.tt, LANES), _f32)],
        compiler_params=sp.params(False, (x.dtype.itemsize, 4), HALO + sp.tt),
        interpret=interpret,
        name=IN_FWD,
    )
    return _run(call, interpret, x, x, cols)[:, :T]


@partial(jax.jit, static_argnames=("interpret",))
def _in_backward(x, cols, do, *, interpret):
    B, T, wide = x.shape
    sp = _Flat(B, T, wide // LANES, LANES)
    n = cols.shape[0] - 1
    x, do = _pad_rows(x, sp), _pad_rows(do, sp)
    call = pl.pallas_call(
        _in_bwd_kernel(n, sp),
        grid=sp.grid,
        out_shape=[
            out_struct(x.shape, x.dtype, x, cols, do),
            out_struct((EDGE, wide), _f32, x, cols, do),
        ],
        in_specs=[sp.flat, sp.before, sp.after, sp.cols(n + 1), sp.flat, sp.flat_after],
        out_specs=[sp.flat, sp.cols(EDGE)],
        scratch_shapes=[
            pltpu.VMEM((2 * HALO + sp.tt, LANES), _f32),
            pltpu.VMEM((sp.tt + EDGE, LANES), _f32),
        ],
        compiler_params=sp.params(
            True, (2 * x.dtype.itemsize, 4), 2 * (HALO + sp.tt)
        ),
        interpret=interpret,
        name=IN_BWD,
    )
    dx, dcols = _run(call, interpret, x, x, x, cols, do, do)
    return dx[:, :T], dcols[:n + 1]


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _in(x, cols, interpret):
    return _settled(_in_forward(x, cols, interpret=interpret), interpret)


def _in_fwd(x, cols, interpret):
    return _in(x, cols, interpret), (x, cols)


def _in_bwd(interpret, res, do):
    return _settled(_in_backward(*res, do, interpret=interpret), interpret)


_in.defvjp(_in_fwd, _in_bwd)


def conv_silu(x, taps, bias, *, interpret: InterpretArg = None):
    """``ops.ssd.conv_silu`` by the kernels: ``x`` (B, T, C) a projection,
    ``taps`` (n, C), ``bias`` (C,); float32 (B, T, C).  Differentiable by
    all three (the taps and the bias reach the kernels as the ``n + 1``
    float32 rows of one array)."""
    cols = jnp.concatenate([taps.astype(_f32), bias.astype(_f32)[None]])
    _, (x, cols) = vary_together(x, cols)
    return _in(x, cols, default_interpret(interpret))


# -- out -----------------------------------------------------------------------------


def _gated(y_ref, z_ref, rows, cols):
    """``(y, z, sigmoid(z), y SiLU(z))`` of a lane tile's rows."""
    y, z = y_ref[0, rows, cols], z_ref[0, rows, cols].astype(_f32)
    sig = jax.nn.sigmoid(z)
    return y, z, sig, y * (z * sig)


def _out_fwd_kernel(sp, eps):
    def kernel(y_ref, z_ref, scale_ref, out_ref, g_ref):
        for _, group in _head_cols(sp):
            tiles = _lane_tiles(group)

            def chunk(r0, _):
                rows = pl.ds(r0, sp.rows)
                squares = jnp.zeros((sp.rows, LANES), _f32)
                for cols in tiles:
                    *_, g = _gated(y_ref, z_ref, rows, cols)
                    g_ref[:, cols] = g
                    squares += g * g
                r = lax.rsqrt(_row_sum(squares) / sp.d + eps)
                for cols in tiles:
                    out_ref[0, rows, cols] = (
                        g_ref[:, cols] * r * scale_ref[:, cols]
                    ).astype(out_ref.dtype)

            _chunks(sp, chunk)

    return kernel


def _out_bwd_kernel(sp, eps):
    def kernel(y_ref, z_ref, scale_ref, do_ref, dy_ref, dz_ref, dcols_ref,
               g_ref, sig_ref):
        _zero_on_first_step(dcols_ref)
        for _, group in _head_cols(sp):
            tiles = _lane_tiles(group)

            def chunk(r0, acc):
                rows = pl.ds(r0, sp.rows)
                squares = along = jnp.zeros((sp.rows, LANES), _f32)
                for cols in tiles:
                    _, _, sig, g = _gated(y_ref, z_ref, rows, cols)
                    g_ref[:, cols], sig_ref[:, cols] = g, sig
                    dn = do_ref[0, rows, cols].astype(_f32) * scale_ref[:, cols]
                    squares += g * g
                    along += dn * g
                r = lax.rsqrt(_row_sum(squares) / sp.d + eps)
                back = (r * r * r / sp.d) * _row_sum(along)
                sums = []
                for a, cols in zip(acc, tiles):
                    g, sig = g_ref[:, cols], sig_ref[:, cols]
                    y, z = y_ref[0, rows, cols], z_ref[0, rows, cols].astype(_f32)
                    do = do_ref[0, rows, cols].astype(_f32)
                    dg = r * (do * scale_ref[:, cols]) - back * g
                    dy_ref[0, rows, cols] = dg * (z * sig)
                    dz_ref[0, rows, cols] = (
                        dg * y * (sig * (1.0 + z * (1.0 - sig)))
                    ).astype(dz_ref.dtype)
                    sums.append(a + _fold(do * (g * r)))
                return tuple(sums)

            zero = jnp.zeros((EDGE, LANES), _f32)
            for a, cols in zip(_chunks(sp, chunk, (zero,) * len(tiles)), tiles):
                _add_rows(dcols_ref, 0, cols, a)

    return kernel


@partial(jax.jit, static_argnames=("groups", "eps", "dtype", "interpret"))
def _out_forward(y, z, scale, *, groups, eps, dtype, interpret):
    B, T, wide = y.shape
    sp = _Flat(B, T, groups, wide // groups)
    y, z = _pad_rows(y, sp), _pad_rows(z, sp)
    call = pl.pallas_call(
        _out_fwd_kernel(sp, eps),
        grid=sp.grid,
        out_shape=out_struct(y.shape, dtype, y, z, scale),
        in_specs=[sp.flat, sp.flat, sp.cols(1)],
        out_specs=sp.flat,
        scratch_shapes=[pltpu.VMEM((sp.rows, sp.cw), _f32)],
        compiler_params=sp.params(
            False, (4, z.dtype.itemsize, jnp.dtype(dtype).itemsize),
            sp.rows * sp.hb,
        ),
        interpret=interpret,
        name=OUT_FWD,
    )
    return _run(call, interpret, y, z, scale)[:, :T]


@partial(jax.jit, static_argnames=("groups", "eps", "interpret"))
def _out_backward(y, z, scale, do, *, groups, eps, interpret):
    B, T, wide = y.shape
    sp = _Flat(B, T, groups, wide // groups)
    y, z, do = _pad_rows(y, sp), _pad_rows(z, sp), _pad_rows(do, sp)
    call = pl.pallas_call(
        _out_bwd_kernel(sp, eps),
        grid=sp.grid,
        out_shape=[
            out_struct(y.shape, _f32, y, z, scale, do),
            out_struct(z.shape, z.dtype, y, z, scale, do),
            out_struct((EDGE, wide), _f32, y, z, scale, do),
        ],
        in_specs=[sp.flat, sp.flat, sp.cols(1), sp.flat],
        out_specs=[sp.flat, sp.flat, sp.cols(EDGE)],
        scratch_shapes=[pltpu.VMEM((sp.rows, sp.cw), _f32)] * 2,
        compiler_params=sp.params(
            True, (8, 2 * z.dtype.itemsize, do.dtype.itemsize),
            2 * sp.rows * sp.hb,
        ),
        interpret=interpret,
        name=OUT_BWD,
    )
    dy, dz, dcols = _run(call, interpret, y, z, scale, do)
    return dy[:, :T], dz[:, :T], dcols[:1]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _out(y, z, scale, how):
    groups, eps, dtype, interpret = how
    return _settled(
        _out_forward(y, z, scale, groups=groups, eps=eps, dtype=dtype, interpret=interpret),
        interpret,
    )


def _out_fwd(y, z, scale, how):
    return _out(y, z, scale, how), (y, z, scale)


def _out_bwd(how, res, do):
    groups, eps, _, interpret = how
    return _settled(
        _out_backward(*res, do, groups=groups, eps=eps, interpret=interpret), interpret
    )


_out.defvjp(_out_fwd, _out_bwd)


def gated_group_norm(y, z, scale, groups: int, eps: float, dtype, *,
                     interpret: InterpretArg = None):
    """``ops.ssd.gated_group_norm`` by the kernels: ``y`` (B, T, C) float32,
    ``z`` (B, T, C) the gate's projection, ``scale`` (C,); (B, T, C) in
    ``dtype``.  Differentiable by all three."""
    scale = scale.astype(_f32)[None]
    _, (y, z, scale) = vary_together(y.astype(_f32), z, scale)
    return _out(
        y, z, scale,
        (groups, float(eps), jnp.dtype(dtype), default_interpret(interpret)),
    )
