"""The KDA mixer's float32 chains round the core (``ops/kda.py``
``conv_in``, ``decay_in``, ``gated_out``) as six Mosaic kernels, each ONE
pass over HBM: a chain's intermediates stay in VMEM, the move between the
projections' ``(B, T, H d)`` and the core's head-major ``(B, H, T, d)`` is
a block's index map, and a ``custom_vjp`` saves nothing but the chain's
own inputs (the projections the matmuls made, the core's ``o``).

``ops.kda`` picks them from the shapes (:func:`takes`: heads of whole
lanes); the XLA forms there stay the oracle they are tested against and
what every other shape runs.

THE CHAINS, forward | backward (everything float32 but the projections
and their cotangents, which keep the matmuls' type):

* ``kda_in_fwd`` | ``kda_in_bwd`` (q, k and v): projection -> causal
  depthwise convolution (zero left padding, the last tap the current
  token's) -> SiLU -> (q, k) the L2 norm over a head's columns, q scaled
  -> head-major | the projection's cotangent and the taps' gradient, the
  pre-SiLU values rebuilt from the projection.
* ``kda_decay_fwd`` | ``kda_decay_bwd``: projection + a bias a channel ->
  ``lower_bound * sigmoid(rate * .)`` (a rate a head, handed over a
  channel), or under ``lower_bound`` None the published unbounded gate
  ``-rate * softplus(.)`` (static: a bound's kernels are the bodies they
  were) -> head-major | the projection's cotangent, the bias's and the
  rate's gradients.
* ``kda_out_fwd`` | ``kda_out_bwd``: the core's ``o`` -> RMS norm a head
  with a scale -> ``* sigmoid(gate projection)`` -> ``(B, T, H d)`` in the
  matmuls' type | ``o``'s and the gate projection's cotangents, the
  scale's gradient.

THE GRID.  ``(column blocks, B, row tiles)``: a step holds :data:`TILE`
rows of as many whole heads as :data:`WIDTH` columns hold, and walks them
a head and :data:`ROWS` rows at a time: what a chain makes for those rows
stays in VMEM, and the rows are many because a row's norm (a sum over
lanes, a root, a broadcast back) is a long chain that only other rows'
work hides (measured at the cell's shape, q forward: 2.25 ms at 32 rows,
0.85 at 128, 0.70 at 256 and at 512).  A row tile of the convolution needs the
``taps - 1`` rows BEFORE it forward, and backward those AFTER it too (a
token's cotangent comes from the next tokens' taps): they arrive as HALO
blocks of the same arrays (:data:`HALO` rows of a projection, :data:`EDGE`
of a head-major cotangent), zeros past either end of the sequence, not by
a second pass.  The forward's shifted rows are unaligned loads from a
float32 copy of the tile in a VMEM scratch.  A gradient that sums over B
and T (the taps', the bias's, the rate's, the scale's) is accumulated a
COLUMN in an output block that stays in VMEM across the two inner grid
axes (``arbitrary``); what sums over heads besides (the rate's columns of
a head, the scale's heads) is summed outside from ``H d`` numbers.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import LANES, InterpretArg, default_interpret, out_struct, vary_together
from .grouped_matmul import _run, _settled

IN_FWD, IN_BWD = "kda_in_fwd", "kda_in_bwd"
DECAY_FWD, DECAY_BWD = "kda_decay_fwd", "kda_decay_bwd"
OUT_FWD, OUT_BWD = "kda_out_fwd", "kda_out_bwd"
#: rows and (at most) columns a grid step, rows a head's chain is run over
#: at a time, rows of a halo block of a projection (bfloat16's
#: sublane tile) and of a head-major float32 array (float32's: the most
#: taps a convolution can have, and the rows a column gradient's block has)
TILE, WIDTH, ROWS, HALO, EDGE = 512, 1024, 256, 16, 8
#: the L2 norm's epsilon, inside the root (``ops.kda.conv_in``'s)
UNIT_EPS = 1e-6

_f32 = jnp.float32


def takes(wide: int, heads: int, taps: int = 1) -> bool:
    """The shape rule: the kernels take heads of whole lanes (``wide``
    columns of ``heads`` heads) and convolutions whose reach a halo block
    holds; any row count (padded to whole tiles).  Any other shape is the
    XLA form's."""
    return (
        wide % heads == 0 and (wide // heads) % LANES == 0
        and 1 <= taps <= EDGE
    )


def _geometry(T, H, d):
    """``(rows a tile, rows at a time, the padded length, heads a column
    block)``: a short sequence is one tile walked at once."""
    tt = min(TILE, -(-T // (2 * HALO)) * 2 * HALO)
    hb = max(n for n in range(1, H + 1) if H % n == 0 and n * d <= max(WIDTH, d))
    return tt, (tt if tt % ROWS else ROWS), -(-T // tt) * tt, hb


class _Specs:
    """Block specs on the grid ``(column blocks, B, row tiles)``: ``flat``
    a ``(B, Tp, H d)`` array's tile, ``heads`` a ``(B, H, Tp, d)`` one's,
    ``cols(rows)`` a ``(rows, H d)`` array's columns, ``head(rows)`` a
    ``(rows, d)`` array whole; ``before`` / ``after`` the halo blocks of a
    flat array, ``heads_after`` of a head-major one (clamped at the ends:
    the kernels zero what lies past them)."""

    def __init__(self, B, T, H, d):
        tt, self.rows, Tp, hb = _geometry(T, H, d)
        self.tt, self.Tp, self.hb, self.d, self.cw = tt, Tp, hb, d, hb * d
        cw = self.cw
        self.grid = (H // hb, B, Tp // tt)
        self.flat = pl.BlockSpec((1, tt, cw), lambda c, b, t: (b, t, c))
        self.heads = pl.BlockSpec((1, hb, tt, d), lambda c, b, t: (b, c, t, 0))
        self.cols = lambda rows: pl.BlockSpec((rows, cw), lambda c, b, t: (0, c))
        self.head = lambda rows: pl.BlockSpec((rows, d), lambda c, b, t: (0, 0))
        self.before = pl.BlockSpec(
            (1, HALO, cw),
            lambda c, b, t: (b, jnp.maximum(t * (tt // HALO) - 1, 0), c),
        )
        self.after = pl.BlockSpec(
            (1, HALO, cw),
            lambda c, b, t: (b, jnp.minimum((t + 1) * (tt // HALO), Tp // HALO - 1), c),
        )
        self.heads_after = pl.BlockSpec(
            (1, hb, EDGE, d),
            lambda c, b, t: (b, c, jnp.minimum((t + 1) * (tt // EDGE), Tp // EDGE - 1), 0),
        )

    def params(self, backward, itemsizes, scratch_rows=0):
        """Compiler parameters: the double-buffered blocks (``itemsizes``
        of the tile-sized operands and results), the scratch, and room
        for what the compiler spills."""
        blocks = 2 * sum(itemsizes) * self.tt * self.cw
        return pltpu.CompilerParams(
            dimension_semantics=(
                ("parallel", "arbitrary", "arbitrary") if backward
                else ("parallel",) * 3
            ),
            vmem_limit_bytes=blocks + scratch_rows * self.d * 4 + (16 << 20),
        )


def _head_cols(sp):
    """``(head, its columns)`` of a column block."""
    return [(j, slice(j * sp.d, (j + 1) * sp.d)) for j in range(sp.hb)]


def _pad_rows(x, sp, axis=1):
    """``x`` with its row axis padded to whole tiles."""
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, sp.Tp - x.shape[axis])
    return jnp.pad(x, widths)


def _chunks(sp, body, carry=None):
    """``body(first row, carry)`` for every ``sp.rows`` rows of a tile."""
    return lax.fori_loop(
        0, sp.tt // sp.rows,
        lambda c, carry: body(pl.multiple_of(c * sp.rows, sp.rows), carry), carry,
    )


def _fold(x):
    """``(rows, d)`` summed to float32's sublane tile ``(EDGE, d)``: whole
    registers added, no move inside one."""
    return sum(x[r:r + EDGE] for r in range(0, x.shape[0], EDGE))


def _row_sum(x):
    """A row's sum over its head's columns, ``(rows, 1)``."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _add_rows(ref, row, cols, acc):
    ref[row:row + 1, cols] += jnp.sum(acc, axis=0, keepdims=True)


def _zero_on_first_step(ref):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        ref[...] = jnp.zeros_like(ref)


# -- q, k, v in --------------------------------------------------------------------


def _shifted(xs_ref, start, rows, n):
    """The ``n`` row windows a convolution's taps multiply, the first
    starting at ``start`` of the staged tile."""
    return [xs_ref[pl.ds(start + i, rows), :] for i in range(n)]


def _conv_silu(shifted, w):
    u = sum(x * tap for x, tap in zip(shifted, w))
    sig = jax.nn.sigmoid(u)
    return u, sig, u * sig


def _in_fwd_kernel(n, sp, unit, scale):
    d, tt = sp.d, sp.tt

    def kernel(x_ref, before_ref, w_ref, out_ref, xs_ref):
        first = pl.program_id(2) == 0
        for j, cols in _head_cols(sp):
            w = [w_ref[i:i + 1, cols].astype(_f32) for i in range(n)]
            xs_ref[:HALO] = jnp.where(first, 0.0, before_ref[0, :, cols].astype(_f32))
            xs_ref[HALO:] = x_ref[0, :, cols].astype(_f32)

            def chunk(r0, _):
                _, _, y = _conv_silu(_shifted(xs_ref, HALO - (n - 1) + r0, sp.rows, n), w)
                if unit:
                    y = y * lax.rsqrt(_row_sum(y * y) + UNIT_EPS)
                    if scale != 1.0:
                        y = y * scale
                out_ref[0, j, pl.ds(r0, sp.rows), :] = y

            _chunks(sp, chunk)

    return kernel


def _in_bwd_kernel(n, sp, unit, scale):
    d, tt = sp.d, sp.tt
    p = n - 1

    def kernel(x_ref, before_ref, after_ref, w_ref, do_ref, do_after_ref,
               dx_ref, dw_ref, xs_ref, du_ref):
        first = pl.program_id(2) == 0
        last = pl.program_id(2) == pl.num_programs(2) - 1
        _zero_on_first_step(dw_ref)
        for j, cols in _head_cols(sp):
            w = [w_ref[i:i + 1, cols].astype(_f32) for i in range(n)]
            xs_ref[:HALO] = jnp.where(first, 0.0, before_ref[0, :, cols].astype(_f32))
            xs_ref[HALO:HALO + tt] = x_ref[0, :, cols].astype(_f32)
            xs_ref[HALO + tt:] = after_ref[0, :, cols].astype(_f32)

            def du_of(r0, rows, do):
                """The cotangent of the convolution's output at ``rows``
                rows from ``r0``, and the windows its taps multiplied."""
                shifted = _shifted(xs_ref, HALO - p + r0, rows, n)
                u, sig, y = _conv_silu(shifted, w)
                if unit:
                    r = lax.rsqrt(_row_sum(y * y) + UNIT_EPS)
                    if scale != 1.0:
                        do = do * scale
                    do = r * do - (r * r * r) * _row_sum(do * y) * y
                return do * (sig * (1.0 + u * (1.0 - sig))), shifted

            def chunk(r0, acc):
                du, shifted = du_of(r0, sp.rows, do_ref[0, j, pl.ds(r0, sp.rows), :])
                du_ref[pl.ds(r0, sp.rows), :] = du
                return tuple(a + _fold(du * x) for a, x in zip(acc, shifted))

            acc = _chunks(sp, chunk, tuple(jnp.zeros((EDGE, d), _f32) for _ in w))
            for i, a in enumerate(acc):
                _add_rows(dw_ref, i, cols, a)
            # the next tile's first rows: their taps reach back into this one
            du_ref[tt:] = du_of(tt, EDGE, jnp.where(last, 0.0, do_after_ref[0, j]))[0]

            def place(r0, _):
                dx = sum(
                    du_ref[pl.ds(r0 + p - i, sp.rows), :] * tap for i, tap in enumerate(w)
                )
                dx_ref[0, pl.ds(r0, sp.rows), cols] = dx.astype(dx_ref.dtype)

            _chunks(sp, place)

    return kernel


@partial(jax.jit, static_argnames=("heads", "unit", "scale", "interpret"))
def _in_forward(x, taps, *, heads, unit, scale, interpret):
    B, T, wide = x.shape
    sp = _Specs(B, T, heads, wide // heads)
    n = taps.shape[0]
    x = _pad_rows(x, sp)
    call = pl.pallas_call(
        _in_fwd_kernel(n, sp, unit, scale),
        grid=sp.grid,
        out_shape=out_struct((B, heads, sp.Tp, sp.d), _f32, x, taps),
        in_specs=[sp.flat, sp.before, sp.cols(n)],
        out_specs=sp.heads,
        scratch_shapes=[pltpu.VMEM((HALO + sp.tt, sp.d), _f32)],
        compiler_params=sp.params(False, (x.dtype.itemsize, 4), HALO + sp.tt),
        interpret=interpret,
        name=IN_FWD,
    )
    return _run(call, interpret, x, x, taps)[:, :, :T]


@partial(jax.jit, static_argnames=("unit", "scale", "interpret"))
def _in_backward(x, taps, do, *, unit, scale, interpret):
    B, T, wide = x.shape
    heads = do.shape[1]
    sp = _Specs(B, T, heads, wide // heads)
    n = taps.shape[0]
    x = _pad_rows(x, sp)
    do = _pad_rows(do, sp, axis=2)
    call = pl.pallas_call(
        _in_bwd_kernel(n, sp, unit, scale),
        grid=sp.grid,
        out_shape=[
            out_struct(x.shape, x.dtype, x, taps, do),
            out_struct((EDGE, wide), _f32, x, taps, do),
        ],
        in_specs=[sp.flat, sp.before, sp.after, sp.cols(n), sp.heads, sp.heads_after],
        out_specs=[sp.flat, sp.cols(EDGE)],
        scratch_shapes=[
            pltpu.VMEM((2 * HALO + sp.tt, sp.d), _f32),
            pltpu.VMEM((sp.tt + EDGE, sp.d), _f32),
        ],
        compiler_params=sp.params(
            True, (2 * x.dtype.itemsize, 4), 2 * (HALO + sp.tt)
        ),
        interpret=interpret,
        name=IN_BWD,
    )
    dx, dw = _run(call, interpret, x, x, x, taps, do, do)
    return dx[:, :T], dw[:n].astype(taps.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _in(x, taps, how):
    heads, unit, scale, interpret = how
    return _settled(
        _in_forward(x, taps, heads=heads, unit=unit, scale=scale, interpret=interpret),
        interpret,
    )


def _in_fwd(x, taps, how):
    return _in(x, taps, how), (x, taps)


def _in_bwd(how, res, do):
    _, unit, scale, interpret = how
    grads = _in_backward(*res, do, unit=unit, scale=scale, interpret=interpret)
    return _settled(grads, interpret)


_in.defvjp(_in_fwd, _in_bwd)


def conv_in(x, taps, heads: int, *, unit: bool, scale: float = 1.0,
            interpret: InterpretArg = None):
    """``ops.kda.conv_in`` by the kernels: ``x`` (B, T, H d) a projection,
    ``taps`` (n, H d); float32 (B, H, T, d).  Differentiable by both."""
    _, (x, taps) = vary_together(x, taps)
    return _in(x, taps, (heads, unit, float(scale), default_interpret(interpret)))


# -- the decay in ------------------------------------------------------------------


def _softplus(f):
    """``log(1 + e^f)`` without overflow."""
    return jnp.maximum(f, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(f)))


def _decay_fwd_kernel(sp, lower_bound):
    def kernel(x_ref, bias_ref, rate_ref, g_ref):
        for j, cols in _head_cols(sp):
            bias, rate = bias_ref[:, cols], rate_ref[:, cols]

            def chunk(r0, _):
                f = x_ref[0, pl.ds(r0, sp.rows), cols].astype(_f32) + bias
                if lower_bound is None:
                    g_ref[0, j, pl.ds(r0, sp.rows), :] = -rate * _softplus(f)
                    return
                g_ref[0, j, pl.ds(r0, sp.rows), :] = lower_bound * jax.nn.sigmoid(rate * f)

            _chunks(sp, chunk)

    return kernel


def _decay_bwd_kernel(sp, lower_bound):
    def kernel(x_ref, bias_ref, rate_ref, dg_ref, dx_ref, dcols_ref):
        _zero_on_first_step(dcols_ref)
        for j, cols in _head_cols(sp):
            bias, rate = bias_ref[:, cols], rate_ref[:, cols]

            def chunk(r0, acc):
                f = x_ref[0, pl.ds(r0, sp.rows), cols].astype(_f32) + bias
                if lower_bound is None:
                    dg = dg_ref[0, j, pl.ds(r0, sp.rows), :]
                    df = -dg * rate * jax.nn.sigmoid(f)
                    dx_ref[0, pl.ds(r0, sp.rows), cols] = df.astype(dx_ref.dtype)
                    return acc[0] + _fold(df), acc[1] - _fold(dg * _softplus(f))
                s = jax.nn.sigmoid(rate * f)
                ds = dg_ref[0, j, pl.ds(r0, sp.rows), :] * lower_bound * (s * (1.0 - s))
                df = ds * rate
                dx_ref[0, pl.ds(r0, sp.rows), cols] = df.astype(dx_ref.dtype)
                return acc[0] + _fold(df), acc[1] + _fold(ds * f)

            zero = jnp.zeros((EDGE, sp.d), _f32)
            for row, a in enumerate(_chunks(sp, chunk, (zero, zero))):
                _add_rows(dcols_ref, row, cols, a)

    return kernel


@partial(jax.jit, static_argnames=("heads", "lower_bound", "interpret"))
def _decay_forward(x, bias, rate, *, heads, lower_bound, interpret):
    B, T, wide = x.shape
    sp = _Specs(B, T, heads, wide // heads)
    x = _pad_rows(x, sp)
    call = pl.pallas_call(
        _decay_fwd_kernel(sp, lower_bound),
        grid=sp.grid,
        out_shape=out_struct((B, heads, sp.Tp, sp.d), _f32, x, bias, rate),
        in_specs=[sp.flat, sp.cols(1), sp.cols(1)],
        out_specs=sp.heads,
        compiler_params=sp.params(False, (x.dtype.itemsize, 4)),
        interpret=interpret,
        name=DECAY_FWD,
    )
    return _run(call, interpret, x, bias, rate)[:, :, :T]


@partial(jax.jit, static_argnames=("lower_bound", "interpret"))
def _decay_backward(x, bias, rate, dg, *, lower_bound, interpret):
    B, T, wide = x.shape
    heads = dg.shape[1]
    sp = _Specs(B, T, heads, wide // heads)
    x = _pad_rows(x, sp)
    dg = _pad_rows(dg, sp, axis=2)
    call = pl.pallas_call(
        _decay_bwd_kernel(sp, lower_bound),
        grid=sp.grid,
        out_shape=[
            out_struct(x.shape, x.dtype, x, bias, rate, dg),
            out_struct((EDGE, wide), _f32, x, bias, rate, dg),
        ],
        in_specs=[sp.flat, sp.cols(1), sp.cols(1), sp.heads],
        out_specs=[sp.flat, sp.cols(EDGE)],
        compiler_params=sp.params(True, (2 * x.dtype.itemsize, 4)),
        interpret=interpret,
        name=DECAY_BWD,
    )
    dx, dcols = _run(call, interpret, x, bias, rate, dg)
    return dx[:, :T], dcols[:1], dcols[1:2]


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decay(x, bias, rate, how):
    heads, lower_bound, interpret = how
    return _settled(
        _decay_forward(
            x, bias, rate, heads=heads, lower_bound=lower_bound, interpret=interpret
        ),
        interpret,
    )


def _decay_fwd(x, bias, rate, how):
    return _decay(x, bias, rate, how), (x, bias, rate)


def _decay_bwd(how, res, dg):
    _, lower_bound, interpret = how
    grads = _decay_backward(*res, dg, lower_bound=lower_bound, interpret=interpret)
    return _settled(grads, interpret)


_decay.defvjp(_decay_fwd, _decay_bwd)


def decay_in(x, dt_bias, a_log, lower_bound: Optional[float], *,
             interpret: InterpretArg = None):
    """``ops.kda.decay_in`` by the kernels: ``x`` (B, T, H d) a projection,
    ``dt_bias`` (H d,), ``a_log`` (H,); float32 (B, H, T, d).
    Differentiable by all three (the rate a head reaches the kernels a
    channel: its exponential and its columns' sum are XLA's, H d numbers)."""
    heads = a_log.shape[0]
    bias = dt_bias.astype(_f32)[None]
    rate = jnp.repeat(jnp.exp(a_log.astype(_f32)), x.shape[-1] // heads)[None]
    _, (x, bias, rate) = vary_together(x, bias, rate)
    return _decay(
        x, bias, rate, (
            heads, None if lower_bound is None else float(lower_bound),
            default_interpret(interpret),
        ),
    )


# -- out ---------------------------------------------------------------------------


def _normed(o, eps):
    r = lax.rsqrt(_row_sum(o * o) / o.shape[-1] + eps)
    return r, o * r


def _out_fwd_kernel(sp, eps):
    def kernel(o_ref, gate_ref, scale_ref, y_ref):
        scale = scale_ref[...]
        for j, cols in _head_cols(sp):

            def chunk(r0, _):
                rows = pl.ds(r0, sp.rows)
                _, normed = _normed(o_ref[0, j, rows, :], eps)
                gate = jax.nn.sigmoid(gate_ref[0, rows, cols].astype(_f32))
                y_ref[0, rows, cols] = (normed * scale * gate).astype(y_ref.dtype)

            _chunks(sp, chunk)

    return kernel


def _out_bwd_kernel(sp, eps):
    def kernel(o_ref, gate_ref, scale_ref, dy_ref, do_ref, dgate_ref, dcols_ref):
        _zero_on_first_step(dcols_ref)
        scale = scale_ref[...]
        for j, cols in _head_cols(sp):

            def chunk(r0, acc):
                rows = pl.ds(r0, sp.rows)
                o = o_ref[0, j, rows, :]
                r, normed = _normed(o, eps)
                gate = jax.nn.sigmoid(gate_ref[0, rows, cols].astype(_f32))
                dy = dy_ref[0, rows, cols].astype(_f32)
                dz = dy * gate
                dgate = dy * (normed * scale) * (gate * (1.0 - gate))
                dgate_ref[0, rows, cols] = dgate.astype(dgate_ref.dtype)
                dn = dz * scale
                do_ref[0, j, rows, :] = r * dn - (r * r * r / sp.d) * _row_sum(dn * o) * o
                return acc + _fold(dz * normed)

            acc = _chunks(sp, chunk, jnp.zeros((EDGE, sp.d), _f32))
            _add_rows(dcols_ref, 0, cols, acc)

    return kernel


@partial(jax.jit, static_argnames=("eps", "dtype", "interpret"))
def _out_forward(o, gate, scale, *, eps, dtype, interpret):
    B, H, T, d = o.shape
    sp = _Specs(B, T, H, d)
    o = _pad_rows(o, sp, axis=2)
    gate = _pad_rows(gate, sp)
    call = pl.pallas_call(
        _out_fwd_kernel(sp, eps),
        grid=sp.grid,
        out_shape=out_struct((B, sp.Tp, H * d), dtype, o, gate, scale),
        in_specs=[sp.heads, sp.flat, sp.head(1)],
        out_specs=sp.flat,
        compiler_params=sp.params(
            False, (4, gate.dtype.itemsize, jnp.dtype(dtype).itemsize)
        ),
        interpret=interpret,
        name=OUT_FWD,
    )
    return _run(call, interpret, o, gate, scale)[:, :T]


@partial(jax.jit, static_argnames=("eps", "interpret"))
def _out_backward(o, gate, scale, dy, *, eps, interpret):
    B, H, T, d = o.shape
    sp = _Specs(B, T, H, d)
    o = _pad_rows(o, sp, axis=2)
    gate, dy = _pad_rows(gate, sp), _pad_rows(dy, sp)
    call = pl.pallas_call(
        _out_bwd_kernel(sp, eps),
        grid=sp.grid,
        out_shape=[
            out_struct(o.shape, _f32, o, gate, scale, dy),
            out_struct(gate.shape, gate.dtype, o, gate, scale, dy),
            out_struct((EDGE, H * d), _f32, o, gate, scale, dy),
        ],
        in_specs=[sp.heads, sp.flat, sp.head(1), sp.flat],
        out_specs=[sp.heads, sp.flat, sp.cols(EDGE)],
        compiler_params=sp.params(
            True, (8, 2 * gate.dtype.itemsize, dy.dtype.itemsize)
        ),
        interpret=interpret,
        name=OUT_BWD,
    )
    do, dgate, dcols = _run(call, interpret, o, gate, scale, dy)
    dscale = dcols[0].reshape(H, d).sum(axis=0)[None]
    return do[:, :, :T], dgate[:, :T], dscale


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _out(o, gate, scale, how):
    eps, dtype, interpret = how
    return _settled(
        _out_forward(o, gate, scale, eps=eps, dtype=dtype, interpret=interpret),
        interpret,
    )


def _out_fwd(o, gate, scale, how):
    return _out(o, gate, scale, how), (o, gate, scale)


def _out_bwd(how, res, dy):
    eps, _, interpret = how
    return _settled(_out_backward(*res, dy, eps=eps, interpret=interpret), interpret)


_out.defvjp(_out_fwd, _out_bwd)


def gated_out(o, gate, o_norm, eps: float, dtype, *,
              interpret: InterpretArg = None):
    """``ops.kda.gated_out`` by the kernels: ``o`` (B, H, T, d) float32,
    ``gate`` (B, T, H d) a projection, ``o_norm`` (d,); (B, T, H d) in
    ``dtype``.  Differentiable by all three."""
    scale = o_norm.astype(_f32)[None]
    _, (o, gate, scale) = vary_together(o.astype(_f32), gate, scale)
    return _out(
        o, gate, scale,
        (float(eps), jnp.dtype(dtype), default_interpret(interpret)),
    )
