"""Grouped matmul: rows grouped contiguously by expert, one matrix a group.

``grouped_matmul(lhs, rhs, group_sizes)`` is ``(m, k) x (E, k, n) ->
(m, n)``: rows ``sum(group_sizes[:g]) .. sum(group_sizes[:g+1])`` of
``lhs`` meet ``rhs[g]`` — the contract of ``jax.lax.ragged_dot``, and what
the dropless experts of ``models/moe.py`` run three times a layer.

Three tiled kernels after jax's megablox
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: the bodies of its
``gmm``, ``gmm(transpose_rhs=True)`` and ``tgmm``, without their
sharded-groups and ``existing_out`` arguments), one for each product
autodiff needs, behind one ``custom_vjp``:

* ``gmm_fwd``: ``out[rows of g] = lhs[rows of g] @ rhs[g]``;
* ``gmm_dlhs`` (the input's gradient): ``grad[rows of g] @ rhs[g].T``,
  the same kernel with the weight block read transposed;
* ``gmm_drhs`` (the weights' gradient): ``lhs[rows of g].T @ grad[rows
  of g]`` for each group.

A row tile is ``tm`` rows wherever the groups begin, so a tile that holds
a group boundary is visited once by each group in it, under a row mask.
Which tile and which group a grid step works on is the GROUP METADATA,
scalar-prefetched: small integer arrays that :func:`group_metadata`
computes from ``group_sizes``, the same for all three forms of all three
matmuls of a layer (one jitted function of ``group_sizes``: traced once a
process, and the compiler merges the calls of a layer).

Why the kernels are in the tree and not imported (PR 28, CHANGES.md):
imported, they were as fast, but the train cell's set-up grew by 3.9 s of
30 where its bound is 10%, because megablox computes that metadata inside
each of its jitted calls (eight traces a process, 850 small device ops a
step); it also builds its ``out_shape`` without the ``vma`` that
``pallas_call`` needs inside a ``check_vma`` shard_map (the sharded train
step), and names no kernel.  From the tree the set-up grows by 1.6 s.

Operands as stored, float32 accumulation, each result in its operand's
dtype.  Each form runs under a plain nested ``jax.named_scope`` of its
name, so that a compiled step's text tells them apart.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax._src.config import _check_vma
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import (
    LANES,
    InterpretArg,
    block_rows,
    default_interpret,
    out_struct,
)

#: the three kernels, by the scope name each runs under
FWD, DLHS, DRHS = "gmm_fwd", "gmm_dlhs", "gmm_drhs"
#: rows a tile (``_common.block_rows``: the largest aligned divisor of the
#: row count up to this, or every row), all three forms: a group that
#: starts inside a tile costs one more visit of that tile, which 512 rows
#: pay 1.49 times over at 1,024 rows a group and 256 rows 1.25 times, while
#: 128 rows feed the MXU worse (chip sweep of PR 28, CHANGES.md)
ROWS = 256
#: one buffer of the ``(tk, tn)`` weight block of ``gmm``: two of them
#: are half of the 16 MiB of VMEM a kernel gets by default
_WEIGHT_BLOCK_BYTES = 4 << 20


def tiles(form: str, m: int, k: int, n: int, dtype) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of one form of the ``(m, k) x (E, k, n)`` product,
    from the shapes and the operands' width alone; ``tm`` divides ``m``
    and is the same for the three forms.

    ``gmm_fwd`` and ``gmm_dlhs`` (where ``n`` is contracted and ``k`` the
    result's columns): ``tk`` tiles the contracted dimension, ``tn`` the
    columns.  ``tk`` is the WHOLE contraction up to 2048: the weight
    block's index then stays put along a group's row tiles and Pallas
    fetches a group's weights once, where two k-steps fetch them again
    for every row tile (on the v5e at 65,536 x 2048 x 1024 x 64 groups,
    bf16: 1.92 against 2.51 ms).  ``tn`` is as wide as leaves the
    double-buffered weight block half of the scoped VMEM.  ``gmm_drhs``:
    ``(tk, tn)`` is the block of one group's ``(k, n)`` result, held in
    float32 while ``tm`` rows a step stream past; 1024 x 1024 in bf16
    reads both operands twice, and larger does not fit.
    """
    itemsize = jnp.dtype(dtype).itemsize
    tm = block_rows(m, ROWS)
    if form == DRHS:
        return tm, _dividing(k, 1024), _dividing(n, 2048 // itemsize)
    contract, cols = (n, k) if form == DLHS else (k, n)
    tk = _dividing(contract, 2048)
    fits = _WEIGHT_BLOCK_BYTES // itemsize // tk // LANES * LANES
    return tm, tk, min(cols, max(fits, LANES))


def _dividing(size: int, most: int) -> int:
    """The tile of a dimension of ``size``: all of it up to ``most``, else
    the largest multiple of the lanes under ``most`` that divides it (5120
    in tiles of 1280, where 2048 would leave a last tile to mask and 1365
    columns no tile Mosaic takes), else ``most``."""
    if size <= most:
        return size
    return next(
        (t for t in range(most // LANES * LANES, 0, -LANES) if size % t == 0),
        most,
    )


# ---------------------------------------------------------------------------
# group metadata


@partial(jax.jit, static_argnames=("m", "tm"))
def group_metadata(group_sizes: jax.Array, *, m: int, tm: int):
    """Which group and which row tile each grid step works on.

    Returns ``(group_offsets (E+1,), group_ids (L,), m_tile_ids (L,),
    num_tiles ())`` with ``L = m // tm + E - 1``, the most steps there
    can be; the first ``num_tiles`` entries count.  Steps go through the
    groups in order and through each group's tiles in order, so the
    visits of one row tile are consecutive (an output tile is finished
    before the next begins) and so are the steps of one group.  A group
    owns the tiles its rows reach; an EMPTY group gets one step, on the
    tile its offset falls in (the last tile for an empty group at the
    end), where the row mask selects nothing: ``gmm_drhs`` needs that
    step to write the group's zero gradient, and the other two forms
    spend it (megablox keeps a second metadata without it for them;
    skipping it in the kernel, with the previous group's weights left in
    place, cost more than it saved: PR 28, CHANGES.md).
    """
    E = group_sizes.shape[0]
    tiles_m = m // tm
    steps = jnp.arange(tiles_m + E - 1, dtype=jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first, last = starts // tm, (ends + tm - 1) // tm     # tiles [first, last)
    group_tiles = jnp.where(group_sizes == 0, 1, last - first)
    # a step's group: how many groups' steps end at or before it
    group_ids = jnp.sum(
        jnp.cumsum(group_tiles)[None, :] <= steps[:, None], axis=1
    )
    # a tile is visited once by the group that holds its first row and
    # once more by every group that starts inside it (an empty group
    # counts as starting there, wherever in the tile its offset is)
    starts_inside = jnp.logical_or(starts % tm != 0, group_sizes == 0)
    visits = 1 + jnp.zeros((tiles_m,), jnp.int32).at[
        jnp.where(starts_inside, first, tiles_m)
    ].add(1, mode="drop")
    m_tile_ids = jnp.sum(jnp.cumsum(visits)[None, :] <= steps[:, None], axis=1)
    return (
        jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(jnp.int32),
        jnp.minimum(group_ids, E - 1).astype(jnp.int32),
        jnp.minimum(m_tile_ids, tiles_m - 1).astype(jnp.int32),
        jnp.sum(group_tiles).astype(jnp.int32),
    )


def _row_mask(meta_refs, step, tm: int, width: int):
    """``(tm, width)``: the rows of this step's tile that belong to this
    step's group."""
    offsets, group_ids, m_tile_ids = meta_refs
    group = group_ids[step]
    rows = lax.broadcasted_iota(jnp.int32, (tm, width), 0) + m_tile_ids[step] * tm
    return jnp.logical_and(rows >= offsets[group], rows < offsets[group + 1])


# ---------------------------------------------------------------------------
# the kernels


def _precision(dtype):
    """float32 operands follow the ambient ``jax.default_matmul_precision``
    as ``ragged_dot`` does; bfloat16 ones are one exact MXU pass whatever
    it says, and Mosaic refuses them any other precision."""
    return lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _interpreter_check_off(interpret, vma):
    """The TPU interpreter binds a kernel's primitives one at a time, and
    inside a ``check_vma`` shard_map (the sharded train step) it refuses
    those that mix a scalar-prefetched value or the data-dependent grid
    bound, when they vary over the mesh axes, with a program id or an
    iota, which do not.  So where a kernel is interpreted there, the
    group metadata and the kernel are traced with the check off (no
    public switch covers a region).  Compiled: nothing."""
    return _check_vma(False) if interpret and vma else contextlib.nullcontext()


def _run(call, interpret, *operands):
    """``call(*operands)``; interpreted inside a ``check_vma`` shard_map,
    with the check off and the operands' axes given back to the result."""
    vma = tuple(jax.typeof(operands[-1]).vma)
    with _interpreter_check_off(interpret, vma):
        out = call(*operands)
    return lax.pcast(out, vma, to="varying") if interpret and vma else out


# jitted, so that the call sites of a program (three a layer of each
# form) share one trace and one lowered function a shape
@partial(jax.jit, static_argnames=("form", "tiling", "transpose_rhs", "interpret"))
def _gmm(lhs, rhs, meta, *, form, tiling, transpose_rhs, interpret):
    """``lhs[rows of g] @ rhs[g]`` (``rhs[g].T`` with ``transpose_rhs``)
    for every group ``g``: grid ``(column tiles, steps, k tiles)``."""
    *meta, num_tiles = meta
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    dtype = lhs.dtype

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref, acc):
        step, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        def mask_rem(x, dim):
            # the last k tile of a contraction that tk does not divide
            keep = lax.broadcasted_iota(jnp.int32, x.shape, dim) < k_rem
            return jnp.where(keep, x.astype(jnp.float32), 0).astype(x.dtype)

        def accumulate(last: bool):
            a, b = lhs_ref[...], rhs_ref[...]
            if last and k_rem:
                a, b = mask_rem(a, 1), mask_rem(b, int(transpose_rhs))
            acc[...] += lax.dot_general(
                a, b, (((1,), (1 if transpose_rhs else 0,)), ((), ())),
                precision=_precision(dtype), preferred_element_type=jnp.float32,
            )
            if last:
                mask = _row_mask((offsets, group_ids, m_tile_ids), step, tm, tn)
                out_ref[...] = lax.select(
                    mask, acc[...], out_ref[...].astype(jnp.float32)
                ).astype(dtype)

        lax.cond(k_i == tiles_k - 1, partial(accumulate, True),
                 partial(accumulate, False))

    def rhs_index(n_i, step, k_i, offsets, group_ids, m_tile_ids):
        return (group_ids[step],) + ((n_i, k_i) if transpose_rhs else (k_i, n_i))

    call = pl.pallas_call(
        kernel,
        out_shape=out_struct((m, n), dtype, lhs, rhs, *meta),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda n_i, step, k_i, o, g, t: (t[step], k_i)
                ),
                pl.BlockSpec(
                    (None, tn, tk) if transpose_rhs else (None, tk, tn),
                    rhs_index,
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, step, k_i, o, g, t: (t[step], n_i)
            ),
            grid=(pl.cdiv(n, tn), num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + k * n * meta[1].size + m * n
            ),
        ),
        interpret=interpret,
        name=form,
    )
    with jax.named_scope(form):
        return _run(call, interpret, *meta, lhs, rhs)


@partial(jax.jit, static_argnames=("tiling", "groups", "out_dtype", "interpret"))
def _tgmm(lhs, grad, meta, *, tiling, groups, out_dtype, interpret):
    """``lhs[rows of g].T @ grad[rows of g]`` for every group ``g``:
    grid ``(n tiles, k tiles, steps)``, a group's ``(tk, tn)`` block
    accumulated over its steps and written on its last."""
    *meta, num_tiles = meta
    (m, k), n = lhs.shape, grad.shape[1]
    tm, tk, tn = tiling

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, grad_ref, out_ref, acc):
        step, steps = pl.program_id(2), pl.num_programs(2)
        group = group_ids[step]
        prev = group_ids[jnp.maximum(step - 1, 0)]
        nxt = group_ids[jnp.minimum(step + 1, steps - 1)]

        @pl.when(jnp.logical_or(step == 0, prev != group))
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(offsets[group + 1] > offsets[group])
        def _():
            def rows(ref, width):
                mask = _row_mask((offsets, group_ids, m_tile_ids), step, tm, width)
                return lax.select(
                    mask, ref[...].astype(jnp.float32),
                    jnp.zeros((tm, width), jnp.float32),
                ).astype(ref.dtype)

            acc[...] += lax.dot(
                rows(lhs_ref, tk).swapaxes(0, 1), rows(grad_ref, tn),
                precision=_precision(lhs.dtype),
                preferred_element_type=jnp.float32,
            )

        @pl.when(jnp.logical_or(step == steps - 1, nxt != group))
        def _():
            out_ref[...] = acc[...].astype(out_dtype)

    call = pl.pallas_call(
        kernel,
        out_shape=out_struct((groups, k, n), out_dtype, lhs, grad, *meta),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec(
                    (tm, tk), lambda n_i, k_i, step, o, g, t: (t[step], k_i)
                ),
                pl.BlockSpec(
                    (tm, tn), lambda n_i, k_i, step, o, g, t: (t[step], n_i)
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda n_i, k_i, step, o, g, t: (g[step], k_i, n_i),
            ),
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * pl.cdiv(n, tn) + m * n * pl.cdiv(k, tk)
                + groups * k * n
            ),
        ),
        interpret=interpret,
        name=DRHS,
    )
    with jax.named_scope(DRHS):
        return _run(call, interpret, *meta, lhs, grad)


# ---------------------------------------------------------------------------
# the product and its gradients


def _settled(out, interpret):
    """``out``, waited for where it is an interpreted kernel's concrete
    result.  Called eagerly (no enclosing jit), the jitted kernel call
    returns while the interpreter's host callbacks are still running,
    and those run jax operations of their own: the caller's next eager
    operation then deadlocks with them once the host is busy enough
    (tests/test_olmoe.py's un-jitted forward, under the six test
    workers).  A tracer has nothing to wait for."""
    return jax.block_until_ready(out) if interpret else out


def _forward(lhs, rhs, meta, interpret):
    (m, k), n = lhs.shape, rhs.shape[2]
    return _settled(
        _gmm(lhs, rhs, meta, form=FWD, tiling=tiles(FWD, m, k, n, lhs.dtype),
             transpose_rhs=False, interpret=interpret),
        interpret,
    )


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, meta, interpret):
    return _forward(lhs, rhs, meta, interpret)


def _grouped_matmul_fwd(lhs, rhs, meta, interpret):
    return _forward(lhs, rhs, meta, interpret), (lhs, rhs, meta)


def _grouped_matmul_bwd(interpret, res, grad):
    lhs, rhs, meta = res
    (m, k), n = lhs.shape, rhs.shape[2]
    d_lhs = _gmm(grad, rhs, meta, form=DLHS, tiling=tiles(DLHS, m, k, n, lhs.dtype),
                 transpose_rhs=True, interpret=interpret)
    d_rhs = _tgmm(lhs, grad, meta, tiling=tiles(DRHS, m, k, n, lhs.dtype),
                  groups=rhs.shape[0], out_dtype=rhs.dtype, interpret=interpret)
    return *_settled((d_lhs, d_rhs), interpret), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(
    lhs: jax.Array,
    rhs: jax.Array,
    group_sizes: jax.Array,
    *,
    interpret: InterpretArg = None,
) -> jax.Array:
    """``(m, k) x (E, k, n) -> (m, n)``, row ``i`` against the matrix of
    the group that holds it: ``group_sizes`` (E,) counts the rows of each
    group, in order, and sums to ``m``.  float32 or bfloat16;
    differentiable in ``lhs`` and ``rhs``."""
    operands = (lhs, rhs, group_sizes.astype(jnp.int32))
    # inside a shard_map: every operand varying over the same axes, as a
    # dot's would be made (the weights' gradient is then summed over the
    # axes the weights do not vary over by that cast's transpose)
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    lhs, rhs, group_sizes = (
        lax.pcast(o, missing, to="varying")
        if (missing := tuple(vma - jax.typeof(o).vma)) else o
        for o in operands
    )
    interpret = default_interpret(interpret)
    with _interpreter_check_off(interpret, vma):
        m = lhs.shape[0]
        meta = group_metadata(group_sizes, m=m, tm=block_rows(m, ROWS))
    return _grouped_matmul(lhs, rhs, meta, interpret)
