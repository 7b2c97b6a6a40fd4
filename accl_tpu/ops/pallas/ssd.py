"""The Mamba-2 core (``ops/ssd.py``: the selective state-space recurrence
with a scalar decay a head, in chunks) as two Mosaic kernels, ``ssd_fwd``
and ``ssd_bwd``, that keep a chunk's decay squares and the running state
in VMEM.

``ops.ssd.ssd_mixer`` picks them from the shapes (:func:`takes`); the XLA
form there stays the oracle they are tested against and what every other
shape runs.

THE GRID.  ``(B, groups, chunks)``, the chunk axis ``arbitrary``: a grid
step is ONE chunk of :data:`CHUNK` tokens of ONE group's heads, with the
group's running state (held TRANSPOSED and side by side, ``(N, W)`` for the
``W = P H / G`` columns of the group's heads: a group-wide product then
reads or writes every head's state at once) in a VMEM scratch that lives
across a group-sequence's chunks.  Operands are TOKEN-MAJOR, as the mixer's
chains leave them: ``x`` and ``y`` ``(B, T, H P)``, a group's ``W`` columns a
block, ``B`` and ``C`` ``(B, T, G N)``; the per-head scalars ``dt`` and the
chunk's cumulative log-decay ``l`` come twice, tokens on lanes (``(B, G,
H / G, T)``: what scales a square's columns) and tokens on sublanes (``(B,
G, T, H / G)``: what scales its rows).

A CHUNK.  ``C B^T`` once a group.  The two products that touch the state
are the group's: ``C S`` for every head is one ``(128, N) @ (N, W)``, what
the chunk writes one ``B^T @ (x dt w)`` into ``(N, W)``.  Only ``(C B^T .
decay_h) (x dt)_h`` is a head's: its decay square ``exp(l_t - l_s)`` is made
in VMEM from the chunk's 128 cumulative log-decays and never stored.  Heads
narrower than a lane row share a 128-column SLAB of ``x``: a head's product
is taken against the whole slab and its own columns kept (a pass streams
128 rows whatever the width).  A per-token factor of a group-wide array is
SPREAD: the head's column broadcast over its ``P`` lanes.

WHAT MOVES.  Forward: x, B, C, dt, l in, y out and, under differentiation,
the state each chunk STARTS from (``(N, W)`` float32 a chunk a group).
Backward (hand-derived, a reverse pass over the chunks carrying ``dS``): the
same inputs, ``dy`` and the saved states in; ``dx``, ``dB``, ``dC``, the
direct ``d dt``, ``dl`` and ``dD`` (a row of ``W`` column sums that stays in
VMEM across a group-sequence, summed outside) out; every other chunk-local
quantity is rebuilt.  ``dl``, PAIRED as autodiff of the XLA form pairs it:
``l`` enters only as differences, so whatever ``l_t`` gains as a reader some
``l_s`` loses as a writer, and a later sum over tokens (the ``cumsum``'s
transpose) cancels the two only if both are the SAME rounded number.
Inside a chunk both are sums of one square a head, ``d(l_t - l_s) = (dy_t .
u_s) (C_t . B_s) exp(l_t - l_s)`` with ``u = x dt``, along its rows (kept as
columns, a second output, turned outside) and along its columns; through
the state they are token-wise dot products over a head's ``P`` lanes, what
a token reads of the start state less what the later chunks read of its
write, ``<u_s, du_s>``, which ``l_C`` gets back term by term beside ``<dS',
exp(l_C) S>``; the direct ``d dt_s`` is ``<x_s, du_s>``.  Those sums over a
head's lanes are ONE product with a 0/1 matrix of the arrays' three
bfloat16 parts (exact products, float32 sums).  ``l``'s cumulative sum,
``A`` and their cotangents stay outside, in XLA, as the XLA form has them
(an exact float32 ``cumsum`` a chunk).

PRECISION, product by product what the XLA form computes on the chip: its
``einsum``s run at the default precision for float32, ONE bfloat16 pass with
float32 accumulation, so every product here is :func:`_mm` (explicit casts,
``preferred_element_type`` float32), the backward's too.  Everything
elementwise, the state and the exponentials are float32.

EXPONENTS.  As the XLA form: every one is a difference ``l_t - l_s`` with
``s <= t`` (or ``l_C - l_s``, or ``l_t`` itself), at most 0; the other half
of the square is set to ``-inf`` BEFORE the exponential.
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
from .kda import _NT, _TN, _mm, _tri_sum

FWD, BWD = "ssd_fwd", "ssd_bwd"
#: tokens a chunk (``ops.ssd``'s: the published ``chunk_size``)
CHUNK = 128

_f32, _bf16 = jnp.float32, jnp.bfloat16
#: what a default-precision product rounds its operands to, read at each
#: call (the tests of the mathematics set float32: a CPU's XLA form rounds
#: nothing)
_ONE_PASS = _bf16


def takes(x_shape, b_shape, heads: int, groups: int, chunk: int = CHUNK) -> bool:
    """The shape rule, from token-major ``x`` (B, T, H P) and ``b`` (B, T,
    G N): this module's chunk, a state of whole lanes, a head that divides
    a lane row, a group's heads filling whole lane rows.  Any other shape
    is the XLA form's."""
    if heads % groups or x_shape[-1] % heads or b_shape[-1] % groups:
        return False
    P, N = x_shape[-1] // heads, b_shape[-1] // groups
    return (
        chunk == CHUNK and N % LANES == 0 and LANES % P == 0
        and (P * (heads // groups)) % LANES == 0
    )


def _column(cols, h):
    """Head ``h``'s column of ``cols`` (CHUNK, heads) over a lane row."""
    return jnp.broadcast_to(cols[:, h:h + 1], (CHUNK, LANES))


def _own(lane, i, P, mine, rest):
    """A slab whose lanes from head ``i``'s on are ``mine``'s (the heads of
    a slab are folded in ascending order, so every later head overwrites
    its own)."""
    return mine if rest is None else jnp.where(lane >= i * P, mine, rest)


def _spread(lane, columns, P):
    """``(CHUNK, W)`` from the heads' lane-row columns: head ``h``'s over its
    own ``P`` lanes."""
    q, slabs = LANES // P, []
    for j in range(len(columns) // q):
        slab = None
        for i in range(q):
            slab = _own(lane, i, P, columns[j * q + i], slab)
        slabs.append(slab)
    return slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, axis=1)


def _local(mm, P, x, bm, cm, lc, dc, lr):
    """What a chunk makes without the state, from its rows ``x`` (CHUNK,
    W), ``bm`` and ``cm`` (CHUNK, N) and its heads' ``l`` and ``dt`` as
    columns (CHUNK, heads) and ``l`` as rows (heads, CHUNK): a dict of the
    module docstring's names."""
    per = lc.shape[1]
    t = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    s = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    lane = lax.broadcasted_iota(jnp.int32, (CHUNK, LANES), 1)
    l_cols = [_column(lc, h) for h in range(per)]
    # a head's decay square, the half above the diagonal 0
    decays = [
        jnp.exp(jnp.where(s <= t, l_cols[h] - lr[h:h + 1], -jnp.inf))
        for h in range(per)
    ]
    sl = _spread(lane, l_cols, P)
    sd = _spread(lane, [_column(dc, h) for h in range(per)], P)
    end = sl[CHUNK - 1:]                              # l_C, (1, W)
    return dict(
        cb=mm(cm, bm, _NT), decays=decays, sd=sd, u=x * sd, el=jnp.exp(sl),
        wp=jnp.exp(end - sl), keep=jnp.exp(end), lane=lane,
    )


def _slab(x, j):
    return x[:, j * LANES:(j + 1) * LANES]


def _fwd_kernel(save, mm, P):
    def kernel(x_ref, b_ref, c_ref, lc_ref, dc_ref, lr_ref, d_ref, y_ref, *rest):
        s_ref, state_ref = rest if save else (None, *rest)

        @pl.when(pl.program_id(2) == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
        loc = _local(mm, P, x, bm, cm, lc_ref[0, 0], dc_ref[0, 0], lr_ref[0, 0])
        state = state_ref[...]
        if save:
            s_ref[0, 0, 0] = state
        u, q = loc["u"], LANES // P
        before = mm(cm, state) * loc["el"] + x * d_ref[0]
        for j in range(x.shape[1] // LANES):
            u_j, within = _slab(u, j), None
            for i in range(q):
                h = j * q + i
                within = _own(
                    loc["lane"], i, P, mm(loc["cb"] * loc["decays"][h], u_j), within
                )
            y_ref[0, :, j * LANES:(j + 1) * LANES] = _slab(before, j) + within
        state_ref[...] = state * loc["keep"] + mm(bm, u * loc["wp"], _TN)

    return kernel


def _segment_sums(z, P, rows):
    """``(rows, z's rows)``: row ``h`` the sums of ``z`` (., W) over head
    ``h``'s ``P`` lanes, exact products (``z`` as its three bfloat16
    parts against a 0/1 matrix) and float32 sums."""
    W = z.shape[1]
    head = lax.broadcasted_iota(jnp.int32, (rows, W), 0)
    lane = lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    return _tri_sum(jnp.where(lane // P == head, 1.0, 0.0).astype(_bf16), z, _NT)


def _bwd_kernel(mm, P):
    def kernel(x_ref, b_ref, c_ref, lc_ref, dc_ref, lr_ref, d_ref, dy_ref, s_ref,
               dx_ref, db_ref, dc_out_ref, ddt_ref, dl_ref, dl_cols_ref, dd_ref,
               d_state_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            d_state_ref[...] = jnp.zeros_like(d_state_ref)
            dd_ref[...] = jnp.zeros_like(dd_ref)

        x, bm, cm, dy = x_ref[0], b_ref[0], c_ref[0], dy_ref[0]
        per = lc_ref.shape[-1]
        loc = _local(mm, P, x, bm, cm, lc_ref[0, 0], dc_ref[0, 0], lr_ref[0, 0])
        start, d_state = s_ref[0, 0, 0], d_state_ref[...]
        u, cb, lane, q = loc["u"], loc["cb"], loc["lane"], LANES // P

        # du: what the later chunks read of this chunk's writes, and what
        # this chunk's own tokens read; dCB summed over the group's heads;
        # a head's square of log-decay cotangents summed along both axes
        later = mm(bm, d_state) * loc["wp"]
        du, d_cb = [], None
        head_row = lax.broadcasted_iota(jnp.int32, (per, CHUNK), 0)
        head_col = lax.broadcasted_iota(jnp.int32, (CHUNK, per), 1)
        read = jnp.zeros((CHUNK, per), _f32)      # of l_t, as a reader's
        wrote = jnp.zeros((per, CHUNK), _f32)     # of l_s, as a writer's
        for j in range(x.shape[1] // LANES):
            u_j, dy_j, own = _slab(u, j), _slab(dy, j), None
            for i in range(q):
                h = j * q + i
                own = _own(lane, i, P, mm(cb * loc["decays"][h], dy_j, _TN), own)
                mine = dy_j if q == 1 else jnp.where(
                    (lane >= i * P) & (lane < (i + 1) * P), dy_j, 0.0
                )
                term = mm(mine, u_j, _NT) * loc["decays"][h]
                d_cb = term if d_cb is None else d_cb + term
                of_pair = term * cb               # d(l_t - l_s), masked
                read = jnp.where(
                    head_col == h, jnp.sum(of_pair, axis=1, keepdims=True), read
                )
                wrote = jnp.where(
                    head_row == h, jnp.sum(of_pair, axis=0, keepdims=True), wrote
                )
            du.append(_slab(later, j) + own)
        du = du[0] if len(du) == 1 else jnp.concatenate(du, axis=1)

        dy_el, u_wp = dy * loc["el"], u * loc["wp"]
        dx_ref[0] = du * loc["sd"] + dy * d_ref[0]
        dc_out_ref[0] = mm(d_cb, bm) + mm(dy_el, start, _NT)
        db_ref[0] = mm(d_cb, cm, _TN) + mm(u_wp, d_state, _NT)
        dd_ref[0, 0] += jnp.sum(dy * x, axis=0, keepdims=True)

        # the token-wise dot products over a head's lanes, in one product:
        # the state's part of dl (what a token reads of the start state,
        # less what the later chunks read of its write; l_C gets the writes
        # back, every one the value its token lost, and <dS', keep S>)
        # above the direct d dt
        lost = u * later
        of_end = jnp.sum(lost + d_state * loc["keep"] * start, axis=0, keepdims=True)
        last = lax.broadcasted_iota(jnp.int32, x.shape, 0) == CHUNK - 1
        of_l = dy * (mm(cm, start) * loc["el"]) - lost + jnp.where(last, of_end, 0.0)
        sums = _segment_sums(
            jnp.concatenate([of_l, x * du], axis=0), P, -(-per // 16) * 16
        )
        dl_ref[0, 0] = sums[:per, :CHUNK] - wrote
        dl_cols_ref[0, 0] = read
        ddt_ref[0, 0] = sums[:per, CHUNK:]

        d_state_ref[...] = d_state * loc["keep"] + mm(cm, dy_el, _TN)

    return kernel


def _specs(N, W, per, chunks, backward):
    """Block specs of a group's chunk: the token-major rows (``W`` or ``N``
    columns), the heads' scalars as columns and as rows, ``D`` spread over
    the group's columns, the chunk's start state; the backward visits the
    chunks in reverse."""
    at = (lambda s: chunks - 1 - s) if backward else (lambda s: s)
    rows = lambda width: pl.BlockSpec(
        (1, CHUNK, width), lambda b, g, s: (b, at(s), g)
    )
    columns = pl.BlockSpec((1, 1, CHUNK, per), lambda b, g, s: (b, g, at(s), 0))
    lanes = pl.BlockSpec((1, 1, per, CHUNK), lambda b, g, s: (b, g, 0, at(s)))
    skip = pl.BlockSpec((1, 1, W), lambda b, g, s: (g, 0, 0))
    states = pl.BlockSpec((1, 1, 1, N, W), lambda b, g, s: (b, g, at(s), 0, 0))
    return rows, columns, lanes, skip, states


def _params(N, W, arrays):
    """Double-buffered row blocks and states, the scratch, and room for
    what a chunk keeps live between its products."""
    block = CHUNK * W * 4
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=2 * (arrays * block + N * W * 4) + N * W * 4 + 24 * block
        + (16 << 20),
    )


# jitted, so that a program's layers share one trace and one lowered
# function a shape
@partial(jax.jit, static_argnames=("save", "interpret", "one_pass"))
def _forward(x, b, c, l_cols, dt_cols, l_rows, d_row, *, save, interpret, one_pass):
    B, T, _ = x.shape
    _, G, per, _ = l_rows.shape
    N, W, chunks = b.shape[-1] // G, x.shape[-1] // G, T // CHUNK
    rows, columns, lanes, skip, states = _specs(N, W, per, chunks, backward=False)
    operands = (x, b, c, l_cols, dt_cols, l_rows, d_row)
    out_shape, out_specs = [out_struct(x.shape, _f32, *operands)], [rows(W)]
    if save:
        out_shape.append(out_struct((B, G, chunks, N, W), _f32, *operands))
        out_specs.append(states)
    call = pl.pallas_call(
        _fwd_kernel(save, partial(_mm, one_pass=one_pass), W // per),
        grid=(B, G, chunks),
        out_shape=out_shape,
        in_specs=[rows(W), rows(N), rows(N), columns, columns, lanes, skip],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((N, W), _f32)],
        compiler_params=_params(N, W, 2),
        interpret=interpret,
        name=FWD,
    )
    return _run(call, interpret, *operands)


@partial(jax.jit, static_argnames=("interpret", "one_pass"))
def _backward(x, b, c, l_cols, dt_cols, l_rows, d_row, dy, starts,
              *, interpret, one_pass):
    B, T, _ = x.shape
    _, G, per, _ = l_rows.shape
    N, W, chunks = b.shape[-1] // G, x.shape[-1] // G, T // CHUNK
    rows, columns, lanes, skip, states = _specs(N, W, per, chunks, backward=True)
    operands = (x, b, c, l_cols, dt_cols, l_rows, d_row, dy, starts)
    struct = lambda shape: out_struct(shape, _f32, *operands)
    call = pl.pallas_call(
        _bwd_kernel(partial(_mm, one_pass=one_pass), W // per),
        grid=(B, G, chunks),
        out_shape=[
            struct(x.shape), struct(b.shape), struct(c.shape),
            struct(l_rows.shape), struct(l_rows.shape), struct(l_cols.shape),
            struct((B, G, 1, W)),
        ],
        in_specs=[rows(W), rows(N), rows(N), columns, columns, lanes, skip,
                  rows(W), states],
        out_specs=[
            rows(W), rows(N), rows(N), lanes, lanes, columns,
            pl.BlockSpec((1, 1, 1, W), lambda b, g, s: (b, g, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((N, W), _f32)],
        compiler_params=_params(N, W, 3),
        interpret=interpret,
        name=BWD,
    )
    return _run(call, interpret, *operands)


def _packed(x, b, c, dt, l, d, groups):
    """The kernels' operands from the token-major rows, ``dt`` and ``l``
    (B, H, T) and ``D`` (H,): the heads' scalars as rows and as columns a
    group, ``D`` spread over its head's columns."""
    B, H, T = dt.shape
    per, P = H // groups, x.shape[-1] // H
    rows = lambda v: v.astype(_f32).reshape(B, groups, per, T)
    columns = lambda v: rows(v).transpose(0, 1, 3, 2)
    d_row = jnp.repeat(d.astype(_f32), P).reshape(groups, 1, per * P)
    x, b, c = (v.astype(_f32) for v in (x, b, c))
    return x, b, c, columns(l), columns(dt), rows(l), d_row


def _apply(x, b, c, dt, l, d, how, save):
    """``(y, the chunks' start states or ())``; ``how``: ``(interpret,
    one_pass, groups)``."""
    interpret, one_pass, groups = how
    y, *saved = _forward(
        *_packed(x, b, c, dt, l, d, groups),
        save=save, interpret=interpret, one_pass=one_pass,
    )
    return _settled(y, interpret), tuple(saved)


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, b, c, dt, l, d, how):
    return _apply(x, b, c, dt, l, d, how, save=False)[0]


def _ssd_fwd(x, b, c, dt, l, d, how):
    y, (starts,) = _apply(x, b, c, dt, l, d, how, save=True)
    return y, (x, b, c, dt, l, d, starts)


def _ssd_bwd(how, res, dy):
    interpret, one_pass, groups = how
    *inputs, starts = res
    dx, db, dc, ddt, dl, dl_cols, dd = _backward(
        *_packed(*inputs, groups), dy.astype(_f32), starts,
        interpret=interpret, one_pass=one_pass,
    )
    B, H, T = inputs[3].shape
    dl = dl + dl_cols.transpose(0, 1, 3, 2)       # the readers' side to rows
    grads = (
        dx, db, dc, ddt.reshape(B, H, T), dl.reshape(B, H, T),
        dd.reshape(B, H, -1).sum((0, 2)),
    )
    return tuple(
        _settled(g.astype(p.dtype), interpret) for g, p in zip(grads, inputs)
    )


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, b, c, dt, l, d, groups: int, *, interpret: InterpretArg = None):
    """The chunked recurrence of ``ops/ssd.py`` by the kernels, from
    token-major ``x`` (B, T, H P), ``b`` and ``c`` (B, T, G N), ``dt`` (B,
    H, T), its cumulative log-decay ``l`` inside each chunk (B, H, T) and
    ``d`` (H,), :func:`takes` their shapes and T whole chunks; ``y`` (B, T,
    H P) float32.  Differentiable by all six."""
    _, operands = vary_together(x, b, c, dt, l, d)
    return _ssd(*operands, (default_interpret(interpret), _ONE_PASS, groups))
