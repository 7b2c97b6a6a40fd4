"""The KDA core (``ops/kda.py``: the chunked gated delta rule with a decay
a channel) as two Mosaic kernels, ``kda_fwd`` and ``kda_bwd``, that keep
what a chunk makes in VMEM.

``ops.kda.kda_chunked`` picks them from the shapes (:func:`takes`: heads
whose ``dk`` and ``dv`` are whole lanes); the XLA form there stays the
oracle they are tested against and what every other shape runs.

THE GRID.  ``(head-sequences, steps)``, the step axis ``arbitrary``: a
grid step holds :data:`BLOCKS` BLOCKS of one head-sequence, a block two
chunks of :data:`CHUNK` tokens (:data:`ROWS` = 128 rows), and walks them
in a loop with the running state ``S`` (held TRANSPOSED, ``(dv, dk)``, so
that a channel's decay scales a lane) in a VMEM scratch that lives across
the steps of a head-sequence.  Everything a chunk makes that does not
wait for ``S`` is made for the two chunks of a block AT ONCE as
block-diagonal ``(128, 128)`` matrices (``A``, ``P``, ``(I + A)^-1``) and
``(128, dk)`` row arrays (``W``, ``U~``, ``K^``, ``Gamma Q``): a product
then fills the MXU's 128 x 128 where a chunk alone would fill a quarter,
at the same count of passes.  Only three products a chunk wait for ``S``
(``U = U~ - W S``, ``S' = diag S + K^^T U``, ``(Gamma Q) S``); the
backward's chain is two (``dU += K^ dS'``, ``dS = ... - W^T dU``).

WHAT MOVES.  Forward: q, k, v, g, beta in, ``o`` out and, under
differentiation, the state each chunk STARTS from (``(dv, dk)`` float32 a
chunk) and each block's inverse (``(128, 128)`` float32: the one thing of
a block that costs ``highest`` products to rebuild).  Backward
(hand-derived, a reverse pass over the blocks carrying ``dS``): the same
inputs, ``do`` and what the forward saved in, the five gradients out;
every other chunk-local quantity is rebuilt from the inputs (with the
saved states nothing of the rebuild waits for the chain).

PRECISION, product by product what the XLA form computes: its ``@`` and
``einsum`` run at the TPU's default precision for float32, ONE bfloat16 pass
with float32 accumulation (operands rounded to bfloat16), so every such
product here is :func:`_mm` (explicit casts, ``preferred_element_type``
float32), the backward's too (autodiff's products of the XLA form round their
cotangents the same way); the inverse's products and its cotangent's two are
:func:`_mm_hi` (``highest``: full float32; the inverse's levels 2 and 4
float32 on the VPU); the cumulative log-decay is a product with a 0/1 triangle
of the three bfloat16 parts of ``g`` (exact products, float32 sums: a float32
cumsum up to the order of the additions), its cotangent the same with the
transposed triangle.  Everything elementwise is float32.

EXPONENTS.  As the XLA form: sub-blocks of :data:`SUB` tokens, a ROW
sub-block's exponents all relative to its own middle token ``m``: a row
carries ``exp(G_t - m)`` (within ``e^+-40`` at the gate's bound of -5), a
key column ``exp(m - G_j)`` (at most ``e^40``, and at most 1 for every
earlier sub-block; what underflows there is below ``e^-47`` beside terms
of order 1).  The XLA form splits blocks below the diagonal at the row
block's START instead; both are exact up to rounding.  ``exp(G_t - m)
exp(m - G_j)`` does not depend on ``m``, so ``m``'s cotangent is zero but
for the products' rounding; the backward leaves that rounding on the
middle token as autodiff of the XLA form does, which cancels it out of
``dg`` for the tokens before (measured: ``dg`` is then as near the
``highest`` truth as the XLA form's, 1.0% rms, and 2.7% without).

ANY ``g <= 0`` (``safe=True``, the unbounded gate; ``ops/kda.py``, ANY ``g <=
0``).  The split at a middle token overflows, so ``A`` and ``P`` are the XLA
form's split by HALVING, for the block's two chunks at once: the segment sums
``D_h`` and ``E_h`` of every level from ``g`` by a doubling recursion of
sublane rolls (:func:`_segments`; plain sums of ``g``, its transpose the
cotangent's, :func:`_segments_bwd`), six masked one-pass products of ``(2
ROWS, dk) x (dk, ROWS)`` and the diagonal's (:func:`_local_safe`), every
factor at most 1; ``Gamma`` and ``Gamma_C / Gamma`` are the top level's; the
inverse goes up the same levels with no power of ``A``
(:func:`_inverse_by_halving`, BOTH gates').  The backward
(:func:`_block_bwd_safe`) shares everything that waits for the state with the
bounded one (:func:`_state_bwd`) and takes each level's two cotangent
products; no exponent is a difference, so no cotangent of ``g`` is two large
terms that cancel.  ``safe`` is static.
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

FWD, BWD = "kda_fwd", "kda_bwd"
#: tokens a chunk and a sub-block (``ops.kda``'s), rows a block (two
#: chunks side by side on the MXU), blocks a grid step
CHUNK, SUB = 64, 16
ROWS = 2 * CHUNK
BLOCKS = 4

#: how much wider a head may get when ``ops.kda`` pads it with zero columns
#: up to whole lanes for these kernels (a decay a head: keys of 96 to 128,
#: values of 192 to 256, a third more); anything narrower is the XLA form's
PAD = 1.5

_f32, _bf16 = jnp.float32, jnp.bfloat16
#: what a default-precision product rounds its operands to, read at each
#: call (the tests of the mathematics set float32: a CPU's XLA form
#: rounds nothing)
_ONE_PASS = _bf16
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def takes(q_shape, v_shape, chunk: int = CHUNK, sub: int = SUB) -> bool:
    """The shape rule: the kernels take heads whose ``dk`` and ``dv`` are
    whole lanes at this module's chunk and sub-block; any other shape is
    the XLA form's."""
    return (
        (chunk, sub) == (CHUNK, SUB)
        and q_shape[-1] % LANES == 0 and v_shape[-1] % LANES == 0
    )


def takes_padded(dk: int, dv: int, chunk: int = CHUNK, sub: int = SUB) -> bool:
    """:func:`takes` for heads that zero columns bring up to whole lanes:
    each width at most :data:`PAD` times wider for it."""
    up = lambda d: -(-d // LANES) * LANES
    return (chunk, sub) == (CHUNK, SUB) and all(
        up(d) <= PAD * d for d in (dk, dv)
    )


def _mm(a, b, dims=_NN, *, one_pass):
    """A product at the XLA form's default precision on the chip: one
    pass of operands rounded to ``one_pass`` (bfloat16), float32 sums."""
    return lax.dot_general(
        a.astype(one_pass), b.astype(one_pass), (dims, ((), ())),
        preferred_element_type=_f32,
    )


def _parts(x):
    """``x`` as its three bfloat16 parts, largest first: their sum is the
    float32 exactly."""
    out = []
    for _ in range(3):
        out.append(x.astype(_bf16))
        x = x - out[-1].astype(_f32)
    return out


def _mm_hi(a, b):
    """A product at full float32 (``highest``)."""
    return lax.dot_general(
        a, b, (_NN, ((), ())), precision=lax.Precision.HIGHEST,
        preferred_element_type=_f32,
    )


def _tri_sum(tri, x, dims):
    """``tri @ x`` (``tri.T @ x`` under ``_TN``) for a 0/1 ``tri`` in
    bfloat16, exact products: ``x`` goes through as its three parts."""
    return sum(
        lax.dot_general(tri, part, (dims, ((), ())), preferred_element_type=_f32)
        for part in reversed(_parts(x))
    )


def _rows(pieces):
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)


def _halves(x):
    return [x[h * CHUNK:(h + 1) * CHUNK] for h in range(ROWS // CHUNK)]


def _pair_masks(row, col):
    """The levels' pair masks, lowest first: ``t xor j``'s highest bit is a pair's level."""
    differ = row ^ col
    return [(differ >> i == 1) & (col < row) for i in range(CHUNK.bit_length() - 1)]


def _inverse_by_halving(a, masks, row, col):
    """``ops.kda._unit_lower_inverse_by_halving`` for the block's two
    chunks, ``inv <- inv - inv a_h inv`` up the levels of ``masks``, nothing
    multiplied that is zero: ``a_h`` (level ``h``'s pairs of ``a``) and the
    correction live in the rows whose bit ``h`` is set, ``inv`` in diagonal
    blocks of ``h``.  Under 8 (rows inside a sublane tile; level 1 comes to
    ``I - a_1``) in float32 on the VPU: ``a_h``'s rows rolled down times
    ``inv``'s subdiagonals as columns, then lanes rolled left times them as
    rows.  From 8 up those rows, whole tiles, go ALONE through two ``highest``
    products: 384 rows a block where whole levels were 1,536."""
    inv = jnp.where(row == col, 1.0, 0.0)
    for i, mask in enumerate(masks):
        h, a_h = 1 << i, jnp.where(mask, a, 0.0)
        if h < 8:
            diags = [jnp.where(col == row - s, inv, 0.0) for s in range(1, h)]
            left = sum((jnp.sum(d, axis=1, keepdims=True) * pltpu.roll(a_h, s, 0)
                        for s, d in enumerate(diags, 1)), a_h)
            inv = inv - sum((pltpu.roll(left, ROWS - s, 1) * jnp.sum(d, axis=0, keepdims=True)
                             for s, d in enumerate(diags, 1)), left)
            continue
        pieces = [inv[s:s + h] for s in range(0, ROWS, h)]   # odd ones: bit h set
        lower = _rows(pieces[1::2])
        lower = lower - _mm_hi(_mm_hi(lower, a_h), inv)
        pieces[1::2] = [lower[s:s + h] for s in range(0, ROWS // 2, h)]
        inv = _rows(pieces)
    return inv


def _local(mm, q, k, v, g, brow, T=None):
    """What a block makes without the state, from its ``(ROWS, .)`` rows
    and its ``(1, ROWS)`` beta: a dict of the module docstring's names.
    ``mm`` is the default-precision product; ``T`` the inverse, where the
    forward saved it."""
    dk = q.shape[-1]
    row = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    col = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    same = (row // CHUNK) == (col // CHUNK)
    upto, below = same & (col <= row), same & (col < row)
    eye = row == col
    tri = jnp.where(upto, 1.0, 0.0).astype(_bf16)

    G = _tri_sum(tri, g, _NN)                        # summed inside a chunk
    mids = [
        G[SUB * a + SUB // 2 - 1: SUB * a + SUB // 2] for a in range(ROWS // SUB)
    ]
    mid = _rows([jnp.broadcast_to(m, (SUB, dk)) for m in mids])
    ends = [x[CHUNK - 1:] for x in _halves(G)]       # (1, dk) a chunk
    end = _rows([jnp.broadcast_to(e, (CHUNK, dk)) for e in ends])

    up = jnp.exp(G - mid)
    q_up, k_up = q * up, k * up
    downs, lhs, p0, a0 = [], [], [], []
    for a, m in enumerate(mids):
        # the key columns a row sub-block can see: its chunk's, up to its own
        lo, hi = CHUNK * (SUB * a // CHUNK), SUB * (a + 1)
        rows = slice(SUB * a, SUB * a + SUB)
        down = _rows(
            ([jnp.zeros((lo, dk), _f32)] if lo else [])
            + [jnp.exp(m - G[lo:hi])]
            + ([jnp.zeros((ROWS - hi, dk), _f32)] if hi < ROWS else [])
        )
        x = jnp.concatenate([q_up[rows], k_up[rows]], axis=0)
        out = mm(x, k * down, _NT)                  # (2 SUB, ROWS)
        downs.append(down)
        lhs.append(x)
        p0.append(out[:SUB])
        a0.append(out[SUB:])
    P0, A0 = _rows(p0), _rows(a0)
    Pm = jnp.where(upto, P0, 0.0) * brow
    if T is None:
        T = _inverse_by_halving(A0 * brow, _pair_masks(row, col), row, col)

    bcol = jnp.sum(
        jnp.where(eye, jnp.broadcast_to(brow, (ROWS, ROWS)), 0.0), axis=1, keepdims=True
    )
    eG, eD = jnp.exp(G), jnp.exp(end - G)
    k_gam = k * eG
    return dict(
        upto=upto, below=below, eye=eye, tri=tri, up=up, downs=downs, lhs=lhs,
        P0=P0, A0=A0, Pm=Pm, T=T, eG=eG, eD=eD, bcol=bcol, k_gam=k_gam,
        q_gam=q * eG, W=mm(T, k_gam), U0=mm(T, v), k_hat=k * eD * bcol,
        keeps=[jnp.exp(e) for e in ends],
    )


def _segments(g):
    """``[(D_h, E_h) for h in 1, 2, .., CHUNK]`` of a block's ``g`` (ROWS,
    dk): a token's ``g`` summed from its ``h``-segment's first token to
    itself, and from the next token to its segment's last.  Doubling: a
    ``2h``-segment's lower half adds the upper half's total, which stands
    ``h`` rows up (any row's ``D_h + E_h`` is its segment's total); the
    upper half adds the lower's, ``h`` rows down."""
    at = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    D, E = g, jnp.zeros_like(g)
    out, h = [(D, E)], 1
    while h < CHUNK:
        total, lower = D + E, (at & h) != 0
        D = D + jnp.where(lower, pltpu.roll(total, h, 0), 0.0)
        E = E + jnp.where(lower, 0.0, pltpu.roll(total, ROWS - h, 0))
        out.append((D, E))
        h *= 2
    return out


def _segments_bwd(dD, dE):
    """The cotangent of ``g`` from those of :func:`_segments`' results
    (two lists, a level each): the recursion transposed, the top level
    first."""
    at = lax.broadcasted_iota(jnp.int32, dD[0].shape, 0)
    cD, cE, h = dD[-1], dE[-1], CHUNK // 2
    for i in reversed(range(len(dD) - 1)):
        lower = (at & h) != 0
        d_total = pltpu.roll(jnp.where(lower, cD, 0.0), ROWS - h, 0) + pltpu.roll(
            jnp.where(lower, 0.0, cE), h, 0
        )
        cD, cE, h = cD + d_total + dD[i], cE + d_total + dE[i], h // 2
    return cD


def _local_safe(mm, q, k, v, g, brow, T=None):
    """:func:`_local` for any ``g <= 0`` (module docstring, ANY ``g <=
    0``): the same names but the middle token's (``up``, ``downs``,
    ``lhs``), and ``levels``: a level's ``(mask, rows, key columns,
    exp(D_h), exp(E_h))``."""
    row = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
    col = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
    same = (row // CHUNK) == (col // CHUNK)
    upto, below = same & (col <= row), same & (col < row)
    eye = row == col
    masks = _pair_masks(row, col)

    *halvings, (G, to_end) = _segments(g)
    P0 = jnp.where(eye, mm(q, k, _NT), 0.0)
    A0 = jnp.zeros_like(P0)
    levels = []
    for mask, (D, E) in zip(masks, halvings):
        eDh, eEh = jnp.exp(D), jnp.exp(E)
        x, y = jnp.concatenate([q * eDh, k * eDh], axis=0), k * eEh
        out = mm(x, y, _NT)                          # (2 ROWS, ROWS)
        P0 = P0 + jnp.where(mask, out[:ROWS], 0.0)
        A0 = A0 + jnp.where(mask, out[ROWS:], 0.0)
        levels.append((mask, x, y, eDh, eEh))
    Pm = P0 * brow
    if T is None:
        T = _inverse_by_halving(A0 * brow, masks, row, col)

    bcol = jnp.sum(
        jnp.where(eye, jnp.broadcast_to(brow, (ROWS, ROWS)), 0.0), axis=1, keepdims=True
    )
    eG, eD = jnp.exp(G), jnp.exp(to_end)
    k_gam = k * eG
    return dict(
        upto=upto, below=below, eye=eye, levels=levels,
        P0=P0, A0=A0, Pm=Pm, T=T, eG=eG, eD=eD, bcol=bcol, k_gam=k_gam,
        q_gam=q * eG, W=mm(T, k_gam), U0=mm(T, v), k_hat=k * eD * bcol,
        keeps=[x[CHUNK - 1:] for x in _halves(eG)],
    )


def _block_fwd(mm, q, k, v, g, brow, state, local=_local):
    """One block forward: ``(o, the state after it, the states its two
    chunks started from, the block's inverse)``; ``state`` is ``S^T`` (dv,
    dk)."""
    loc = local(mm, q, k, v, g, brow)
    starts, u, read = [], [], []
    for W, U0, q_gam, k_hat, keep in zip(
        *(_halves(loc[n]) for n in ("W", "U0", "q_gam", "k_hat")), loc["keeps"]
    ):
        starts.append(state)
        u.append(U0 - mm(W, state, _NT))
        read.append(mm(q_gam, state, _NT))
        state = state * keep + mm(u[-1], k_hat, _TN)
    o = _rows(read) + mm(loc["Pm"], _rows(u))
    return o, state, starts, loc["T"]


def _state_bwd(mm, loc, v, brow, do, starts, d_state):
    """What both backward passes share: the chain through the chunks'
    states backwards, and every cotangent it leaves of the block's own
    quantities, as a dict (``d_state``: of the state before the block)."""
    T = loc["T"]
    Pm = loc["Pm"]
    W, U0, q_gam, k_hat, do_h = (
        _halves(x) for x in (loc["W"], loc["U0"], loc["q_gam"], loc["k_hat"], do)
    )
    u = [u0 - mm(w, s, _NT) for w, u0, s in zip(W, U0, starts)]
    U = _rows(u)

    # the chain: the chunks backwards
    dU_p = _halves(mm(Pm, do, _TN))
    dU, d_after = [None] * len(starts), [None] * len(starts)
    for h in reversed(range(len(starts))):
        d_after[h] = d_state
        dU[h] = dU_p[h] + mm(k_hat[h], d_state, _NT)
        d_state = d_state * loc["keeps"][h] + mm(
            jnp.concatenate([do_h[h], -dU[h]], axis=0),
            jnp.concatenate([q_gam[h], W[h]], axis=0), _TN,
        )

    # what the chain leaves: every cotangent of the block's own quantities
    dq_gam, dW, dk_hat, d_end = [], [], [], []
    for h, (s, d_s) in enumerate(zip(starts, d_after)):
        both = mm(jnp.concatenate([do_h[h], dU[h]], axis=0), s)
        dq_gam.append(both[:CHUNK])
        dW.append(-both[CHUNK:])
        dk_hat.append(mm(u[h], d_s))
        d_end.append(jnp.sum(s * d_s, axis=0, keepdims=True) * loc["keeps"][h])
    dU, dq_gam, dW, dk_hat = _rows(dU), _rows(dq_gam), _rows(dW), _rows(dk_hat)

    dPm = mm(do, U, _NT)
    dT = mm(dW, loc["k_gam"], _NT) + mm(dU, v, _NT)
    Tt = T.T
    dk_gam, dv = mm(Tt, dW), mm(Tt, dU)
    dAm = -_mm_hi(_mm_hi(Tt, dT), Tt)
    dP0 = jnp.where(loc["upto"], dPm * brow, 0.0)
    dA0 = jnp.where(loc["below"], dAm * brow, 0.0)
    dbrow = jnp.sum(
        jnp.where(loc["below"], loc["A0"] * dAm, 0.0)
        + jnp.where(loc["upto"], loc["P0"] * dPm, 0.0),
        axis=0, keepdims=True,
    )
    return dict(
        d_state=d_state, dq_gam=dq_gam, dk_gam=dk_gam, dk_hat=dk_hat,
        d_end=d_end, dv=dv, dP0=dP0, dA0=dA0, dbrow=dbrow,
    )


def _block_bwd(mm, q, k, v, g, brow, do, starts, T, d_state):
    """One block backward: the block's rows, ``do``, the states its chunks
    started from, its inverse and the cotangent ``d_state`` of the state
    after it (both states transposed); returns ``(dq, dk, dv, dg, dbeta
    (1, ROWS), the cotangent of the state before it)``."""
    loc = _local(mm, q, k, v, g, brow, T)
    eG, eD, bcol = (loc[n] for n in ("eG", "eD", "bcol"))
    c = _state_bwd(mm, loc, v, brow, do, starts, d_state)
    d_state, dq_gam, dk_gam, dk_hat, d_end, dv, dP0, dA0, dbrow = (
        c[n] for n in ("d_state", "dq_gam", "dk_gam", "dk_hat", "d_end",
                       "dv", "dP0", "dA0", "dbrow")
    )

    dq_up, dk_up, d_mid, back = [], [], [], None
    middle = lax.broadcasted_iota(jnp.int32, (SUB, q.shape[-1]), 0) == SUB // 2 - 1
    for a, (down, x) in enumerate(zip(loc["downs"], loc["lhs"])):
        rows = slice(SUB * a, SUB * a + SUB)
        d = jnp.concatenate([dP0[rows], dA0[rows]], axis=0)      # (2 SUB, ROWS)
        got = mm(d, k * down)
        dq_up.append(got[:SUB])
        dk_up.append(got[SUB:])
        term = mm(d, x, _TN) * down                             # (ROWS, dk)
        back = term if back is None else back + term
        # the reference m's cotangent: zero but for the products'
        # rounding, left on the middle token (module docstring)
        of_m = jnp.sum(k * term, axis=0, keepdims=True) - jnp.sum(
            x * got, axis=0, keepdims=True
        )
        d_mid.append(jnp.where(middle, of_m, 0.0))

    dk_end = dk_hat * eD * bcol                 # through K^ = k exp(G_C - G) beta
    back = back + dk_end
    dq = _rows(dq_up) * loc["up"] + dq_gam * eG
    dk = _rows(dk_up) * loc["up"] + dk_gam * eG + back
    # every exponent is +-G: a factor's cotangent times the factor
    dG = q * dq + k * (dk - 2.0 * back) + _rows(d_mid)
    at = lax.broadcasted_iota(jnp.int32, dG.shape, 0)
    for h, (x, e) in enumerate(zip(_halves(dk_end * k), d_end)):
        total = jnp.sum(x, axis=0, keepdims=True) + e            # of G_C
        dG = dG + jnp.where(at == h * CHUNK + CHUNK - 1, total, 0.0)
    dg = _tri_sum(loc["tri"], dG, _TN)
    dbcol = jnp.sum(dk_hat * k * eD, axis=1, keepdims=True)
    dbrow = dbrow + jnp.sum(
        jnp.where(loc["eye"], jnp.broadcast_to(dbcol, (ROWS, ROWS)), 0.0),
        axis=0, keepdims=True,
    )
    return dq, dk, dv, dg, dbrow, d_state


def _block_bwd_safe(mm, q, k, v, g, brow, do, starts, T, d_state):
    """:func:`_block_bwd` for any ``g <= 0``: a level's two cotangent
    products where the bounded one has a row sub-block's, and every
    exponent's cotangent its factor's times the factor (the exponents are
    sums of ``g``: :func:`_segments_bwd` takes them back to it)."""
    loc = _local_safe(mm, q, k, v, g, brow, T)
    eG, eD, bcol = (loc[n] for n in ("eG", "eD", "bcol"))
    c = _state_bwd(mm, loc, v, brow, do, starts, d_state)
    dq_gam, dk_gam, dk_hat, dP0, dA0 = (
        c[n] for n in ("dq_gam", "dk_gam", "dk_hat", "dP0", "dA0")
    )

    d = jnp.where(loc["eye"], dP0, 0.0)
    dq, dk = mm(d, k), mm(d, q, _TN)
    dD, dE = [], []
    for mask, x, y, eDh, eEh in loc["levels"]:
        d = jnp.concatenate(
            [jnp.where(mask, dP0, 0.0), jnp.where(mask, dA0, 0.0)], axis=0
        )                                                        # (2 ROWS, ROWS)
        got, back = mm(d, y), mm(d, x, _TN)
        dq = dq + got[:ROWS] * eDh
        dk = dk + got[ROWS:] * eDh + back * eEh
        dD.append(x[:ROWS] * got[:ROWS] + x[ROWS:] * got[ROWS:])
        dE.append(y * back)

    dk_end = dk_hat * eD * bcol                 # through K^ = k exp(G_C - G) beta
    dq = dq + dq_gam * eG
    dk = dk + dk_gam * eG + dk_end
    # the top level: Gamma = exp(D_C) on q and k, exp(E_C) in K^, and a
    # chunk's exp(G_C) (its last token's D_C) on the state it starts from
    dG = loc["q_gam"] * dq_gam + loc["k_gam"] * dk_gam
    at = lax.broadcasted_iota(jnp.int32, dG.shape, 0)
    for h, e in enumerate(c["d_end"]):
        dG = dG + jnp.where(at == h * CHUNK + CHUNK - 1, e, 0.0)
    dg = _segments_bwd(dD + [dG], dE + [dk_end * k])
    dbcol = jnp.sum(dk_hat * k * eD, axis=1, keepdims=True)
    dbrow = c["dbrow"] + jnp.sum(
        jnp.where(loc["eye"], jnp.broadcast_to(dbcol, (ROWS, ROWS)), 0.0),
        axis=0, keepdims=True,
    )
    return dq, dk, c["dv"], dg, dbrow, c["d_state"]


def _fwd_kernel(blocks, save, mm, safe=False):
    local = _local_safe if safe else _local

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest):
        s_ref, t_ref, state_ref = rest if save else (None, None, *rest)

        @pl.when(pl.program_id(1) == 0)
        def _():
            state_ref[...] = jnp.zeros_like(state_ref)

        state = state_ref[...]
        # unrolled, yet the final bundles run the blocks one after another:
        # a block's inverse is a chain of products the MXUs wait through
        for i in range(blocks):
            rows = slice(i * ROWS, (i + 1) * ROWS)
            o, state, starts, T = _block_fwd(
                mm, q_ref[0, rows], k_ref[0, rows], v_ref[0, rows],
                g_ref[0, rows], b_ref[0, i], state, local,
            )
            o_ref[0, rows] = o
            if save:
                for h, s in enumerate(starts):
                    s_ref[0, 2 * i + h] = s
                t_ref[0, i] = T
        state_ref[...] = state

    return kernel


def _bwd_kernel(blocks, mm, safe=False):
    block_bwd = _block_bwd_safe if safe else _block_bwd

    def kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, t_ref,
               dq_ref, dk_ref, dv_ref, dg_ref, db_ref, d_state_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            d_state_ref[...] = jnp.zeros_like(d_state_ref)

        d_state = d_state_ref[...]
        for i in reversed(range(blocks)):
            rows = slice(i * ROWS, (i + 1) * ROWS)
            dq, dk, dv, dg, db, d_state = block_bwd(
                mm, q_ref[0, rows], k_ref[0, rows], v_ref[0, rows],
                g_ref[0, rows], b_ref[0, i], do_ref[0, rows],
                [s_ref[0, 2 * i + h] for h in range(ROWS // CHUNK)],
                t_ref[0, i], d_state,
            )
            dq_ref[0, rows] = dq
            dk_ref[0, rows] = dk
            dv_ref[0, rows] = dv
            dg_ref[0, rows] = dg
            db_ref[0, i] = db
        d_state_ref[...] = d_state

    return kernel


def _specs(blocks, dk, dv, steps, backward):
    """Block specs of the row arrays, beta and what the forward saves (the
    chunks' start states, the blocks' inverses); the backward visits the
    steps in reverse."""
    at = (lambda s: steps - 1 - s) if backward else (lambda s: s)
    rows = lambda d: pl.BlockSpec((1, blocks * ROWS, d), lambda n, s: (n, at(s), 0))
    beta = pl.BlockSpec((1, blocks, 1, ROWS), lambda n, s: (n, at(s), 0, 0))
    states = pl.BlockSpec((1, 2 * blocks, dv, dk), lambda n, s: (n, at(s), 0, 0))
    inverses = pl.BlockSpec((1, blocks, ROWS, ROWS), lambda n, s: (n, at(s), 0, 0))
    return rows, beta, (states, inverses)


def _vmem_limit(blocks, dk, dv, arrays):
    """Double-buffered row blocks, states and inverses, the scratch, and
    room for what a block keeps live between its products."""
    row = blocks * ROWS * max(dk, dv) * 4
    saved = blocks * (2 * dk * dv + ROWS * ROWS) * 4
    return 2 * (arrays * row + saved) + dk * dv * 4 + (24 << 20)


# jitted, so that a program's layers share one trace and one lowered
# function a shape
@partial(jax.jit, static_argnames=("save", "blocks", "interpret", "one_pass", "safe"))
def _forward(q, k, v, g, beta, *, save, blocks, interpret, one_pass, safe=False):
    N, Tp, dk = q.shape
    dv = v.shape[-1]
    steps = Tp // (blocks * ROWS)
    rows, b_spec, s_spec = _specs(blocks, dk, dv, steps, backward=False)
    operands = (q, k, v, g, beta)
    out_shape = [out_struct((N, Tp, dv), _f32, *operands)]
    out_specs = [rows(dv)]
    if save:
        out_shape += [
            out_struct((N, Tp // CHUNK, dv, dk), _f32, *operands),
            out_struct((N, Tp // ROWS, ROWS, ROWS), _f32, *operands),
        ]
        out_specs += s_spec
    call = pl.pallas_call(
        _fwd_kernel(blocks, save, partial(_mm, one_pass=one_pass), safe),
        grid=(N, steps),
        out_shape=out_shape,
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), b_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((dv, dk), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(blocks, dk, dv, 5),
        ),
        interpret=interpret,
        name=FWD,
    )
    return _run(call, interpret, *operands)


@partial(jax.jit, static_argnames=("blocks", "interpret", "one_pass", "safe"))
def _backward(q, k, v, g, beta, do, states, inverses, *, blocks, interpret,
              one_pass, safe=False):
    N, Tp, dk = q.shape
    dv = v.shape[-1]
    steps = Tp // (blocks * ROWS)
    rows, b_spec, s_spec = _specs(blocks, dk, dv, steps, backward=True)
    operands = (q, k, v, g, beta, do, states, inverses)
    call = pl.pallas_call(
        _bwd_kernel(blocks, partial(_mm, one_pass=one_pass), safe),
        grid=(N, steps),
        out_shape=[
            out_struct(x.shape, _f32, *operands) for x in (q, k, v, g, beta)
        ],
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), b_spec, rows(dv), *s_spec],
        out_specs=[rows(dk), rows(dk), rows(dv), rows(dk), b_spec],
        scratch_shapes=[pltpu.VMEM((dv, dk), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(blocks, dk, dv, 10),
        ),
        interpret=interpret,
        name=BWD,
    )
    return _run(call, interpret, *operands)


def _blocks_for(T):
    """Blocks a grid step and the padded length: BLOCKS, or all of a
    shorter sequence's."""
    n = -(-T // ROWS)
    blocks = min(BLOCKS, n)
    return blocks, -(-n // blocks) * blocks * ROWS


def _flat(x, Tp):
    """``(B, H, T, .)`` or ``(B, H, T)`` as float32 ``(B H, Tp, .)`` rows,
    the tail zeros."""
    B, H, T = x.shape[:3]
    x = x.astype(_f32).reshape(B * H, T, -1)
    return jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))


def _packed(q, k, v, g, beta):
    """``(blocks a step, the operands)``: rows padded to whole grid steps
    with tokens that leave the state alone (no decay, no write), beta a
    lane row a block."""
    blocks, Tp = _blocks_for(q.shape[2])
    q, k, v, g, beta = (_flat(x, Tp) for x in (q, k, v, g, beta))
    return blocks, (q, k, v, g, beta.reshape(-1, Tp // ROWS, 1, ROWS))


def _apply(q, k, v, g, beta, how, save):
    """``(o, what the forward saves for the backward or ())``; ``how``:
    ``(interpret, one_pass, safe)``."""
    B, H, T, _ = q.shape
    blocks, packed = _packed(q, k, v, g, beta)
    o, *saved = _forward(
        *packed, save=save, blocks=blocks, interpret=how[0], one_pass=how[1],
        safe=how[2],
    )
    o = _settled(o.reshape(B, H, -1, o.shape[-1])[:, :, :T], how[0])
    return o, tuple(saved)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, how):
    return _apply(q, k, v, g, beta, how, save=False)[0]


def _kda_fwd(q, k, v, g, beta, how):
    o, saved = _apply(q, k, v, g, beta, how, save=True)
    return o, (q, k, v, g, beta, *saved)


def _kda_bwd(how, res, do):
    interpret, one_pass, safe = how
    *inputs, states, inverses = res
    B, H, T, _ = inputs[0].shape
    blocks, packed = _packed(*inputs)
    do = _flat(do, packed[0].shape[1])
    *grads, dbeta = _backward(
        *packed, do, states, inverses,
        blocks=blocks, interpret=interpret, one_pass=one_pass, safe=safe,
    )
    grads = [x[:, :T].reshape(B, H, T, -1) for x in grads]
    grads.append(dbeta.reshape(B, H, -1)[:, :, :T])
    return tuple(
        _settled(x.astype(p.dtype), interpret) for x, p in zip(grads, inputs)
    )


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, *, safe: bool = False,
        interpret: InterpretArg = None):
    """``ops.kda.kda_chunked`` by the kernels: ``q``, ``k``, ``g`` (B, H,
    T, dk), ``v`` (B, H, T, dv), ``beta`` (B, H, T), :func:`takes` their
    shapes; ``o`` (B, H, T, dv) float32.  Differentiable by all five.
    ``safe``: any ``g <= 0`` (the split by halving)."""
    _, operands = vary_together(q, k, v, g, beta)
    return _kda(*operands, (default_interpret(interpret), _ONE_PASS, bool(safe)))
