"""Kimi Delta Attention's core (KDA, arXiv:2510.26692): the gated delta
rule with a decay a CHANNEL, in its chunked form.

A head keeps a state ``S`` (dk x dv, ``S_0 = 0``).  Token ``t`` brings a
query ``q_t`` and a key ``k_t`` (dk; the caller has L2-normalised both and
scaled q), a value ``v_t`` (dv), a log-decay ``g_t <= 0`` a channel of dk
and a write strength ``beta_t`` in (0, 1), or in (0, 2) where the model
allows the transition's eigenvalue along ``k_t`` to be negative:

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_chunked` computes that for whole sequences in chunks of
``CHUNK`` tokens, memory linear in T, float32 in and out, by ONE OF TWO
lowerings picked from the shapes (``ops.pallas.kda.takes``): heads whose
``dk`` and ``dv`` are whole lanes (multiples of 128) at the default chunk
and sub-block run the Mosaic kernels ``kda_fwd`` / ``kda_bwd``
(``ops/pallas/kda.py``: what a chunk makes stays in VMEM, the scan over
the chunk states is fused, the backward is a kernel of its own behind a
``custom_vjp`` that saves only the chunk start states); every other shape
(the tests' tiny widths, a CPU rehearsal's) runs the XLA FORM below, plain
``jax.numpy`` that XLA lowers and autodiff differentiates, which is also
the oracle the kernels are tested against.  Both compute the same
products at the same precision (the kernels' docstring lists them).

THE CHUNKED FORM.  Inside a chunk, with ``G_t`` the sum of ``g`` from the
chunk's first token to ``t``, ``Gamma_t = exp(G_t)`` and ``S`` the state
the chunk starts from, the rule unrolls to

    u_t = v_t - S^T (Gamma_t k_t) - sum_{j<t} beta_j [(Gamma_t k_t).(k_j / Gamma_j)] u_j
    o_t = S^T (Gamma_t q_t) + sum_{j<=t} beta_j [(Gamma_t q_t).(k_j / Gamma_j)] u_j
    S'  = diag(Gamma_C) S + sum_j beta_j (Gamma_C / Gamma_j) k_j u_j^T

(``u_t`` is what token t writes: its value less what the state already
answers to its key).  With ``A[t, j] = beta_j (Gamma_t k_t).(k_j /
Gamma_j)`` for ``j < t`` and ``P[t, j] = (Gamma_t q_t).(k_j / Gamma_j)``
for ``j <= t`` the first line is ``(I + A) U = V - (Gamma K) S``, so with
``T = (I + A)^-1`` (:func:`_unit_lower_inverse`), ``W = T (Gamma K)`` and
``U~ = T V``:

    U  = U~ - W S                      the only steps that wait for S:
    S' = diag(Gamma_C) S + K^^T U      a scan over the chunks
    O  = (Gamma Q) S + (P beta) U      all chunks at once, after it

EXPONENTS.  ``k_j / Gamma_j`` overflows float32 within a chunk (64 tokens
at the gate's lower bound of -5 sum to -320), so ``A`` and ``P`` are
built in sub-blocks of ``SUB`` tokens and every exponent is taken
relative to a point between its row and its column: a block below the
diagonal splits ``exp(G_t - G_j)`` at the row block's start (both factors
at most 1), a diagonal block at its own middle token (factors within
``exp(+-SUB / 2 * 5)``, e^40 at the bound).  What underflows is smaller
than float32 resolves beside the terms it is added to.

ANY ``g <= 0`` (``safe=True``: the published, unbounded gate ``-exp(a_log)
softplus(.)``, where one token can carry -50 and eight of them leave
float32).  No exponent may then be taken from a point BETWEEN tokens of
one sub-block, so ``exp(G_t - G_j)`` is split by HALVING instead: a chunk
is two half chunks and the square below them, ``t`` in the lower half and
``j`` in the upper, split at the lower half's start, ``exp(G_t - c) exp(c
- G_j)`` with both factors at most 1; each half again, down to single
tokens.  A pair ``j < t`` belongs to exactly one LEVEL ``h`` (32, 16, ..,
1: the highest bit in which ``t`` and ``j`` differ), so ``A`` and ``P``
are the sum over the levels of one masked product each, rows times
``exp(D_h)`` (a token's log-decay summed from its ``h``-segment's first
token to itself) and key columns times ``exp(E_h)`` (summed from the next
token to its segment's last), plus ``P``'s diagonal.  Both are plain sums
of ``g``, never a difference of two large ``G``: nothing overflows, what
underflows bounds its product under ``e^-87``, and a log-decay's
cotangent has no two large terms that must cancel.  ``Gamma`` and
``Gamma_C / Gamma`` are ``exp(D_C)`` and ``exp(E_C)`` the same way, and
``(I + A)^-1`` goes up the same levels (:func:`_unit_lower_inverse_by_
halving`: the write strengths such a model has, in (0, 2), cost the
powers of ``A`` float32's last digits).
:func:`_safe_products` is that form; the bounded gate keeps the sub-block
form above (chosen statically by the caller: ``models.transformer``
``DeltaAttention.lower_bound``), so its program is what it was.

A DECAY A HEAD (Gated DeltaNet, arXiv:2412.06464: ``g`` of ONE column,
``(B, H, T, 1)``) is the same rule with every channel of a head decaying
alike, and runs the same core for any ``g <= 0`` (:func:`_decay_a_head`):
``g`` goes to every channel of the head, and heads that are not whole lanes
but within :data:`ops.pallas.kda.PAD` of them (keys of 96, values of 192)
are padded with zero columns up to whole lanes for the kernels, exactly (a
zero key column writes nothing and answers nothing; ``o``'s columns are cut
back).  The scalar gate's own chunked form, whose ``exp(G_t - G_j)``
multiplies ``K K^T`` and ``Q K^T`` AFTER the products with no split at all,
is not built.

THE CHAINS ROUND THE CORE (the end of this module): what the mixer runs in
float32 between a projection and the core and between the core and ``wo``
(:func:`conv_in`, :func:`decay_in`, :func:`gated_out`), by the same kind
of choice (``ops.pallas.kda_mixer.takes``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import kda as _kernels
from .pallas import kda_mixer as _chains

#: tokens a chunk (one step of the scan over the states) and a sub-block:
#: the kernels' own, which take no other
CHUNK, SUB = _kernels.CHUNK, _kernels.SUB


def _dot(x, y):
    return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` (..., n, n):
    ``a`` is nilpotent, so the inverse is ``sum_k (-a)^k = (I - a)(I +
    a^2)(I + a^4)...``, log2(n) squarings and as many products, each at
    ``highest`` precision (a rounding of ``a`` would be raised to its
    powers).  The cotangent is ``-T^T g T^T``: two products, and only
    ``T`` is kept."""
    n = a.shape[-1]
    power = -a
    inv = jnp.eye(n, dtype=a.dtype) + power
    reach = 2                      # inv holds the powers below ``reach``
    while reach < n:
        power = _dot(power, power)
        inv = inv + _dot(inv, power)
        reach *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    inv = _unit_lower_inverse(a)
    return inv, inv


def _unit_lower_inverse_bwd(inv, g):
    t = jnp.swapaxes(inv, -1, -2)
    # ``a`` is strictly lower: what falls elsewhere is no cotangent of it
    return (jnp.tril(-_dot(_dot(t, g), t), -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


@jax.custom_vjp
def _unit_lower_inverse_by_halving(a):
    """:func:`_unit_lower_inverse` without a power of ``a``: the inverse of
    a block ``[[M1, 0], [C, M2]]`` is ``[[T1, 0], [-T2 C T1, T2]]``, that is
    ``T - T C T`` for the block-diagonal ``T`` of the two halves' inverses,
    from single tokens up (n a power of two; two ``highest`` products a
    level).  Under a write strength in (0, 2) and keys that lean the same way
    the powers' entries reach the hundreds before they cancel, which costs
    float32 its last three digits; these factors stay of the inverse's own
    size.  The same cotangent."""
    n = a.shape[-1]
    inv, h = jnp.eye(n, dtype=a.dtype), 1
    while h < n:
        inv = inv - _dot(_dot(inv, jnp.where(_level_pairs(n, h), a, 0.0)), inv)
        h *= 2
    return inv


def _by_halving_fwd(a):
    inv = _unit_lower_inverse_by_halving(a)
    return inv, inv


_unit_lower_inverse_by_halving.defvjp(_by_halving_fwd, _unit_lower_inverse_bwd)


def _decayed_products(q, k, within, start):
    """``[q_t . k_j exp(G_t - G_j), k_t . k_j exp(G_t - G_j)]`` of every
    chunk, (2, ..., C, C), right where ``j <= t`` and finite elsewhere
    (the callers mask it).  ``q``, ``k``: (..., S, s, dk) in sub-blocks;
    ``within``: a token's log-decay summed from its sub-block's first
    token; ``start``: (..., S, 1, dk) the sum before a sub-block."""
    S, s = q.shape[-3], q.shape[-2]
    rows = jnp.stack([q, k])                             # (2, ..., S, s, dk)
    # diagonal blocks, exponents from the block's middle token
    mid = within[..., s // 2 - 1: s // 2, :]
    diag = jnp.einsum(
        "r...td,...jd->r...tj",
        rows * jnp.exp(within - mid), k * jnp.exp(mid - within),
    )                                                    # (2, ..., S, s, s)
    # blocks below the diagonal, exponents from the ROW block's start
    rows = rows * jnp.exp(within)
    G = start + within
    out = []
    for a in range(S):
        parts = [diag[..., a, :, :]]
        if a:
            keys = k[..., :a, :, :] * jnp.exp(
                start[..., a: a + 1, :, :] - G[..., :a, :, :]
            )
            keys = keys.reshape(*keys.shape[:-3], a * s, keys.shape[-1])
            parts.insert(0, jnp.einsum(
                "r...td,...jd->r...tj", rows[..., a, :, :], keys
            ))
        if a < S - 1:
            parts.append(jnp.zeros(
                (*diag.shape[:-3], s, (S - 1 - a) * s), diag.dtype
            ))
        out.append(jnp.concatenate(parts, axis=-1))      # (2, ..., s, C)
    return jnp.concatenate(out, axis=-2)


def _segment_sums(g, h: int):
    """``(D_h, E_h)`` of ``g`` (..., C, dk) in segments of ``h`` tokens: a
    token's ``g`` summed from its segment's first token to itself, and from
    the NEXT token to its segment's last (0 at the last); plain sums, so
    neither is above 0 for ``g <= 0``."""
    C, dk = g.shape[-2:]
    x = g.reshape(*g.shape[:-2], C // h, h, dk)
    after = jnp.concatenate([x[..., 1:, :], jnp.zeros_like(x[..., :1, :])], -2)
    return (
        jnp.cumsum(x, axis=-2).reshape(g.shape),
        lax.cumsum(after, axis=after.ndim - 2, reverse=True).reshape(g.shape),
    )


def _level_pairs(n: int, h: int):
    """The (n, n) mask of level ``h``'s pairs: ``t`` in the lower half and
    ``j`` in the upper half of one segment of ``2 h`` tokens."""
    t, j = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    return (t // (2 * h) == j // (2 * h)) & (t // h % 2 == 1) & (j // h % 2 == 0)


def _safe_products(q, k, g):
    """``[q_t . k_j exp(G_t - G_j), k_t . k_j exp(G_t - G_j)]`` of every
    chunk, (2, ..., C, C), for ANY ``g <= 0`` (module docstring, ANY ``g <=
    0``): zero where ``j > t`` (the caller masks the second to ``j < t``).  ``q``, ``k``, ``g``: (..., C, dk)."""
    C = q.shape[-2]
    rows = jnp.stack([q, k])                             # (2, ..., C, dk)
    t, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    pairs = lambda x, y: jnp.einsum("r...td,...jd->r...tj", x, y)
    out = jnp.where(t == j, pairs(rows, k), 0.0)
    h = C // 2
    while h:
        D, E = _segment_sums(g, h)
        out = out + jnp.where(
            _level_pairs(C, h), pairs(rows * jnp.exp(D), k * jnp.exp(E)), 0.0
        )
        h //= 2
    return out


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB,
                safe: bool = False):
    """The gated delta rule of the module docstring over whole sequences:
    ``q``, ``k``, ``g`` (B, H, T, dk), ``v`` (B, H, T, dv), ``beta`` (B, H,
    T); returns ``o`` (B, H, T, dv) in float32.  Without ``safe``, ``g``
    must not pass ``-80 / sub`` a token (the gate's lower bound of -5 at
    ``sub`` 16), or a diagonal block's exponents leave float32; with it
    any ``g <= 0`` is right (the split by halving; ``chunk`` a power of
    two).  T need be no multiple of the chunk: the tail is padded with
    tokens that leave the state alone (no decay, no write).  The shapes
    pick the lowering (module docstring).  A ``g`` of ONE column, (B, H, T,
    1), is a decay a head (module docstring, A DECAY A HEAD): it has no
    bound, so it runs as under ``safe`` whatever ``safe`` says."""
    if chunk % sub or sub % 2:
        raise ValueError(f"chunk {chunk} is no whole even sub-blocks of {sub}")
    head_decay = g.shape[-1] == 1 and q.shape[-1] != 1
    if (safe or head_decay) and chunk & (chunk - 1):
        raise ValueError(f"the split by halving needs a chunk of 2^n, not {chunk}")
    if head_decay:
        return _decay_a_head(q, k, v, g, beta, chunk, sub)
    if _kernels.takes(q.shape, v.shape, chunk, sub):
        return _kernels.kda(q, k, v, g, beta, safe=safe)
    return _xla_form(q, k, v, g, beta, chunk, sub, safe)


def _decay_a_head(q, k, v, g, beta, chunk, sub):
    """:func:`kda_chunked` for a decay a head, ``g`` (B, H, T, 1) (module
    docstring, A DECAY A HEAD): the split by halving with ``g`` on every
    channel, by the kernels where the heads are whole lanes or within
    ``ops.pallas.kda.PAD`` of them (zero columns up to whole lanes, ``o``
    cut back), else by the XLA form."""
    dv = v.shape[-1]
    if not _kernels.takes_padded(q.shape[-1], dv, chunk, sub):
        g = jnp.broadcast_to(g, q.shape)
        return _xla_form(q, k, v, g, beta, chunk, sub, safe=True)
    wide = lambda x: jnp.pad(
        x, [(0, 0)] * 3 + [(0, -x.shape[-1] % _kernels.LANES)]
    )
    q, k, v = wide(q), wide(k), wide(v)
    g = jnp.broadcast_to(g, q.shape)
    return _kernels.kda(q, k, v, g, beta, safe=True)[..., :dv]


def _xla_form(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB,
              safe: bool = False):
    """:func:`kda_chunked` as plain ``jax.numpy``, at any shape."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if pad := -T % chunk:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 3))
            for x in (q, k, v, g, beta)
        )
    N, S = (T + pad) // chunk, chunk // sub
    blocks = lambda x: x.reshape(B, H, N, S, sub, x.shape[-1])
    flat = lambda x: x.reshape(B, H, N, chunk, x.shape[-1])
    beta = beta.reshape(B, H, N, chunk)

    if safe:
        # G_t and G_C - G_t as plain sums of g over the chunk
        G, to_end = _segment_sums(flat(g), chunk)
        end = G[..., -1:, :]                             # G_C
        P, A = _safe_products(flat(q), flat(k), flat(g))
    else:
        # log-decay summed inside a sub-block, and before it inside the chunk
        within = jnp.cumsum(blocks(g), axis=4)
        total = within[..., -1:, :]
        start = jnp.cumsum(total, axis=3) - total
        G = flat(start + within)                         # G_t
        end = G[..., -1:, :]                             # G_C
        P, A = _decayed_products(blocks(q), blocks(k), within, start)
    t, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    by_column = beta[..., None, :]
    P = jnp.where(j <= t, P, 0.0) * by_column
    inverse = _unit_lower_inverse_by_halving if safe else _unit_lower_inverse
    inv = inverse(jnp.where(j < t, A, 0.0) * by_column)

    q, k, v = flat(q), flat(k), flat(v)
    w = inv @ (k * jnp.exp(G))                           # W
    u0 = inv @ v                                         # U~
    write = k * jnp.exp(to_end if safe else end - G) * beta[..., None]  # K^
    read = q * jnp.exp(G)
    keep = jnp.exp(end[..., 0, :])                       # (B, H, N, dk)

    def a_chunk(state, xs):
        w, u0, write, keep = xs
        u = u0 - w @ state
        new = keep[..., None] * state + jnp.swapaxes(write, -1, -2) @ u
        return new, (state, u)

    first = lambda x: jnp.moveaxis(x, 2, 0)              # chunks lead the scan
    state = jnp.zeros((B, H, dk, dv), jnp.float32)
    # inside a shard_map: the scan's carry varying over the axes its
    # inputs vary over, from the first step on
    if varying := tuple(jax.typeof(w).vma):
        state = lax.pcast(state, varying, to="varying")
    _, (states, u) = lax.scan(
        a_chunk, state, (first(w), first(u0), first(write), first(keep)),
    )
    o = jnp.einsum("bhntd,nbhde->bhnte", read, states) + jnp.einsum(
        "bhntj,nbhje->bhnte", P, u
    )
    return o.reshape(B, H, N * chunk, dv)[:, :, :T]


# -- the mixer's float32 chains round the core ---------------------------------
#
# What ``models.transformer._kda_partial`` runs between a projection and the
# core, and between the core and ``wo``: float32 from the projection (which
# has the matmuls' type) on, by ONE OF TWO lowerings picked from the shapes
# (``ops.pallas.kda_mixer.takes``: heads of whole lanes), the Mosaic kernels
# there (one pass over HBM a chain, forward and backward) or the XLA forms
# here (plain ``jax.numpy``: every other shape's, and the kernels' oracle).


def conv_in(x, taps, heads: int, *, unit: bool, scale: float = 1.0):
    """q, k or v from its projection ``x`` (B, T, H d): the causal depthwise
    convolution by ``taps`` (n, H d) (zero left padding, the last tap the
    current token's), SiLU, under ``unit`` the L2 norm over a head's
    columns (``+ 1e-6`` inside the root) times ``scale``; float32
    (B, H, T, d)."""
    if _chains.takes(x.shape[-1], heads, taps.shape[0]):
        return _chains.conv_in(x, taps, heads, unit=unit, scale=scale)
    return _xla_conv_in(x, taps, heads, unit=unit, scale=scale)


def _heads(x, heads):
    B, T, _ = x.shape
    return x.reshape(B, T, heads, -1).transpose(0, 2, 1, 3)


def _xla_conv_in(x, taps, heads, *, unit, scale=1.0):
    T, n = x.shape[1], taps.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    y = _heads(jax.nn.silu(
        sum(x[:, i:i + T] * taps[i].astype(jnp.float32) for i in range(n))
    ), heads)
    if unit:
        y = y * lax.rsqrt(
            jnp.sum(y * y, axis=-1, keepdims=True) + _chains.UNIT_EPS
        )
        if scale != 1.0:
            y = y * scale
    return y


def decay_in(x, dt_bias, a_log, lower_bound):
    """The log-decay a channel from its projection ``x`` (B, T, H d):
    ``lower_bound * sigmoid(exp(a_log) (x + dt_bias))``, ``dt_bias`` a
    channel, ``a_log`` (H,) a head; under ``lower_bound`` None the
    published gate without a bound, ``-exp(a_log) softplus(x + dt_bias)``
    (the core then runs ``safe``); float32 (B, H, T, d).  ``d`` 1 is a
    decay a head (``x`` (B, T, H), ``dt_bias`` a head): the XLA form's."""
    if _chains.takes(x.shape[-1], a_log.shape[0]):
        return _chains.decay_in(x, dt_bias, a_log, lower_bound)
    return _xla_decay_in(x, dt_bias, a_log, lower_bound)


def _xla_decay_in(x, dt_bias, a_log, lower_bound):
    f = _heads(
        x.astype(jnp.float32) + dt_bias.astype(jnp.float32), a_log.shape[0]
    )
    rate = jnp.exp(a_log.astype(jnp.float32))[None, :, None, None]
    if lower_bound is None:
        return -rate * jax.nn.softplus(f)
    return lower_bound * jax.nn.sigmoid(rate * f)


def gated_out(o, gate, o_norm, eps: float, dtype, silu: bool = False):
    """What ``wo`` takes from the core's ``o`` (B, H, T, d): the RMS norm a
    head with the scale ``o_norm`` (d,), times ``sigmoid`` of the gate's
    projection ``gate`` (B, T, H d), or under ``silu`` times its SiLU (the
    XLA form alone); (B, T, H d) in ``dtype``."""
    if not silu and _chains.takes(gate.shape[-1], o.shape[1]):
        return _chains.gated_out(o, gate, o_norm, eps, dtype)
    return _xla_gated_out(o, gate, o_norm, eps, dtype, silu)


def _xla_gated_out(o, gate, o_norm, eps, dtype, silu=False):
    B, _, T, d = o.shape
    o = o.astype(jnp.float32)
    o = o * lax.rsqrt(jnp.sum(o * o, axis=-1, keepdims=True) / d + eps)
    o = o * o_norm.astype(jnp.float32)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    act = jax.nn.silu if silu else jax.nn.sigmoid
    return (o * act(gate.astype(jnp.float32))).astype(dtype)
