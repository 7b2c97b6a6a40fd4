"""Kimi Delta Attention's core (KDA, arXiv:2510.26692): the gated delta
rule with a decay a CHANNEL, in its chunked form.

A head keeps a state ``S`` (dk x dv, ``S_0 = 0``).  Token ``t`` brings a
query ``q_t`` and a key ``k_t`` (dk; the caller has L2-normalised both and
scaled q), a value ``v_t`` (dv), a log-decay ``g_t <= 0`` a channel of dk
and a write strength ``beta_t`` in (0, 1):

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


def kda_chunked(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB):
    """The gated delta rule of the module docstring over whole sequences:
    ``q``, ``k``, ``g`` (B, H, T, dk), ``v`` (B, H, T, dv), ``beta`` (B, H,
    T); returns ``o`` (B, H, T, dv) in float32.  ``g`` must not pass
    ``-80 / sub`` a token (the gate's lower bound of -5 at ``sub`` 16), or
    a diagonal block's exponents leave float32.  T need be no multiple of
    the chunk: the tail is padded with tokens that leave the state alone
    (no decay, no write).  The shapes pick the lowering (module
    docstring)."""
    if chunk % sub or sub % 2:
        raise ValueError(f"chunk {chunk} is no whole even sub-blocks of {sub}")
    if _kernels.takes(q.shape, v.shape, chunk, sub):
        return _kernels.kda(q, k, v, g, beta)
    return _xla_form(q, k, v, g, beta, chunk, sub)


def _xla_form(q, k, v, g, beta, chunk: int = CHUNK, sub: int = SUB):
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

    # log-decay summed inside a sub-block, and before it inside the chunk
    within = jnp.cumsum(blocks(g), axis=4)
    total = within[..., -1:, :]
    start = jnp.cumsum(total, axis=3) - total
    G = flat(start + within)                             # G_t
    end = G[..., -1:, :]                                 # G_C

    P, A = _decayed_products(blocks(q), blocks(k), within, start)
    t, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    by_column = beta[..., None, :]
    P = jnp.where(j <= t, P, 0.0) * by_column
    inv = _unit_lower_inverse(jnp.where(j < t, A, 0.0) * by_column)

    q, k, v = flat(q), flat(k), flat(v)
    w = inv @ (k * jnp.exp(G))                           # W
    u0 = inv @ v                                         # U~
    write = k * jnp.exp(end - G) * beta[..., None]       # K^
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


def decay_in(x, dt_bias, a_log, lower_bound: float):
    """The log-decay a channel from its projection ``x`` (B, T, H d):
    ``lower_bound * sigmoid(exp(a_log) (x + dt_bias))``, ``dt_bias`` a
    channel, ``a_log`` (H,) a head; float32 (B, H, T, d)."""
    if _chains.takes(x.shape[-1], a_log.shape[0]):
        return _chains.decay_in(x, dt_bias, a_log, lower_bound)
    return _xla_decay_in(x, dt_bias, a_log, lower_bound)


def _xla_decay_in(x, dt_bias, a_log, lower_bound):
    f = _heads(
        x.astype(jnp.float32) + dt_bias.astype(jnp.float32), a_log.shape[0]
    )
    rate = jnp.exp(a_log.astype(jnp.float32))[None, :, None, None]
    return lower_bound * jax.nn.sigmoid(rate * f)


def gated_out(o, gate, o_norm, eps: float, dtype):
    """What ``wo`` takes from the core's ``o`` (B, H, T, d): the RMS norm a
    head with the scale ``o_norm`` (d,), times ``sigmoid`` of the gate's
    projection ``gate`` (B, T, H d); (B, T, H d) in ``dtype``."""
    if _chains.takes(gate.shape[-1], o.shape[1]):
        return _chains.gated_out(o, gate, o_norm, eps, dtype)
    return _xla_gated_out(o, gate, o_norm, eps, dtype)


def _xla_gated_out(o, gate, o_norm, eps, dtype):
    B, _, T, d = o.shape
    o = o.astype(jnp.float32)
    o = o * lax.rsqrt(jnp.sum(o * o, axis=-1, keepdims=True) / d + eps)
    o = o * o_norm.astype(jnp.float32)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    return (o * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)
