"""Mamba-2's core (state-space duality, SSD, arXiv:2405.21060): the selective
state-space recurrence with a SCALAR decay a head, in its chunked form.

A head keeps a state ``S`` (P x N, ``S_0 = 0``).  Token ``t`` brings an
input ``x_t`` (P, the head's width), a step ``dt_t > 0`` (the caller's
softplus) and, shared by the ``H / G`` heads of its GROUP, an input
direction ``B_t`` and an output direction ``C_t`` (N, the state's width);
the head has a rate ``A < 0`` and a skip ``D``, both scalars:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

:func:`ssd_mixer` computes that for whole sequences in chunks of ``CHUNK``
tokens, memory linear in T, float32 in and out, by ONE OF TWO lowerings
picked from the shapes (``ops.pallas.ssd.takes``: the published chunk, a
state of whole lanes, a head that divides a lane row, a group's heads
filling whole lane rows): the Mosaic kernels ``ssd_fwd`` / ``ssd_bwd``
(``ops/pallas/ssd.py``: a chunk's decay squares and the running state stay
in VMEM, the scan over the chunks is the grid, the backward hand-derived;
ROADMAP S9 (b), M5), or, at any other shape (the tests' tiny widths, the
benchmark's ``--rehearse``), :func:`ssd_chunked`, plain ``jax.numpy`` that
XLA lowers and autodiff differentiates, which is also the kernels' oracle.
The XLA form shares ``ops/kda.py``'s shape, chunks and a ``lax.scan`` over
the chunks' states with every exponent kept inside float32, and none of its
inverse: without a delta rule a token's write does not depend on the state,
so a chunk is two masked products and no solve.

THE CHUNKED FORM.  Inside a chunk, with ``l_t`` the sum of ``dt A`` from the
chunk's first token to ``t`` (``<= 0``, falling) and ``S`` the state the
chunk starts from:

    y_t = sum_{s<=t} (C_t . B_s) exp(l_t - l_s) dt_s x_s      masked (C B^T) x
        + exp(l_t) S C_t                                      what came before
        + D x_t
    S'  = exp(l_C) S + sum_s exp(l_C - l_s) dt_s x_s B_s^T    a scan over chunks

``C B^T`` is a GROUP's (its heads share it); the decay ``exp(l_t - l_s)`` is
a head's.  EXPONENTS: every one is a difference ``l_t - l_s`` with ``s <=
t``, at most 0; the other half of the chunk's square is set to ``-inf``
BEFORE the exponential (``exp(l_t) / exp(l_s)`` would overflow, and a mask
after the exponential would hand its backward ``0 x inf``).

THE MIXER'S CHAINS round the core live here too: :func:`conv_silu` (x, B and
C from their projections) and :func:`gated_group_norm` (what ``W_out`` takes
from ``y`` and the gate), float32 and token-major, each by one of two
lowerings the shapes pick as well (``ops.pallas.mamba_mixer.takes``: columns
in whole blocks of 1,024, a group of whole lanes, taps that a halo block
holds): the Mosaic kernels ``mamba_in_fwd`` / ``mamba_in_bwd`` and ``mamba_out_fwd`` /
``mamba_out_bwd`` (one pass over HBM a chain, forward and backward), or the
XLA forms ``_xla_conv_silu`` / ``_xla_gated_group_norm``, their oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import mamba_mixer as _chains
from .pallas import ssd as _kernels

#: tokens a chunk (one step of the scan over the states): the published
#: ``chunk_size``
CHUNK = 128


def ssd_mixer(x, b, c, dt, a, d, groups: int, chunk: int = CHUNK):
    """The recurrence of the module docstring over whole sequences, TOKEN-MAJOR
    as the mixer's chains leave their operands: ``x`` (B, T, H P), ``b`` and
    ``c`` (B, T, G N) with head ``i`` in group ``i // (H / G)``, ``dt`` (B,
    T, H) positive, ``a`` (H,) negative, ``d`` (H,); returns ``y`` (B, T, H
    P) in float32.  The shapes pick the lowering (module docstring): the
    kernels read and write these rows where they lie, the XLA form takes
    them head-major and gives them back."""
    B, T, H = dt.shape
    f32 = jnp.float32
    if _kernels.takes(x.shape, b.shape, H, groups, chunk):
        pad = ((0, 0), (0, -T % chunk), (0, 0))
        x, b, c, dt = (jnp.pad(v.astype(f32), pad) for v in (x, b, c, dt))
        dt = dt.transpose(0, 2, 1)                        # (B, H, T + pad)
        # l_t inside each chunk: an exact float32 sum, as the XLA form's
        l = jnp.cumsum(
            (dt * a.astype(f32)[None, :, None]).reshape(B, H, -1, chunk), axis=-1
        ).reshape(dt.shape)
        return _kernels.ssd(x, b, c, dt, l, d, groups)[:, :T]
    heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
    y = ssd_chunked(
        heads(x, H), heads(b, groups), heads(c, groups), dt.transpose(0, 2, 1),
        a, d, chunk,
    )
    return y.transpose(0, 2, 1, 3).reshape(B, T, -1)


def ssd_chunked(x, b, c, dt, a, d, chunk: int = CHUNK):
    """The XLA form, at any shape and head-major.  The recurrence of the module docstring over whole sequences: ``x``
    (B, H, T, P), ``b`` and ``c`` (B, G, T, N) with head ``i`` in group ``i
    // (H / G)``, ``dt`` (B, H, T) positive, ``a`` (H,) negative, ``d``
    (H,); returns ``y`` (B, H, T, P) in float32.  T need be no multiple of
    the chunk: the tail is padded with tokens of ``dt = 0``, which neither
    decay the state nor write to it."""
    B, H, T, P = x.shape
    G, N = b.shape[1], b.shape[-1]
    if H % G:
        raise ValueError(f"{H} heads are no whole groups of {G}")
    f32 = jnp.float32
    x, b, c, dt, a, d = (v.astype(f32) for v in (x, b, c, dt, a, d))
    if pad := -T % chunk:
        x, b, c, dt = (
            jnp.pad(v, [(0, 0), (0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 3))
            for v in (x, b, c, dt)
        )
    n, per = (T + pad) // chunk, H // G
    # (B, G, heads of a group, chunks, chunk, .): a group's B and C meet its
    # heads by broadcasting, never by a copy a head
    x = x.reshape(B, G, per, n, chunk, P)
    dt = dt.reshape(B, G, per, n, chunk)
    b = b.reshape(B, G, n, chunk, N)
    c = c.reshape(B, G, n, chunk, N)
    a = a.reshape(G, per)

    l = jnp.cumsum(dt * a[None, :, :, None, None], axis=-1)   # l_t
    end = l[..., -1:]                                         # l_C
    t, s = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(
        s <= t, l[..., :, None] - l[..., None, :], -jnp.inf
    ))                                                 # (B, G, per, n, C, C)
    cb = jnp.einsum("bgntk,bgnsk->bgnts", c, b)        # a group's C B^T
    xdt = x * dt[..., None]
    within = jnp.einsum(
        "bgpnts,bgpnsd->bgpntd", cb[:, :, None] * decay, xdt
    )
    # what a chunk adds to the state, decayed to the chunk's end
    wrote = jnp.einsum(
        "bgpnsd,bgnsk->bgpndk", xdt * jnp.exp(end - l)[..., None], b
    )                                                  # (B, G, per, n, P, N)
    keep = jnp.exp(end[..., 0])                        # (B, G, per, n)

    def a_chunk(state, xs):
        wrote, keep = xs
        return keep[..., None, None] * state + wrote, state

    state = jnp.zeros((B, G, per, P, N), f32)
    # inside a shard_map: the scan's carry varying over the axes its inputs
    # vary over, from the first step on
    if varying := tuple(jax.typeof(wrote).vma):
        state = lax.pcast(state, varying, to="varying")
    _, starts = lax.scan(
        a_chunk, state, (jnp.moveaxis(wrote, 3, 0), jnp.moveaxis(keep, 3, 0))
    )                                                  # (n, B, G, per, P, N)
    before = jnp.einsum(
        "bgntk,nbgpdk->bgpntd", c, starts
    ) * jnp.exp(l)[..., None]
    y = within + before + x * d.reshape(G, per)[None, :, :, None, None, None]
    return y.reshape(B, H, n * chunk, P)[:, :, :T]


# -- the mixer's float32 chains round the core (module docstring) ---------------


def conv_silu(x, taps, bias):
    """``SiLU(conv(x) + bias)``: ``x`` (B, T, C) in the matmuls' type, the
    causal depthwise convolution by ``taps`` (n, C) (zero left padding, the
    last tap the current token's), a ``bias`` a channel; float32 (B, T,
    C).  Whole blocks of 1,024 columns run the kernels ``mamba_in_fwd`` /
    ``mamba_in_bwd``, any other width the XLA form."""
    if _chains.takes(x.shape[-1], taps=taps.shape[0]):
        return _chains.conv_silu(x, taps, bias)
    return _xla_conv_silu(x, taps, bias)


def _xla_conv_silu(x, taps, bias):
    T, n = x.shape[1], taps.shape[0]
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    y = sum(x[:, i:i + T] * taps[i].astype(jnp.float32) for i in range(n))
    return jax.nn.silu(y + bias.astype(jnp.float32))


def gated_group_norm(y, z, scale, groups: int, eps: float, dtype):
    """What ``W_out`` takes from the core's ``y`` (B, T, C) and the gate's
    projection ``z`` (B, T, C): ``y SiLU(z)`` (the gate BEFORE the norm),
    RMS-normed over each of ``groups`` runs of ``C / groups`` columns,
    times the learned ``scale`` (C,); float32 inside, ``dtype`` out.
    Groups of whole lanes in whole blocks of 1,024 columns run the kernels
    ``mamba_out_fwd`` / ``mamba_out_bwd`` on the rows as they lie, any other
    width the XLA form over its ``(B, T, groups, C / groups)`` view."""
    if _chains.takes(y.shape[-1], groups):
        return _chains.gated_group_norm(y, z, scale, groups, eps, dtype)
    return _xla_gated_group_norm(y, z, scale, groups, eps, dtype)


def _xla_gated_group_norm(y, z, scale, groups, eps, dtype):
    B, T, C = y.shape
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y = y.reshape(B, T, groups, C // groups)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y.reshape(B, T, C) * scale.astype(jnp.float32)).astype(dtype)
