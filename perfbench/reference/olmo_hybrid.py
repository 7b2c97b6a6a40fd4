"""Plain reference of the Olmo Hybrid decoder (allenai/Olmo-Hybrid-7B,
``model_type`` ``olmo_hybrid``): three Gated DeltaNet layers to one
full-attention layer, dense, a norm AFTER each sub-layer and none before.

Straightforward ``jax.numpy`` in float32, no kernels, no chunks, no padding,
no split exponents; written from ``config.json``'s keys and, where they say
nothing, from the Gated DeltaNet paper (arXiv:2412.06464) and the family's
conventions (the configuration file lists each such point under
``assumed``); independent of ``accl_tpu.models``, of ``accl_tpu.ops`` and of
the other references here (what it has in common with
``reference/solar_open2.py`` is a copy, not an import):

    h = embed_tokens[tokens]
    for each layer l (published index), x the residual stream ITSELF:
        layer_types[l] == "linear_attention", Gated DeltaNet, 30 heads,
        keys of 96, values of 192:
            q, k, v = silu(conv4(x q_proj)), silu(conv4(x k_proj)),
                      silu(conv4(x v_proj))     (causal, depthwise, 4 taps)
            q, k    = q / |q| * 96 ** -0.5, k / |k|            (L2, a head)
            g_t     = -exp(A_log) softplus(x_t a_proj + dt_bias)
                      ONE value a HEAD in (-inf, 0), a_proj 3840 x 30
            b_t     = 2 sigmoid(x_t b_proj)     a value a head, in (0, 2)
            S_t     = exp(g_t) (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
                      (96 x 192, S_0 = 0)
            o_t     = S_t^T q_t           TOKEN BY TOKEN (:func:`delta_recurrence`)
            y       = [RMSNorm_head(o; o_norm) * silu(x g_proj)] o_proj
        else full attention, NO position (rope_theta null):
            q, k    = RMSNorm(x q_proj; q_norm), RMSNorm(x k_proj; k_norm)
                      over the WHOLE 3,840 columns, then 30 heads of 128
            s       = q . k * 128 ** -0.5,  keys j <= i
            y       = [softmax(s) v] o_proj
        h = x + RMSNorm(y; post_attention_layernorm)
        h = h + RMSNorm(mlp(h); post_feedforward_layernorm)
            mlp(h)  = (silu(h gate_proj) * (h up_proj)) down_proj
    logits = RMSNorm(h; norm) @ lm_head                          (untied head)
    loss   = mean next-token NLL

Departures from the published code, none of which changes a value: a linear
weight is stored (in, out) and applied as ``x @ w``; q, k, v, the gate and
the two head-wise projections are six matrices, not two fused ones; a
convolution's taps are stored (tap, channel), the last tap the current
token's, one set a projection (one convolution over the concatenated
columns is the same thing); attention is computed in blocks of query rows
and the loss in blocks of rows (:func:`nll_from_hidden`: the float32 logits
of 8,192 x 100,352 are 3.3 GB and never whole here); a batch is a loop over
its sequences, and a caller short of memory runs :func:`layer` a layer at a
time (weights are upcast where they are used).

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
L2_EPS = 1e-6           # the L2 norm of q and k


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


silu = jax.nn.silu


def l2_norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + L2_EPS)


def short_conv(x, taps):
    """Causal depthwise convolution: ``x`` (T, C), ``taps`` (K, C), zero
    left padding; ``y_t = sum_i taps[i] x_{t - (K - 1) + i}``."""
    K = taps.shape[0]
    T = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(_f32(taps[i]) * padded[i:i + T] for i in range(K))


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule, a token at a time: ``q``, ``k`` (T, H, dk),
    ``v`` (T, H, dv), ``beta`` (T, H) and the log-decay ``g`` (T, H), one
    value a head, or (T, H, dk), one a channel (no layer of the model: the
    tests' way of telling a decay a channel apart); ``S_0 = 0``; returns
    ``o`` (T, H, dv).  Any ``g <= 0``: ``exp(g)`` underflows to 0 at worst."""
    H, dk = q.shape[1:]
    if g.ndim == 2:
        g = g[..., None]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S                  # the decay
        answered = jnp.einsum("hk,hkv->hv", k_t, S)      # S^T k
        S = S + b_t[:, None, None] * k_t[..., None] * (v_t - answered)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    # the same tokens in the same order, in stretches whose states are
    # computed again for the gradient rather than kept (one state is H x dk
    # x dv: 8,192 of them are 18 GB at the published widths)
    T = q.shape[0]
    stretch = math.gcd(T, 128)
    stretches = jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs))
    _, o = jax.lax.scan(
        stretches, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
        jax.tree.map(
            lambda x: x.reshape(T // stretch, stretch, *x.shape[1:]),
            (q, k, v, g, beta),
        ),
    )
    return o.reshape(T, H, -1)


def log_decay(x, lp):
    """The log-decay a head of one sequence ``x`` (T, d), (T, H):
    ``-exp(A_log) softplus(x a_proj + dt_bias)``."""
    a = x @ _f32(lp["a_proj"]) + _f32(lp["dt_bias"])
    return -jnp.exp(_f32(lp["A_log"])) * jax.nn.softplus(a)


def delta_attention(x, lp, *, n_head: int, beta_scale: float = 2.0,
                    no_decay: bool = False, no_conv: bool = False,
                    sigmoid_gate: bool = False, channel_spread: float = 0.0):
    """One sequence ``x`` (T, d) through the Gated DeltaNet mixer of a layer
    (``beta_scale`` 1: the write strength without its 2; ``no_decay``: the
    plain delta rule, ``g = 0``; ``no_conv``: the projections straight into
    the SiLU; ``sigmoid_gate``: the output gated by a sigmoid, KDA's;
    ``channel_spread``: a decay a CHANNEL, a head's ``g`` times ``1 +
    channel_spread * c`` for ``c`` from -1 to 1 over its key columns: ways of
    getting it wrong, for the tests and the chip's controls)."""
    T = x.shape[0]
    heads = lambda y: y.reshape(T, n_head, -1)

    def branch(proj, conv):
        y = x @ _f32(lp[proj])
        return heads(silu(y if no_conv else short_conv(y, lp[conv])))

    q, k, v = (branch(p + "_proj", p + "_conv1d") for p in "qkv")
    q = l2_norm(q) * q.shape[-1] ** -0.5
    k = l2_norm(k)
    g = log_decay(x, lp)
    if no_decay:
        g = jnp.zeros_like(g)
    if channel_spread:
        g = g[..., None] * (
            1.0 + channel_spread * jnp.linspace(-1.0, 1.0, k.shape[-1])
        )
    beta = beta_scale * jax.nn.sigmoid(x @ _f32(lp["b_proj"]))   # (T, H)
    o = rms_norm(delta_recurrence(q, k, v, g, beta), lp["o_norm"])
    gate = (jax.nn.sigmoid if sigmoid_gate else silu)(x @ _f32(lp["g_proj"]))
    return (o.reshape(T, -1) * gate) @ _f32(lp["o_proj"])


def gate_facts(g):
    """Of one layer's log-decays ``g`` (T, H): the quantiles 0, 0.01, 0.1,
    0.5, 0.9, 0.99, 1 of a token's, and the share of (run of 64 tokens,
    head) sums above -1 (a head that remembers across a chunk)."""
    T = g.shape[0] // 64 * 64
    chunks = g[:T].reshape(T // 64, 64, -1).sum(axis=1)
    return {
        "chunks_remembered": jnp.mean(chunks > -1.0),
        "quantiles": jnp.quantile(
            g.reshape(-1), jnp.array([0.0, 0.01, 0.1, 0.5, 0.9, 0.99, 1.0])
        ),
    }


def rope(x, theta: float):
    """x: (T, H, d), ``rotate_half``.  The model has NONE (``rope_theta``
    null): here for the control that turns it on."""
    T, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, scale: float, q_block: int):
    """q, k, v: (T, H, d); one sequence, query rows ``q_block`` at a time
    against all keys ``j <= i`` (a padded row past the end sees every key
    and is dropped)."""
    T, H, _ = q.shape
    cols = jnp.arange(T)

    @jax.checkpoint      # a block's scores again for the gradient, not kept
    def rows_from(start, qb):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(q_block)
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    # a loop, so that one block's scores are alive at a time forwards AND
    # backwards; a tail short of a block is padded with rows that are dropped
    blocks = -(-T // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - T), (0, 0), (0, 0)))
    _, out = jax.lax.scan(
        lambda _, x: (None, rows_from(*x)), None,
        (jnp.arange(blocks) * q_block, q.reshape(blocks, q_block, H, -1)),
    )
    return out.reshape(blocks * q_block, H, -1)[:T]


def full_attention(x, lp, *, n_head: int, q_block: int,
                   no_qk_norm: bool = False, rope_theta=None):
    """One sequence ``x`` (T, d) through a full-attention layer: QK-norm
    over the whole projection, causal softmax without position
    (``no_qk_norm`` leaves the two norms out, ``rope_theta`` rotates q and
    k: two ways of getting it wrong)."""
    T = x.shape[0]
    q, k = x @ _f32(lp["q_proj"]), x @ _f32(lp["k_proj"])
    if not no_qk_norm:
        q, k = rms_norm(q, lp["q_norm"]), rms_norm(k, lp["k_norm"])
    q, k = q.reshape(T, n_head, -1), k.reshape(T, n_head, -1)
    v = (x @ _f32(lp["v_proj"])).reshape(T, n_head, -1)
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    out = causal_attention(q, k, v, q.shape[-1] ** -0.5, q_block)
    return out.reshape(T, -1) @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def layer(h, lp, *, n_head: int, q_block: int = 512, pre_norm: bool = False,
          delta_how=None, full_how=None):
    """The residual stream ``h`` (B, T, d) through one layer: the Gated
    DeltaNet mixer where its weights have an ``A_log``, else full attention;
    a norm AFTER each sub-layer (``pre_norm``: the same two scales BEFORE
    the sub-layers instead, the usual block: a way of getting it wrong)."""
    B = h.shape[0]
    if "A_log" in lp:
        mix = lambda x: delta_attention(x, lp, n_head=n_head, **(delta_how or {}))
    else:
        mix = lambda x: full_attention(
            x, lp, n_head=n_head, q_block=q_block, **(full_how or {})
        )
    mlp = lambda m: gated_mlp(m, lp["gate_proj"], lp["up_proj"], lp["down_proj"])
    mixed = lambda x: jnp.stack([mix(x[b]) for b in range(B)])
    n1, n2 = lp["post_attention_layernorm"], lp["post_feedforward_layernorm"]
    if pre_norm:
        h = h + mixed(rms_norm(h, n1))
        return h + mlp(rms_norm(h, n2))
    h = h + rms_norm(mixed(h), n1)
    return h + rms_norm(mlp(h), n2)


def layer_gate_facts(h, lp):
    """:func:`gate_facts` of a Gated DeltaNet layer's log-decays on the
    first sequence of the stream ``h`` (B, T, d) that enters it."""
    return gate_facts(log_decay(h[0], lp))


def embed(weights: dict, tokens):
    return _f32(weights["embed_tokens"][tokens])


def hidden(weights: dict, tokens, **model):
    """``tokens`` (B, T) through the layers: the residual stream (B, T, d)
    before the final norm."""
    h = embed(weights, tokens)
    for lp in weights["layers"]:
        h = layer(h, lp, **model)
    return h


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets, rows: int = 1024):
    """Mean next-token NLL of the stream ``h`` (B, T, d), ``rows`` rows of
    logits at a time (computed again for the gradient, not kept: 8,192 x
    100,352 float32 logits, their log-softmax and its gradient do not fit
    beside the head)."""
    d = h.shape[-1]
    h, targets = h.reshape(-1, d), targets.reshape(-1)
    n = h.shape[0]
    rows = math.gcd(n, rows)

    @jax.checkpoint
    def block(weights, h, targets):
        logp = jax.nn.log_softmax(head(weights, h), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    total, _ = jax.lax.scan(
        lambda acc, x: (acc + block(weights, *x), None), jnp.zeros((), jnp.float32),
        (h.reshape(n // rows, rows, d), targets.reshape(n // rows, rows)),
    )
    return total / n


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL.  ``jax.grad`` of it gives the reference gradients."""
    return nll_from_hidden(weights, hidden(weights, tokens, **model), targets)
