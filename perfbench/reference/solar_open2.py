"""Plain reference of the Solar Open 2 hybrid decoder (upstage/Solar-Open2-250B,
``model_type`` ``solar_open2``, 250B-A15B), as ONE chip of its 8-way
expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no chunks, no split
exponents, no sort, no buffer; written from ``config.json``'s keys and, where
they say nothing, from the Kimi Delta Attention paper (arXiv:2510.26692) and
the family's conventions (the configuration file lists each such point under
``assumed``); independent of ``accl_tpu.models``, of ``accl_tpu.ops`` and of
the other references here (what it has in common with
``reference/bailing_hybrid.py`` is a copy, not an import):

    h = embed_tokens[tokens]
    for each layer l (published index):
        u = RMSNorm(h; input_layernorm)                            (eps 1e-5)
        l not in gqa_layers, the KDA mixer, 64 heads of 128:
            q, k, v = silu(conv4(u q_proj)), silu(conv4(u k_proj)),
                      silu(conv4(u v_proj))     (causal, depthwise, 4 taps)
            q, k    = q / |q| * 128 ** -0.5, k / |k|           (L2, a head)
            g_t     = -exp(A_log) softplus(u_t f_a_proj f_b_proj + dt_bias)
                      a value a CHANNEL in (-inf, 0): NO lower bound
            b_t     = 2 sigmoid(u_t b_proj)     a value a head, in (0, 2)
            S_t     = (I - b_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
            o_t     = S_t^T q_t              TOKEN BY TOKEN (:func:`kda_recurrence`)
            y       = [RMSNorm_head(o; o_norm) * sigmoid(u g_a_proj g_b_proj)] o_proj
        else grouped-query softmax attention, NO position (use_rope false):
            q       = u q_proj -> (T, 64, 128); k, v = u k_proj, u v_proj
                      -> (T, 8, 128), a KV head for 8 query heads
            s       = q . k * 128 ** -0.5,  keys j <= i
            y       = [softmax(s) v * sigmoid(u g_proj)] o_proj   (a channel)
        h = h + y
        m = RMSNorm(h; post_attention_layernorm)
        every layer (first_k_dense_replace 0):
            s    = sigmoid(m @ gate) over ALL 320 experts, float32
            sel  = top8(s + bias)                         (no group limit)
            w    = 1 * s[sel] / sum(s[sel])               (without the bias)
            f    = shared(m) + sum_{e in sel, e held} w_e expert_e(m)
        h = h + f
    logits = RMSNorm(h; norm) @ lm_head                          (untied head)
    loss   = mean next-token NLL                     (no auxiliary loss term)

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 320; the router, its top 8 and the
weights are over all of them, and what an expert that is not held would
have added is left out (the model-configs guide, section 4).  With all of
them held this is the whole model.

Departures from the published code, none of which changes a value: a linear
weight is stored (in, out) and applied as ``x @ w``; a convolution's taps
are stored (tap, channel), the last tap the current token's; the held
experts are stacked on a leading axis and every held expert is applied to
EVERY token under a dense (tokens, held) weight mask, in a plain loop;
attention is computed in blocks of query rows; a batch is a loop over its
sequences, and a caller short of memory runs :func:`layer` a layer at a time
(weights are upcast where they are used).

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5          # rms_norm_eps
L2_EPS = 1e-6           # KDA's L2 norm of q and k


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


def kda_recurrence(q, k, v, g, beta):
    """The gated delta rule, a token at a time: ``q``, ``k``, ``g`` (T, H,
    dk), ``v`` (T, H, dv), ``beta`` (T, H); ``S_0 = 0``; returns ``o`` (T,
    H, dv).  Any ``g <= 0``: ``exp(g)`` underflows to 0 at worst."""
    H, dk = q.shape[1:]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S                  # diag(exp(g)) S
        answered = jnp.einsum("hk,hkv->hv", k_t, S)      # S^T k
        S = S + b_t[:, None, None] * k_t[..., None] * (v_t - answered)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    # the same tokens in the same order, in stretches whose states are
    # computed again for the gradient rather than kept (one state is H x dk
    # x dv: 8,192 of them are 34 GB at the published widths)
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


def log_decay(u, lp, *, n_head: int, bounded_gate=None):
    """The log-decay a channel of one sequence ``u`` (T, d), (T, H, dk):
    ``-exp(A_log) softplus(u f_a_proj f_b_proj + dt_bias)``
    (``bounded_gate``: a lower bound, Ling-3.0's ``bound * sigmoid(exp(A_log)
    .)`` in its place, a way of getting it wrong)."""
    f = (u @ _f32(lp["f_a_proj"])) @ _f32(lp["f_b_proj"]) + _f32(lp["dt_bias"])
    f = f.reshape(u.shape[0], n_head, -1)
    rate = jnp.exp(_f32(lp["A_log"]))[None, :, None]
    if bounded_gate is not None:
        return bounded_gate * jax.nn.sigmoid(rate * f)
    return -rate * jax.nn.softplus(f)


def kda_attention(u, lp, *, n_head: int, beta_scale: float = 2.0,
                  bounded_gate=None, no_decay: bool = False,
                  no_conv: bool = False):
    """One sequence ``u`` (T, d) through the KDA mixer of a layer
    (``beta_scale`` 1: the write strength without its 2; ``bounded_gate``:
    :func:`log_decay`'s; ``no_decay``: the plain delta rule, ``g = 0``;
    ``no_conv``: the projections straight into the SiLU: ways of getting it
    wrong, for the tests and the chip's controls)."""
    T = u.shape[0]
    heads = lambda x: x.reshape(T, n_head, -1)

    def branch(proj, conv):
        x = u @ _f32(lp[proj])
        return heads(silu(x if no_conv else short_conv(x, lp[conv])))

    q, k, v = (branch(p + "_proj", p + "_conv1d") for p in "qkv")
    q = l2_norm(q) * q.shape[-1] ** -0.5
    k = l2_norm(k)
    g = log_decay(u, lp, n_head=n_head, bounded_gate=bounded_gate)
    if no_decay:
        g = jnp.zeros_like(g)
    beta = beta_scale * jax.nn.sigmoid(u @ _f32(lp["b_proj"]))   # (T, H)
    o = rms_norm(kda_recurrence(q, k, v, g, beta), lp["o_norm"])
    gate = jax.nn.sigmoid((u @ _f32(lp["g_a_proj"])) @ _f32(lp["g_b_proj"]))
    return (o.reshape(T, -1) * gate) @ _f32(lp["o_proj"])


def gate_facts(g, sub: int = 16):
    """Of one layer's log-decays ``g`` (T, H, dk): the share of (token,
    channel) values under -5 (Ling-3.0's bound), the share of (run of
    ``sub`` tokens, channel) sums under -88 (where ``exp`` of the sum leaves
    float32: what a split at a sub-block's middle cannot take), the share of
    (run of 64 tokens, channel) sums above -1 (a channel that remembers
    across a chunk), and the quantiles 0, 0.01, 0.1, 0.5, 0.9, 0.99, 1."""
    T = g.shape[0] // 64 * 64
    runs = lambda n: g[:T].reshape(T // n, n, -1).sum(axis=1)
    return {
        "under_bound": jnp.mean(g < -5.0),
        "sub_blocks_past_float32": jnp.mean(runs(sub) < -88.0),
        "chunks_remembered": jnp.mean(runs(64) > -1.0),
        "quantiles": jnp.quantile(
            g.reshape(-1), jnp.array([0.0, 0.01, 0.1, 0.5, 0.9, 0.99, 1.0])
        ),
    }


def rope(x, theta: float):
    """x: (T, H, d), ``rotate_half``.  The model has NONE (``use_rope``
    false): here for the control that turns it on."""
    T, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, scale: float, q_block: int):
    """q: (T, H, d); k, v: (T, Hkv, d), a KV head for ``H / Hkv`` query
    heads in a row; one sequence, query rows ``q_block`` at a time against
    all keys ``j <= i`` (a padded row past the end sees every key and is
    dropped)."""
    T, H, _ = q.shape
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    cols = jnp.arange(T)

    @jax.checkpoint      # a block's scores again for the gradient, not kept
    def rows_from(start, qb):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(q_block)
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    # a loop, so that one block's scores are alive at a time forwards AND
    # backwards (sixteen blocks of (64, 512, 8192) float32 side by side are
    # 16 GB); a tail short of a block is padded with rows that are dropped
    blocks = -(-T // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - T), (0, 0), (0, 0)))
    _, out = jax.lax.scan(
        lambda _, x: (None, rows_from(*x)), None,
        (jnp.arange(blocks) * q_block, q.reshape(blocks, q_block, H, -1)),
    )
    return out.reshape(blocks * q_block, H, -1)[:T]


def gqa_attention(u, lp, *, n_head: int, n_kv_head: int, q_block: int,
                  no_gate: bool = False, rope_theta=None):
    """One sequence ``u`` (T, d) through a softmax layer: grouped-query
    causal attention without position, the output gated a channel by
    ``sigmoid(u g_proj)`` (``no_gate`` leaves the gate out, ``rope_theta``
    rotates q and k: two ways of getting it wrong)."""
    T = u.shape[0]
    q = (u @ _f32(lp["q_proj"])).reshape(T, n_head, -1)
    k = (u @ _f32(lp["k_proj"])).reshape(T, n_kv_head, -1)
    v = (u @ _f32(lp["v_proj"])).reshape(T, n_kv_head, -1)
    if rope_theta is not None:
        q, k = rope(q, rope_theta), rope(k, rope_theta)
    out = causal_attention(q, k, v, q.shape[-1] ** -0.5, q_block).reshape(T, -1)
    if not no_gate:
        out = out * jax.nn.sigmoid(u @ _f32(lp["g_proj"]))
    return out @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def route(scores, bias, top_k: int, scale: float, biased_weights: bool = False):
    """The (tokens, E) weight of every expert for every token: the choice on
    ``scores + bias`` over all the experts, the weights from ``scores``
    alone, divided by their sum, times ``scale`` (``biased_weights``: a way
    of getting it wrong)."""
    E = scores.shape[1]
    pick = scores + _f32(bias)
    _, top_e = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(pick if biased_weights else scores, top_e, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(top_e, E, dtype=w.dtype))


def moe(m, lp, *, top_k: int, routed_scaling_factor: float,
        first_expert: int = 0, shared: bool = True, **how):
    """``m`` (N, d) through the sparse MLP of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    scores + bias over all experts)``.  ``shared=False`` leaves the shared
    expert out (for the sum over the shares)."""
    scores = jax.nn.sigmoid(m @ _f32(lp["gate"]))
    weights = route(scores, lp["expert_bias"], top_k, routed_scaling_factor,
                    **how)
    held = lp["experts.gate_proj"].shape[0]
    weights = weights[:, first_expert:first_expert + held]

    @jax.checkpoint       # its hidden rows again for the gradient, not kept
    def expert(m, gate_proj, up_proj, down_proj, w):
        return w[:, None] * gated_mlp(m, gate_proj, up_proj, down_proj)

    def one_expert(acc, xs):
        return acc + expert(m, *xs), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (lp["experts.gate_proj"], lp["experts.up_proj"],
         lp["experts.down_proj"], weights.T),
    )
    if shared:
        out = out + gated_mlp(
            m, lp["shared_experts.gate_proj"], lp["shared_experts.up_proj"],
            lp["shared_experts.down_proj"],
        )
    return out, scores + _f32(lp["expert_bias"])


def layer(h, lp, *, q_block: int = 512, moe_how=None, kda_how=None,
          gqa_how=None, **model):
    """The residual stream ``h`` (B, T, d) through one layer: the KDA mixer
    where its weights have an ``A_log``, else the softmax one.  Returns
    ``(h, the router's scores + bias (B*T, E))``."""
    B, T, _ = h.shape
    u = rms_norm(h, lp["input_layernorm"])
    if "A_log" in lp:
        mix = lambda x: kda_attention(
            x, lp, n_head=model["n_head"], **(kda_how or {}),
        )
    else:
        mix = lambda x: gqa_attention(
            x, lp, n_head=model["n_head"], n_kv_head=model["n_kv_head"],
            q_block=q_block, **(gqa_how or {}),
        )
    h = h + jnp.stack([mix(u[b]) for b in range(B)])
    m = rms_norm(h, lp["post_attention_layernorm"]).reshape(B * T, -1)
    f, picked = moe(
        m, lp, top_k=model["top_k"],
        routed_scaling_factor=model["routed_scaling_factor"],
        first_expert=model.get("first_expert", 0), **(moe_how or {}),
    )
    return h + f.reshape(h.shape), picked


def layer_gate_facts(h, lp, *, n_head: int):
    """:func:`gate_facts` of a KDA layer's log-decays on the first sequence
    of the stream ``h`` (B, T, d) that enters it."""
    u = rms_norm(h[0], lp["input_layernorm"])
    return gate_facts(log_decay(u, lp, n_head=n_head))


def embed(weights: dict, tokens):
    return _f32(weights["embed_tokens"][tokens])


def hidden(weights: dict, tokens, **model):
    """``tokens`` (B, T) through the layers: the residual stream (B, T, d)
    before the final norm, and each layer's ``scores + bias``."""
    h = embed(weights, tokens)
    picked = []
    for lp in weights["layers"]:
        h, layer_picked = layer(h, lp, **model)
        picked.append(layer_picked)
    return h, picked


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL (the sigmoid router adds no term).  ``jax.grad`` of it
    gives the reference gradients."""
    h, _ = hidden(weights, tokens, **model)
    return nll_from_hidden(weights, h, targets)


def moved_bias(bias, counts, rate: float):
    """The bias after a step that sent ``counts`` tokens to each expert:
    towards the experts that got fewer than the mean."""
    c = _f32(counts)
    return _f32(bias) + rate * jnp.sign(jnp.mean(c) - c)


def routing_facts(picked, top_k: int):
    """From one layer's ``scores + bias`` (N, E): tokens an expert (E,), and
    a token's distance from a tie in bf16 spacings (2^-8) of the layer's
    score RMS, the gap between its ``top_k``-th and next expert."""
    E = picked.shape[1]
    top, top_e = jax.lax.top_k(picked, top_k + 1)
    counts = jnp.sum(
        jax.nn.one_hot(top_e[:, :top_k], E, dtype=jnp.int32), axis=(0, 1)
    )
    spacing = 2.0 ** -8 * jnp.sqrt(jnp.mean(picked ** 2))
    return counts, (top[:, top_k - 1] - top[:, top_k]) / spacing
