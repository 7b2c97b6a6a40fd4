"""Plain reference of the Ling-3.0 hybrid decoder (inclusionAI/Ling-3.0-flash,
``model_type`` ``bailing_hybrid``), as ONE chip of its 8-way expert-parallel
group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no chunks, no sort, no
buffer; written from ``config.json``'s keys and, where they say nothing, from
the Kimi Delta Attention paper (arXiv:2510.26692) and the family's
conventions (the configuration file lists each such point under ``assumed``);
independent of ``accl_tpu.models`` and ``accl_tpu.ops``:

    h = embed_tokens[tokens]
    for each layer l:
        a = RMSNorm(h; input_layernorm)                            (eps 1e-6)
        (l + 1) % layer_group_size != 0, the KDA mixer, a head of 128:
            q, k, v = silu(conv4(a q_proj)), silu(conv4(a k_proj)),
                      silu(conv4(a v_proj))     (causal, depthwise, 4 taps)
            q, k    = q / |q| * 128 ** -0.5, k / |k|           (L2, a head)
            g_t     = lower_bound * sigmoid(exp(A_log) (a_t f_proj + dt_bias))
                                         a value a CHANNEL, in [-5, 0)
            b_t     = sigmoid(a_t b_proj)                 a value a head
            S_t     = (I - b_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
            o_t     = S_t^T q_t              TOKEN BY TOKEN (:func:`kda_recurrence`)
            y       = [RMSNorm_head(o; o_norm) * sigmoid(a g_proj)] o_proj
        else the latent mixer (no position in KDA; rope here only):
            q       = a q_proj -> (T, 32, 128 + 64) = [q_n | q_r]
            [c | k_r] = a kv_a_proj_with_mqa    (T, 512 + 64), k_r ONE head
            [k_n | v] = RMSNorm(c; kv_a_layernorm) kv_b_proj
            q_r, k_r = rope(q_r), rope(k_r)       (theta 6e6, rotate_half)
            s       = (q_n . k_n + q_r . k_r) * 192 ** -0.5,  keys j <= i
            y       = [softmax(s) v * sigmoid(a g_proj)_head] o_proj
        h = h + y
        m = RMSNorm(h; post_attention_layernorm)
        l < first_k_dense_replace:
            f = (silu(m gate_proj) * (m up_proj)) down_proj       (width 6144)
        else (``noaux_tc``):
            s    = sigmoid(m @ gate) over ALL 512 experts, float32
            g_d  = the SUM of the two largest (s + bias) of group d's 64
            sel  = top8(s + bias inside the 4 best of the 8 groups)
            w    = 2.5 * s[sel] / sum(s[sel])           (without the bias)
            f    = shared(m) + sum_{e in sel, e held} w_e expert_e(m)
        h = h + f
    logits = RMSNorm(h; norm) @ lm_head                          (untied head)
    loss   = mean next-token NLL                     (no auxiliary loss term)

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 512 (a whole routing group on this
chip); the router, its groups, its top 8 and the weights are over all of
them, and what an expert that is not held would have added is left out (the
model-configs guide, section 4).  With all of them held this is the whole
model.

Departures from the published code, none of which changes a value: a linear
weight is stored (in, out) and applied as ``x @ w``; a convolution's taps
are stored (tap, channel), the last tap the current token's; the rope
columns are rotated as two halves (``rope_interleave`` is a relabelling of
columns on seeded weights); the held experts are stacked on a leading axis
and every held expert is applied to EVERY token under a dense (tokens,
held) weight mask, in a plain loop; attention is computed in blocks of
query rows; a batch is a loop over its sequences, and a caller short of
memory runs :func:`layer` a layer at a time (weights are upcast where they
are used).

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
L2_EPS = 1e-6           # KDA's L2 norm of q and k


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


silu = jax.nn.silu       # hidden_act, linear_silu


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
    H, dv)."""
    H, dk = q.shape[1:]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S                  # diag(exp(g)) S
        answered = jnp.einsum("hk,hkv->hv", k_t, S)      # S^T k
        S = S + b_t[:, None, None] * k_t[..., None] * (v_t - answered)[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    _, o = jax.lax.scan(
        token, jnp.zeros((H, dk, v.shape[-1]), jnp.float32),
        (q, k, v, g, beta),
    )
    return o


def kda_attention(a, lp, *, n_head: int, kda_lower_bound: float,
                  no_decay: bool = False, no_conv: bool = False):
    """One sequence ``a`` (T, d) through the KDA mixer of a layer
    (``no_decay``: the plain delta rule, ``g = 0``; ``no_conv``: the
    projections straight into the SiLU: two ways of getting it wrong, for
    the tests and the chip's controls)."""
    T = a.shape[0]
    heads = lambda x: x.reshape(T, n_head, -1)

    def branch(proj, conv):
        x = a @ _f32(lp[proj])
        return heads(silu(x if no_conv else short_conv(x, lp[conv])))

    q, k, v = (branch(p + "_proj", p + "_conv1d") for p in "qkv")
    q = l2_norm(q) * q.shape[-1] ** -0.5
    k = l2_norm(k)
    f = heads(a @ _f32(lp["f_proj"]) + _f32(lp["dt_bias"]))
    g = kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(_f32(lp["A_log"]))[None, :, None] * f
    )
    if no_decay:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(a @ _f32(lp["b_proj"]))        # (T, H)
    o = rms_norm(kda_recurrence(q, k, v, g, beta), lp["o_norm"])
    gate = jax.nn.sigmoid(a @ _f32(lp["g_proj"]))
    return (o.reshape(T, -1) * gate) @ _f32(lp["o_proj"])


def rope(x, theta: float):
    """x: (T, H, dr).  ``rotate_half``: the two HALVES of the columns pair
    up; no scaling (``rope_scaling`` null)."""
    T, _, dr = x.shape
    inv_freq = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, scale: float, q_block: int):
    """q, k: (T, H, dqk); v: (T, H, dv); one sequence, query rows
    ``q_block`` at a time against all keys ``j <= i``."""
    T = q.shape[0]
    cols = jnp.arange(T)
    out = []
    for start in range(0, T, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(qb.shape[0])
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out, axis=0)


def latent_attention(a, lp, *, n_head: int, qk_nope_head_dim: int,
                     qk_rope_head_dim: int, v_head_dim: int,
                     kv_lora_rank: int, rope_theta: float, q_block: int,
                     no_gate: bool = False):
    """One sequence ``a`` (T, d) through the latent mixer of a layer: q
    straight from the hidden state (``q_lora_rank`` null), a head-wise
    sigmoid gate on the output (``no_gate`` leaves it out, for the
    tests)."""
    T = a.shape[0]
    dn, dr = qk_nope_head_dim, qk_rope_head_dim
    q = (a @ _f32(lp["q_proj"])).reshape(T, n_head, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    ckv = a @ _f32(lp["kv_a_proj_with_mqa"])
    k_r = ckv[:, kv_lora_rank:].reshape(T, 1, dr)
    kv = rms_norm(ckv[:, :kv_lora_rank], lp["kv_a_layernorm"]) @ _f32(
        lp["kv_b_proj"]
    )
    kv = kv.reshape(T, n_head, dn + v_head_dim)
    k_n, v = kv[..., :dn], kv[..., dn:]
    q_r, k_r = rope(q_r, rope_theta), rope(k_r, rope_theta)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (T, n_head, dr))], axis=-1)
    out = causal_attention(q, k, v, (dn + dr) ** -0.5, q_block)
    if not no_gate:
        out = out * jax.nn.sigmoid(a @ _f32(lp["g_proj"]))[:, :, None]
    return out.reshape(T, n_head * v_head_dim) @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def kept_groups(pick, n_group: int, topk_group: int, group_max: bool = False):
    """(tokens, n_group) 1/0 and the groups' scores: the ``topk_group``
    groups of largest score, a group's score the SUM of the two largest
    ``pick`` of its consecutive experts (``group_max``: the largest alone,
    DeepSeek-V2's rule and here a way of getting it wrong)."""
    N, E = pick.shape
    members = pick.reshape(N, n_group, E // n_group)
    if group_max:
        score = members.max(axis=-1)
    else:
        score = jax.lax.top_k(members, 2)[0].sum(axis=-1)
    _, keep = jax.lax.top_k(score, topk_group)
    return jnp.sum(jax.nn.one_hot(keep, n_group, dtype=pick.dtype), axis=1), score


def route(scores, bias, top_k: int, n_group: int, topk_group: int,
          scale: float, biased_weights: bool = False, **how):
    """The (tokens, E) weight of every expert for every token under
    ``noaux_tc``: the choice on ``scores + bias`` inside the kept groups,
    the weights from ``scores`` alone, divided by their sum, times
    ``scale`` (``biased_weights``: a way of getting it wrong)."""
    N, E = scores.shape
    pick = scores + _f32(bias)
    kept, _ = kept_groups(pick, n_group, topk_group, **how)
    inside = jnp.repeat(kept, E // n_group, axis=1) > 0
    _, top_e = jax.lax.top_k(jnp.where(inside, pick, -jnp.inf), top_k)
    w = jnp.take_along_axis(pick if biased_weights else scores, top_e, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(top_e, E, dtype=w.dtype))


def moe(m, lp, *, top_k: int, n_group: int, topk_group: int,
        routed_scaling_factor: float, first_expert: int = 0,
        shared: bool = True, **how):
    """``m`` (N, d) through the sparse MLP of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    scores + bias over all experts)``.  ``shared=False`` leaves the shared
    expert out (for the sum over the shares)."""
    scores = jax.nn.sigmoid(m @ _f32(lp["gate"]))
    weights = route(scores, lp["expert_bias"], top_k, n_group, topk_group,
                    routed_scaling_factor, **how)
    held = lp["experts.gate_proj"].shape[0]
    weights = weights[:, first_expert:first_expert + held]

    def one_expert(acc, xs):
        gate_proj, up_proj, down_proj, w = xs
        return acc + w[:, None] * gated_mlp(m, gate_proj, up_proj, down_proj), None

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


_LATENT = ("n_head", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
           "kv_lora_rank", "rope_theta")


def layer(h, lp, *, q_block: int = 512, moe_how=None, kda_how=None,
          latent_how=None, **model):
    """The residual stream ``h`` (B, T, d) through one layer: the KDA
    mixer where its weights have an ``A_log``, else the latent one; dense
    where they have no ``gate``.  Returns ``(h, the router's scores + bias
    (B*T, E) or None)``."""
    B, T, _ = h.shape
    a = rms_norm(h, lp["input_layernorm"])
    if "A_log" in lp:
        mix = lambda x: kda_attention(
            x, lp, n_head=model["n_head"],
            kda_lower_bound=model["kda_lower_bound"], **(kda_how or {}),
        )
    else:
        mix = lambda x: latent_attention(
            x, lp, q_block=q_block, **{k: model[k] for k in _LATENT},
            **(latent_how or {}),
        )
    h = h + jnp.stack([mix(a[b]) for b in range(B)])
    m = rms_norm(h, lp["post_attention_layernorm"]).reshape(B * T, -1)
    if "gate" not in lp:
        f = gated_mlp(m, lp["mlp.gate_proj"], lp["mlp.up_proj"],
                      lp["mlp.down_proj"])
        return h + f.reshape(h.shape), None
    f, picked = moe(
        m, lp, top_k=model["top_k"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scaling_factor=model["routed_scaling_factor"],
        first_expert=model.get("first_expert", 0), **(moe_how or {}),
    )
    return h + f.reshape(h.shape), picked


def embed(weights: dict, tokens):
    return _f32(weights["embed_tokens"][tokens])


def hidden(weights: dict, tokens, **model):
    """``tokens`` (B, T) through the layers: the residual stream (B, T, d)
    before the final norm, and each EXPERT layer's ``scores + bias``."""
    h = embed(weights, tokens)
    picked = []
    for lp in weights["layers"]:
        h, layer_picked = layer(h, lp, **model)
        if layer_picked is not None:
            picked.append(layer_picked)
    return h, picked


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL (``noaux_tc`` adds no term; the prediction module's is
    times ``mtp_loss_scaling_factor`` 0).  ``jax.grad`` of it gives the
    reference gradients."""
    h, _ = hidden(weights, tokens, **model)
    return nll_from_hidden(weights, h, targets)


def moved_bias(bias, counts, rate: float):
    """The bias after a step that sent ``counts`` tokens to each expert:
    towards the experts that got fewer than the mean."""
    c = _f32(counts)
    return _f32(bias) + rate * jnp.sign(jnp.mean(c) - c)


def routing_facts(picked, top_k: int, n_group: int, topk_group: int):
    """From one layer's ``scores + bias`` (N, E), by the same rule as
    :func:`route`: tokens an expert (E,); tokens whose kept groups include
    each group (n_group,); and a token's distance from a tie in bf16
    spacings (2^-8) of the layer's score RMS, the smaller of the gap
    between its ``topk_group``-th and next GROUP score and the gap between
    its ``top_k``-th and next expert among the kept."""
    N, E = picked.shape
    per = E // n_group
    _, group = kept_groups(picked, n_group, topk_group)
    top_g, keep = jax.lax.top_k(group, topk_group + 1)
    kept = jnp.sum(
        jax.nn.one_hot(keep[:, :topk_group], n_group, dtype=jnp.int32), axis=1
    )
    masked = jnp.where(jnp.repeat(kept, per, axis=1) > 0, picked, -jnp.inf)
    top, top_e = jax.lax.top_k(masked, top_k + 1)
    counts = jnp.sum(
        jax.nn.one_hot(top_e[:, :top_k], E, dtype=jnp.int32), axis=(0, 1)
    )
    spacing = 2.0 ** -8 * jnp.sqrt(jnp.mean(picked ** 2))
    gap = jnp.minimum(
        top_g[:, topk_group - 1] - top_g[:, topk_group],
        top[:, top_k - 1] - top[:, top_k],
    ) / spacing
    return counts, kept.sum(axis=0), gap
