"""Plain reference of the afmoe decoder (arcee-ai/Trinity-Mini), as ONE
chip of an expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no sort,
no buffer; written from ``config.json``'s keys and, where they say
nothing, from memory of ``transformers``' ``modeling_afmoe.py`` and of
Arcee's Trinity report (the configuration file lists each such point
under ``assumed``); independent of ``accl_tpu.models``:

    h = embed_tokens[tokens] * sqrt(hidden_size)            (mup_enabled)
    for each layer l:
        a    = RMSNorm(h; input_layernorm)                    (eps 1e-5)
        q, k, v, g = a @ q_proj, a @ k_proj, a @ v_proj, a @ gate_proj
        q, k = RMSNorm over EACH head's 128 (one scale for q, one for k)
        sliding layers: q, k = rope(q), rope(k)   (theta 10000, rotate_half)
        full layers:    no position at all
        s    = q k^T / sqrt(128), query head i on KV head i // 8
        mask = 0 <= i - j < 2048 on sliding layers, i >= j on full ones
        o    = (softmax(s) v * sigmoid(g)) @ o_proj
        h    = h + RMSNorm(o; post_attention_layernorm)
        m    = RMSNorm(h; pre_mlp_layernorm)
        l < num_dense_layers:
            f = (silu(m gate_proj) * (m up_proj)) down_proj    (width 6144)
        else:
            s   = sigmoid(m @ router) over ALL experts, float32
            sel = top8(s + expert_bias)
            w   = s[sel];  w = w / (sum w + 1e-20);  w = 2.826 * w
            f   = shared(m) + sum_{e in sel, e held} w_e expert_e(m)
        h    = h + RMSNorm(f; post_mlp_layernorm)
    logits = RMSNorm(h; norm) @ lm_head                      (untied head)

    loss = mean next-token NLL, no auxiliary term
    after a step: expert_bias += 0.001 * sign(mean(c) - c),  c = tokens an
    expert of the step, over all experts

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's ``num_experts``; the router, its
top 8 and the weights are over all of them, and what an expert that is
not held would have added is left out (the model-configs guide, section
4).  With all of them held this is the whole model.

Departures from the published code, none of which changes a value:

* a linear weight is stored (in, out) and applied as ``x @ w``;
* the held experts' matrices are stacked on a leading axis and every held
  expert is applied to EVERY token under a dense (tokens, held) weight
  mask that is zero outside a token's top 8, in a plain loop;
* attention is computed in blocks of query rows against the whole context
  so that T=8192 fits beside the weights;
* a batch is a loop over its sequences.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5          # rms_norm_eps
ROPE_THETA = 10000.0    # rope_theta
ROUTE_EPS = 1e-20       # the renormalisation's guard
BIAS_RATE = 0.001       # load_balance_coeff


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


def qk_norm(x, weight):
    """RMSNorm over each head's width: ``x`` is (T, H, hd), ``weight``
    (hd,)."""
    return rms_norm(x, weight)


def post_norm(x, weight):
    """The norm on a half's OUTPUT, before the residual add."""
    return rms_norm(x, weight)


silu = jax.nn.silu       # hidden_act
gate_fn = jax.nn.sigmoid  # the attention output's gate


def rope(x):
    """x: (T, H, hd).  ``rotate_half``: the two HALVES of a head pair up."""
    T, _, hd = x.shape
    inv_freq = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def masked_attention(q, k, v, window, q_block: int):
    """q: (T, H, hd); k, v: (T, Hkv, hd), query head i on KV head
    ``i // (H // Hkv)``; one sequence, query rows ``q_block`` at a time
    against all keys.  ``window``: keys ``0 <= i - j < window`` (the
    query's own among them), or ``None`` for every ``j <= i``."""
    T, H, hd = q.shape
    groups = H // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    scale = 1.0 / math.sqrt(hd)
    cols = jnp.arange(T)
    out = []
    for start in range(0, T, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(qb.shape[0])
        dist = rows[:, None] - cols[None, :]
        mask = dist >= 0
        if window is not None:
            mask &= dist < window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out, axis=0)


def attention(a, lp, *, n_head: int, n_kv_head: int, sliding: bool,
              window: int, q_block: int):
    """One sequence ``a`` (T, d) through the attention half of a layer."""
    T = a.shape[0]
    q = (a @ _f32(lp["q_proj"])).reshape(T, n_head, -1)
    k = (a @ _f32(lp["k_proj"])).reshape(T, n_kv_head, -1)
    v = (a @ _f32(lp["v_proj"])).reshape(T, n_kv_head, -1)
    q, k = qk_norm(q, lp["q_norm"]), qk_norm(k, lp["k_norm"])
    if sliding:
        q, k = rope(q), rope(k)
    out = masked_attention(q, k, v, window if sliding else None, q_block)
    out = out.reshape(T, -1) * gate_fn(a @ _f32(lp["gate_proj"]))
    return out @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def route(scores, bias, top_k: int, route_norm: bool, route_scale: float,
          biased_weights: bool = False):
    """The (tokens, E) weight of every expert for every token: selection
    on ``scores + bias``, weights from the UNBIASED scores of the chosen
    (``biased_weights`` is a way of getting it wrong, for the tests),
    renormalised and scaled; zero outside a token's ``top_k``."""
    picked = scores + _f32(bias)
    _, top_e = jax.lax.top_k(picked, top_k)
    w = jnp.take_along_axis(picked if biased_weights else scores, top_e, -1)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    w = w * route_scale
    onehot = jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("nk,nke->ne", w, onehot)


def moe(m, lp, *, top_k: int, route_norm: bool, route_scale: float,
        first_expert: int = 0, shared: bool = True, **how):
    """``m`` (N, d) through the sparse MLP of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    scores + bias over all experts)``.  ``shared=False`` leaves the
    shared expert out (for the sum over the shares)."""
    scores = jax.nn.sigmoid(m @ _f32(lp["router"]))
    weights = route(scores, lp["expert_bias"], top_k, route_norm,
                    route_scale, **how)
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


def hidden(weights: dict, tokens, *, n_head: int, n_kv_head: int,
           layer_types, sliding_window: int, top_k: int, route_norm: bool,
           route_scale: float, first_expert: int = 0, q_block: int = 512):
    """``tokens`` (B, T) through the layers: the residual stream
    (B, T, d) before the final norm, and each EXPERT layer's selection
    scores ``s + b`` (B*T, E).  A layer is dense where its weights have
    no ``router``."""
    B, T = tokens.shape
    d = weights["embed_tokens"].shape[1]
    h = _f32(weights["embed_tokens"][tokens]) * math.sqrt(d)
    picked = []
    for lp, kind in zip(weights["layers"], layer_types):
        a = rms_norm(h, lp["input_layernorm"])
        o = jnp.stack([
            attention(a[b], lp, n_head=n_head, n_kv_head=n_kv_head,
                      sliding=kind == "sliding_attention",
                      window=sliding_window, q_block=q_block)
            for b in range(B)
        ])
        h = h + post_norm(o, lp["post_attention_layernorm"])
        m = rms_norm(h, lp["pre_mlp_layernorm"]).reshape(B * T, -1)
        if "router" in lp:
            f, picked_l = moe(
                m, lp, top_k=top_k, route_norm=route_norm,
                route_scale=route_scale, first_expert=first_expert,
            )
            picked.append(picked_l)
        else:
            f = gated_mlp(m, lp["mlp.gate_proj"], lp["mlp.up_proj"],
                          lp["mlp.down_proj"])
        h = h + post_norm(f.reshape(h.shape), lp["post_mlp_layernorm"])
    return h, picked


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def logits(weights: dict, tokens, *, last: int, **model):
    """Float32 logits of the LAST ``last`` positions of one sequence
    ``tokens`` (T,), each computed against the whole context."""
    h, _ = hidden(weights, tokens[None], **model)
    return head(weights, h[0, tokens.shape[0] - last:])


def loss_from_hidden(weights: dict, h, targets):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL, nothing else.  ``jax.grad`` of it gives the reference
    gradients (the bias's is zero: selection has no gradient)."""
    h, _ = hidden(weights, tokens, **model)
    return loss_from_hidden(weights, h, targets)


def expert_tokens(picked, top_k: int):
    """Tokens an expert under the top ``top_k`` of one layer's selection
    scores (N, E)."""
    _, top_e = jax.lax.top_k(picked, top_k)
    return jnp.sum(
        jax.nn.one_hot(top_e, picked.shape[-1], dtype=jnp.int32), axis=(0, 1)
    )


def moved_bias(bias, counts, rate: float = BIAS_RATE):
    """The bias after a step that sent ``counts`` tokens to each expert."""
    c = _f32(counts)
    return _f32(bias) + rate * jnp.sign(jnp.mean(c) - c)
