"""Plain reference of the OLMoE decoder (allenai/OLMoE-1B-7B-*).

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no sort,
no capacity; written from the published architecture (``OlmoeConfig``,
``modeling_olmoe.py``) and independent of ``accl_tpu.models``:

    h = embed_tokens[tokens]
    for each layer:
        a    = RMSNorm(h; input_layernorm)                 (eps 1e-5)
        q, k = RMSNorm(a @ q_proj; q_norm), RMSNorm(a @ k_proj; k_norm)
               -- over the WHOLE projection, before the split into heads
        q, k = rope(q), rope(k)          (theta 10000, rotate_half halves)
        h    = h + softmax(causal(q k^T / sqrt(head_dim))) v @ o_proj
        m    = RMSNorm(h; post_attention_layernorm)
        p    = softmax(m @ gate) over all experts, in float32
        w, e = top_k(p)                  (NOT renormalised: norm_topk_prob false)
        h    = h + sum_j w_j * down_proj[e_j](silu(m gate_proj[e_j]) * m up_proj[e_j])
    logits = RMSNorm(h; norm) @ lm_head                    (untied head)

    loss = mean NLL + 0.01 * load balance + 0.001 * router z

Weights come as a dict under the published names.  Departures from the
published code, none of which changes a value:

* a linear weight is stored (in, out) and applied as ``x @ w`` (torch
  stores (out, in));
* the experts' matrices are stacked on a leading axis of 64 (the
  published module is a list of 64 MLPs) and every expert is applied to
  EVERY token under a dense (tokens, 64) weight mask that is zero
  outside a token's top k, in a plain loop over the experts (the
  published loop gathers each expert's tokens first);
* attention is computed in blocks of query rows against the whole
  context so that T=4096 fits beside the weights;
* a batch is a loop over its sequences.

The two router terms are the ones OLMoE was trained with (its paper,
arXiv:2409.02060, section on the auxiliary losses; megablocks'
``batched_load_balancing_loss``), computed a layer and averaged over
layers: load balance ``E * sum_e f_e * P_e`` with ``f_e`` the share of
the tokens x k routing entries sent to expert e and ``P_e`` the mean
router probability of e; router z ``mean(logsumexp(router logits)^2)``.
(``transformers``' ``load_balancing_loss_func`` concatenates the layers
before the two means and does not divide by k: another normalisation of
the same quantity, and it has no z term.)

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5        # OlmoeConfig.rms_norm_eps
ROPE_THETA = 10000.0  # OlmoeConfig.rope_theta
AUX_COEF = 0.01       # OlmoeConfig.router_aux_loss_coef
Z_COEF = 0.001        # the OLMoE paper's router z-loss weight


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


def qk_norm(x, weight):
    """RMSNorm over the whole projected q or k, every head at once."""
    return rms_norm(x, weight)


silu = jax.nn.silu  # OlmoeConfig.hidden_act


def rope(x):
    """x: (T, H, hd).  ``rotate_half``: the two HALVES of a head pair up."""
    T, _, hd = x.shape
    inv_freq = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, q_block: int):
    """q, k, v: (T, H, hd), one sequence; query rows ``q_block`` at a
    time against all keys."""
    T, _, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    cols = jnp.arange(T)
    out = []
    for start in range(0, T, q_block):
        qb = q[start:start + q_block]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(qb.shape[0])
        mask = cols[None, :] <= rows[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out, axis=0)


def attention(a, lp, n_head: int, q_block: int):
    """One sequence ``a`` (T, d) through the attention half of a layer."""
    T, d = a.shape
    hd = d // n_head
    q = qk_norm(a @ _f32(lp["q_proj"]), lp["q_norm"])
    k = qk_norm(a @ _f32(lp["k_proj"]), lp["k_norm"])
    v = a @ _f32(lp["v_proj"])
    q, k = rope(q.reshape(T, n_head, hd)), rope(k.reshape(T, -1, hd))
    out = causal_attention(q, k, v.reshape(T, -1, hd), q_block)
    return out.reshape(T, d) @ _f32(lp["o_proj"])


def route(router_logits, top_k: int, norm_topk_prob: bool):
    """The (tokens, E) weight of every expert for every token: the
    softmax probability on a token's ``top_k`` experts, zero elsewhere."""
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_e, probs.shape[-1], dtype=probs.dtype)
    return jnp.einsum("nk,nke->ne", top_p, onehot)


def moe(m, lp, top_k: int, norm_topk_prob: bool):
    """``m`` (N, d) through the sparse MLP; returns (out, router logits)."""
    router_logits = m @ _f32(lp["gate"])
    weights = route(router_logits, top_k, norm_topk_prob)

    def one_expert(acc, xs):
        gate_proj, up_proj, down_proj, w = xs
        hidden = silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))
        return acc + w[:, None] * (hidden @ _f32(down_proj)), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (lp["experts.gate_proj"], lp["experts.up_proj"],
         lp["experts.down_proj"], weights.T),
    )
    return out, router_logits


def hidden(weights: dict, tokens, *, n_head: int, top_k: int,
           norm_topk_prob: bool = False, q_block: int = 512):
    """``tokens`` (B, T) through the layers: the residual stream
    (B, T, d) before the final norm, and each layer's router logits
    (B*T, E)."""
    B, T = tokens.shape
    h = _f32(weights["embed_tokens"][tokens])
    router = []
    for lp in weights["layers"]:
        a = rms_norm(h, lp["input_layernorm"])
        h = h + jnp.stack(
            [attention(a[b], lp, n_head, q_block) for b in range(B)]
        )
        m = rms_norm(h, lp["post_attention_layernorm"])
        out, logits_l = moe(m.reshape(B * T, -1), lp, top_k, norm_topk_prob)
        h = h + out.reshape(h.shape)
        router.append(logits_l)
    return h, router


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def logits(weights: dict, tokens, *, n_head: int, top_k: int, last: int,
           norm_topk_prob: bool = False, q_block: int = 512):
    """Float32 logits of the LAST ``last`` positions of one sequence
    ``tokens`` (T,), each computed against the whole context."""
    h, _ = hidden(weights, tokens[None], n_head=n_head, top_k=top_k,
                  norm_topk_prob=norm_topk_prob, q_block=q_block)
    return head(weights, h[0, tokens.shape[0] - last:])


def router_terms(router_logits, top_k: int):
    """(load balance, router z) of one layer's (N, E) router logits."""
    N, E = router_logits.shape
    probs = jax.nn.softmax(router_logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, top_k)
    f = jnp.sum(jax.nn.one_hot(top_e, E), axis=(0, 1)) / (N * top_k)
    balance = E * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return balance, z


def loss_from_hidden(weights: dict, h, router, targets, top_k: int):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    terms = [router_terms(r, top_k) for r in router]
    balance = sum(t[0] for t in terms) / len(terms)
    z = sum(t[1] for t in terms) / len(terms)
    return nll + AUX_COEF * balance + Z_COEF * z


def loss(weights: dict, tokens, targets, *, n_head: int, top_k: int,
         norm_topk_prob: bool = False, q_block: int = 512):
    """The training loss of a batch ``tokens``, ``targets`` (B, T):
    ``jax.grad`` of it gives the reference gradients."""
    h, router = hidden(weights, tokens, n_head=n_head, top_k=top_k,
                       norm_topk_prob=norm_topk_prob, q_block=q_block)
    return loss_from_hidden(weights, h, router, targets, top_k)
