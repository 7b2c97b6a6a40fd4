"""Plain reference of SDAR-30B-A3B-Chat's block (``model_type: sdar_moe``,
JetLM) under its block-diffusion TRAINING objective, as ONE chip of an
expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, a dense mask, no kernels, no
cache, no sort, no buffer; written from ``config.json``'s keys and, where
they say nothing, from memory of Qwen3-MoE's modelling code and of the
BD3-LM and SDAR papers (the configuration file lists each such point under
``assumed``); independent of ``accl_tpu.models``.

THE BLOCK (Qwen3-MoE's), for a row ``x`` at position ``p``:

    a = RMSNorm(x; input_layernorm)                              (eps 1e-6)
    q = a Wq (32 heads of 128), k = a Wk, v = a Wv (4 heads of 128)
    q, k = RMSNorm over EACH head's 128 (one scale for q, one for k, 1e-6)
    q, k = rope(q, p), rope(k, p)          (theta 1e6, rotate_half)
    o = softmax(q k^T / sqrt(128) + log M) v, query head h on KV head h // 8
    x = x + o Wo
    m = RMSNorm(x; post_attention_layernorm)
    P = softmax_f32(m G) over all 128 experts; top 8; w = P_sel / sum P_sel
    x = x + sum_{e chosen, e held} w_e W2_e (silu(W1_e m) * W3_e m)
    (no shared expert; every layer sparse)
    logits = RMSNorm(x; norm) @ lm_head                      (untied head)

THE OBJECTIVE (BD3-LM's, as the SDAR family adopts it).  A clean sequence
``x_0`` of ``L`` ids is ``L / B`` blocks.  Each block ``b`` of each
sequence has a level ``t_b = eps + (1 - eps) u_b``, ``u_b ~ U[0, 1)``,
``eps = 1e-3``; each position of the block is masked independently with
probability ``t_b``; ``x_t`` has ``mask_token_id`` where masked and ``x_0``
elsewhere.  The model runs on ``[x_t ; x_0]``: ``2 L`` rows, BOTH halves at
positions ``0..L-1``, under the mask ``M`` (:func:`visible`):

    noisy -> noisy: the same block (both directions);
    noisy -> clean: blocks STRICTLY before the query's;
    clean -> clean: blocks up to and including the query's;
    clean -> noisy: nothing.

Logits are taken on the noisy half only and predict the id AT the position
(no shift):

    loss = 1 / (S L) sum_s sum_i masked_i / t_b(i) * -log softmax(z_i)[x_0,i]
           + 0.001 * mean over layers of (128 sum_e f_e P_e)

with ``f_e`` the share of a layer's ``S * 2 L * 8`` routing entries sent to
expert ``e`` (no gradient) and ``P_e`` the mean of its router probability
over the ``S * 2 L`` rows, over ALL 128 router outputs whatever is held.

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 128; the router, its top 8 and the
weights are over all of them, and what an expert that is not held would
have added is left out (the model-configs guide, section 4).  With all of
them held this is the whole model.  The noise (ids, levels) is GIVEN: the
reference draws none.

Departures from the published code, none of which changes a value: a
linear weight is stored (in, out) and applied as ``x @ w``; the held
experts' matrices are stacked on a leading axis and every held expert is
applied to EVERY row under a dense (rows, held) weight that is zero
outside a row's top 8; attention is computed in blocks of query rows
against all ``2 L`` keys; a batch is a loop over its sequences; the model
can be run a layer at a time (:func:`embed`, :func:`layer`, :func:`head`)
so that one layer's float32 weights are alive at once.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps
ROPE_THETA = 1e6        # rope_theta
AUX_COEF = 0.001        # router_aux_loss_coef (assumed)


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


def qk_norm(x, weight):
    """RMSNorm over each head's width: ``x`` is (T, H, hd), ``weight``
    (hd,)."""
    return rms_norm(x, weight)


silu = jax.nn.silu       # hidden_act


def rope(x, positions):
    """x: (T, H, hd) at ``positions`` (T,).  ``rotate_half``: the two
    HALVES of a head pair up."""
    hd = x.shape[-1]
    inv_freq = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def visible(rows, cols, L: int, block: int):
    """``M``: whether the query at row ``rows`` of ``[x_t ; x_0]`` sees the
    key at row ``cols`` (arrays that broadcast; rows ``0..L`` are the noisy
    half, ``L..2L`` the clean one; both at positions ``row mod L``)."""
    q_noisy, k_noisy = rows < L, cols < L
    q_block, k_block = (rows % L) // block, (cols % L) // block
    return (
        (q_noisy & k_noisy & (q_block == k_block))
        | (q_noisy & ~k_noisy & (k_block < q_block))
        | (~q_noisy & ~k_noisy & (k_block <= q_block))
    )


def masked_attention(q, k, v, L: int, block: int, q_block: int):
    """q: (2L, H, hd); k, v: (2L, Hkv, hd), query head i on KV head
    ``i // (H // Hkv)``; one sequence, query rows ``q_block`` at a time
    against all keys, under :func:`visible`."""
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
        mask = visible(rows[:, None], cols[None, :], L, block)
        scores = jnp.where(mask[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out, axis=0)


def attention(a, lp, *, n_head: int, n_kv_head: int, block: int,
              q_block: int):
    """One doubled sequence ``a`` (2L, d) through the attention half."""
    T = a.shape[0]
    L = T // 2
    q = (a @ _f32(lp["q_proj"])).reshape(T, n_head, -1)
    k = (a @ _f32(lp["k_proj"])).reshape(T, n_kv_head, -1)
    v = (a @ _f32(lp["v_proj"])).reshape(T, n_kv_head, -1)
    q, k = qk_norm(q, lp["q_norm"]), qk_norm(k, lp["k_norm"])
    positions = jnp.arange(T) % L       # 0..L-1 twice
    q, k = rope(q, positions), rope(k, positions)
    out = masked_attention(q, k, v, L, block, q_block)
    return out.reshape(T, -1) @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def route(probs, top_k: int, norm_topk_prob: bool):
    """The (rows, E) weight of every expert for every row: the chosen
    probabilities, renormalised over the chosen (``norm_topk_prob``); zero
    outside a row's ``top_k``."""
    w, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_e, probs.shape[-1], dtype=probs.dtype)
    return jnp.einsum("nk,nke->ne", w, onehot)


def load_balance(probs, top_k: int):
    """The Switch load-balance term of one layer: ``E sum_e f_e P_e`` over
    all ``E`` router outputs and all the rows; the counts carry no
    gradient."""
    N, E = probs.shape
    _, top_e = jax.lax.top_k(probs, top_k)
    f = jnp.sum(jax.nn.one_hot(top_e, E, dtype=probs.dtype), axis=(0, 1))
    f = jax.lax.stop_gradient(f) / (N * top_k)
    return E * jnp.sum(f * jnp.mean(probs, axis=0))


def moe(m, lp, *, top_k: int, norm_topk_prob: bool, first_expert: int = 0):
    """``m`` (N, d) through the sparse MLP of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    router logits over all experts, the layer's load-balance term)``."""
    logits = m @ _f32(lp["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    weights = route(probs, top_k, norm_topk_prob)
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
    return out, logits, load_balance(probs, top_k)


def embed(weights: dict, ids):
    """``ids`` (S, 2L), ``[x_t ; x_0]``, as float32 rows."""
    return _f32(weights["embed_tokens"][ids])


def layer(h, lp, *, n_head: int, n_kv_head: int, block: int, top_k: int,
          norm_topk_prob: bool, first_expert: int = 0, q_block: int = 512):
    """One layer on ``h`` (S, 2L, d): ``(h, router logits (S * 2L, E),
    load-balance term)``."""
    S, T, _ = h.shape
    a = rms_norm(h, lp["input_layernorm"])
    o = jnp.stack([
        attention(a[s], lp, n_head=n_head, n_kv_head=n_kv_head, block=block,
                  q_block=q_block)
        for s in range(S)
    ])
    h = h + o
    m = rms_norm(h, lp["post_attention_layernorm"]).reshape(S * T, -1)
    f, logits, balance = moe(
        m, lp, top_k=top_k, norm_topk_prob=norm_topk_prob,
        first_expert=first_expert,
    )
    return h + f.reshape(h.shape), logits, balance


def hidden(weights: dict, ids, **model):
    """``ids`` (S, 2L) through the layers: the residual stream before the
    final norm, each layer's router logits, and the mean over the layers
    of their load-balance terms."""
    h = embed(weights, ids)
    logits, balance = [], 0.0
    for lp in weights["layers"]:
        h, logits_l, balance_l = layer(h, lp, **model)
        logits.append(logits_l)
        balance = balance + balance_l
    return h, logits, balance / len(weights["layers"])


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def noisy_half(h):
    """The rows the head sees: the first half of the ``2 L``."""
    return h[..., : h.shape[-2] // 2, :]


def targets_of(clean):
    """The id a noisy position predicts: the clean id AT the position."""
    return clean


def weighted_nll(logits, clean, masked, t):
    """``sum masked / t * nll / (S L)``: ``logits`` (S, L, V) of the noisy
    half, ``clean`` (S, L) ids, ``masked`` (S, L) bool, ``t`` (S, L) the
    level of each position's block."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, targets_of(clean)[..., None], axis=-1
    )[..., 0]
    return jnp.sum(jnp.where(masked, nll / t, 0.0)) / clean.size


def logits(weights: dict, noisy, clean, **model):
    """Float32 logits (S, L, V) of the noisy half of ``[noisy ; clean]``."""
    h, _, _ = hidden(weights, jnp.concatenate([noisy, clean], axis=1), **model)
    return head(weights, noisy_half(h))


def loss(weights: dict, noisy, clean, masked, t, **model):
    """The training loss of a batch: the weighted NLL of the masked
    positions plus ``AUX_COEF`` times the layers' mean load-balance term.
    ``jax.grad`` of it gives the reference gradients."""
    h, _, balance = hidden(
        weights, jnp.concatenate([noisy, clean], axis=1), **model
    )
    return weighted_nll(
        head(weights, noisy_half(h)), clean, masked, t
    ) + AUX_COEF * balance
