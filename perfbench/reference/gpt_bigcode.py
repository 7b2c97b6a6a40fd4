"""Plain reference of the GPT-BigCode decoder (bigcode/starcoderbase-*).

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no
batching; written from the published architecture (``GPTBigCodeConfig``,
``modeling_gpt_bigcode.py``) and independent of ``accl_tpu.models``:

    h   = wte[tokens] + wpe[positions]
    for each layer:
        a   = LayerNorm(h; ln_1)                       (eps 1e-5)
        qkv = a @ c_attn (+ bias)                      (d -> d + 2*head_dim)
        q   = qkv[:, :d] as n_head heads; k, v = the ONE shared head
        h   = h + softmax(causal(q k^T / sqrt(head_dim))) v @ attn_c_proj
        m   = LayerNorm(h; ln_2)
        h   = h + gelu_tanh(m @ c_fc (+ bias)) @ mlp_c_proj (+ bias)
    logits = LayerNorm(h; ln_f) @ wte^T                (tied head)

Weights come as a dict under the published names; a missing ``*_b``
entry means "no bias" (the system under test has none — a departure the
configuration file lists).  Attention is computed in blocks of query
rows against the whole context so that T=8192 fits a chip; that changes
no value.  Callers wrap calls in
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5  # GPTBigCodeConfig.layer_norm_epsilon


def layer_norm(x, weight, bias=None):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + LN_EPS) * weight
    return y if bias is None else y + bias


def gelu_tanh(x):
    """``gelu_pytorch_tanh``, written out."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def linear(x, w, b=None):
    y = x @ w
    return y if b is None else y + b


def mqa_attention(q, k, v, q_block: int):
    """Causal multi-query attention of one sequence.  q: (T, H, hd);
    k, v: (T, hd), the single head every query head shares.  Query rows
    are taken ``q_block`` at a time against all keys."""
    T, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    cols = jnp.arange(T)
    out = []
    for start in range(0, T, q_block):
        qb = q[start:start + q_block]                      # (b, H, hd)
        scores = jnp.einsum("qhd,kd->hqk", qb, k) * scale  # (H, b, T)
        rows = start + jnp.arange(qb.shape[0])
        mask = cols[None, :] <= rows[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,kd->qhd", probs, v))
    return jnp.concatenate(out, axis=0)                    # (T, H, hd)


def block(h, lp, n_head: int, q_block: int):
    T, d = h.shape
    hd = d // n_head
    a = layer_norm(h, lp["ln_1_w"], lp.get("ln_1_b"))
    qkv = linear(a, lp["c_attn_w"], lp.get("c_attn_b"))
    q = qkv[:, :d].reshape(T, n_head, hd)
    k, v = qkv[:, d:d + hd], qkv[:, d + hd:d + 2 * hd]
    attn = mqa_attention(q, k, v, q_block).reshape(T, d)
    h = h + linear(attn, lp["attn_c_proj_w"], lp.get("attn_c_proj_b"))
    m = layer_norm(h, lp["ln_2_w"], lp.get("ln_2_b"))
    m = gelu_tanh(linear(m, lp["c_fc_w"], lp.get("c_fc_b")))
    return h + linear(m, lp["mlp_c_proj_w"], lp.get("mlp_c_proj_b"))


def logits(weights: dict, tokens, n_head: int, last: int,
           q_block: int = 512):
    """Float32 logits of the LAST ``last`` positions of one sequence
    ``tokens`` (T,), each computed against the whole context."""
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    T = tokens.shape[0]
    h = f32(weights["wte"])[tokens] + f32(weights["wpe"])[:T]
    for lp in weights["layers"]:
        h = block(h, f32(lp), n_head, q_block)
    h = layer_norm(h[T - last:], f32(weights["ln_f_w"]),
                   None if "ln_f_b" not in weights else f32(weights["ln_f_b"]))
    return h @ f32(weights["wte"]).T
