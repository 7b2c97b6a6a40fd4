"""Plain reference of the mimo_v2 decoder (XiaomiMiMo/MiMo-V2.5, the
MiMo-V2-Flash family), as ONE chip of an expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no cache, no sort, no
buffer; written from ``config.json``'s keys and, where they say nothing,
from memory of ``modeling_mimo_v2_flash.py`` (the configuration file lists
each such point under ``assumed``); independent of ``accl_tpu``:

    layer i is FULL where hybrid_layer_pattern[i] == 0, SWA where it is 1
    Hkv = 4 full (num_key_value_heads), 8 swa (swa_num_key_value_heads)

    a = RMSNorm(h; input_layernorm)                              (eps 1e-5)
    q = a Wq -> 64 heads of 192,  k = a Wk -> Hkv heads of 192
    v = 0.707 * (a Wv) -> Hkv heads of 128       (attention_value_scale)
    q, k: the FIRST 64 of a head's 192 columns rotate (rotate_half over
          the two halves of the 64; theta 1e7 full, 1e4 swa); the other
          128 carry no position                  (partial_rotary_factor)
    s_ij = q_i . k_j / sqrt(192), query head i on KV head i // (64 / Hkv)
           for 0 <= i - j            (full)
           for 0 <= i - j < 128      (swa: the query's own key among them)
    full: p = softmax_j(s)
    swa : one learned scalar b_h a QUERY head is one more column of the
          row's softmax, after the scale; it takes probability and has no
          value:  p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(b_h))
    h = h + (p v -> 64 heads of 128) Wo
    m = RMSNorm(h; post_attention_layernorm)
    layer 0    : h = h + (silu(m W1) * (m W3)) W2                (16,384)
    layers >= 1: s = sigmoid(m Wr) over ALL 256 experts, float32
                 sel = top8(s + bias)       (n_group = topk_group = 1)
                 w = s[sel] / (sum s[sel] + 1e-20)       (norm_topk_prob)
                 h = h + sum_{e in sel, e held} w_e expert_e(m)   (2,048)
    logits = RMSNorm(h; norm) @ lm_head                     (untied head)
    loss   = mean next-token NLL, no auxiliary term

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 256; the router, its top 8 and the
weights are over all of them, and what an expert that is not held would
have added is left out (the model-configs guide, section 4).  With all of
them held this is the whole layer.

Departures from the published code, none of which changes a value: a linear
weight is stored (in, out) and applied as ``x @ w`` (``fused_qkv`` is a
storage layout: three matrices here); the held experts' matrices are
stacked on a leading axis and every held expert is applied to EVERY token
under a dense (tokens, held) weight that is zero outside a token's top 8;
attention is computed in blocks of query rows against the whole context,
one block's scores alive at a time; the loss in blocks of rows; a batch is
a loop over its sequences.

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5          # layernorm_epsilon
ROUTE_EPS = 1e-20       # the renormalisation's guard


def _f32(x):
    return x.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


def rope(x, theta: float, rotary: int, last: bool = False):
    """x: (T, H, d): ``rotary`` of a head's columns rotate, the FIRST ones
    (``last``: the last ones, a way of getting it wrong), ``rotate_half``
    over the two halves of those; the others pass."""
    T, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    keep, turn = (x[..., : d - rotary], x[..., d - rotary:]) if last else (
        x[..., rotary:], x[..., :rotary]
    )
    x1, x2 = turn[..., : rotary // 2], turn[..., rotary // 2:]
    turned = turn * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([keep, turned] if last else [turned, keep], -1)


def sink_attention(q, k, v, scale: float, window, sink, q_block: int,
                   valued: bool = False):
    """q: (T, H, d); k (T, Hkv, d), v (T, Hkv, dv), query head i on KV head
    ``i // (H // Hkv)``; one sequence, query rows ``q_block`` at a time
    against all keys ``0 <= i - j`` (``< window`` where one is given).
    ``sink`` (H,) or None: one more column of each row's softmax, the head's
    scalar, whose probability multiplies no value (``valued``: the row's own
    v, a way of getting it wrong).  Returns the output (T, H, dv) and the
    sink's probability a row (T, H) (zeros without one)."""
    T, H, _ = q.shape
    groups = H // k.shape[1]
    k, v = jnp.repeat(k, groups, axis=1), jnp.repeat(v, groups, axis=1)
    cols = jnp.arange(T)

    @jax.checkpoint      # a block's scores again for the gradient, not kept
    def rows_from(start, qb, vb):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        dist = (start + jnp.arange(q_block))[:, None] - cols[None, :]
        mask = dist >= 0
        if window is not None:
            mask &= dist < window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        if sink is None:
            probs = jax.nn.softmax(scores, -1)
            return jnp.einsum("hqk,khd->qhd", probs, v), jnp.zeros(
                (q_block, H), jnp.float32
            )
        column = jnp.broadcast_to(_f32(sink)[:, None, None], (H, q_block, 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], -1), -1)
        out = jnp.einsum("hqk,khd->qhd", probs[..., :-1], v)
        p_sink = probs[..., -1].T                       # (q_block, H)
        if valued:
            out = out + p_sink[..., None] * vb
        return out, p_sink

    # a loop, so that one block's scores are alive at a time forwards AND
    # backwards; a tail short of a block is padded with rows that are dropped
    blocks = -(-T // q_block)
    pad = ((0, blocks * q_block - T), (0, 0), (0, 0))
    _, (out, p_sink) = jax.lax.scan(
        lambda _, x: (None, rows_from(*x)), None,
        (jnp.arange(blocks) * q_block,
         jnp.pad(q, pad).reshape(blocks, q_block, H, -1),
         jnp.pad(v, pad).reshape(blocks, q_block, H, -1)),
    )
    return (
        out.reshape(blocks * q_block, H, -1)[:T],
        p_sink.reshape(blocks * q_block, H)[:T],
    )


def attention(a, lp, *, swa: bool, n_head: int, head_dim: int, rotary: int,
              thetas, window: int, v_scale: float, q_block: int,
              sink: str = "published", rotate: str = "first",
              pair_kv: bool = False):
    """One sequence ``a`` (T, d) through the attention half of a layer of
    the kind ``swa`` says; returns ``(out (T, d), the sink's probability a
    row (T, H))``.  ``thetas`` = (full, swa).  Ways of getting it wrong:
    ``sink`` ``"none"`` (left out), ``"full_too"`` (a sink of 4.0 a head on
    the full layers as well), ``"valued"`` (the sink's probability times the
    row's own v); ``rotate`` ``"all"`` (every column of a head) or
    ``"last"`` (the last ``rotary``); ``pair_kv``: a sliding layer's K/V
    heads averaged in pairs (the full layers' count in both kinds)."""
    T = a.shape[0]
    q = (a @ _f32(lp["q_proj"])).reshape(T, n_head, head_dim)
    k = (a @ _f32(lp["k_proj"])).reshape(T, -1, head_dim)
    v = v_scale * (a @ _f32(lp["v_proj"]))
    v = v.reshape(T, k.shape[1], -1)
    if pair_kv and swa:
        k = k.reshape(T, -1, 2, head_dim).mean(axis=2)
        v = v.reshape(T, k.shape[1], 2, -1).mean(axis=2)
    theta = thetas[1] if swa else thetas[0]
    turn = head_dim if rotate == "all" else rotary
    q = rope(q, theta, turn, last=rotate == "last")
    k = rope(k, theta, turn, last=rotate == "last")
    b = None
    if swa and sink != "none":
        b = lp["attention_sink_bias"]
    elif sink == "full_too":
        b = jnp.full((n_head,), 4.0, jnp.float32)
    out, p_sink = sink_attention(
        q, k, v, 1.0 / math.sqrt(head_dim), window if swa else None, b,
        q_block, valued=sink == "valued",
    )
    return out.reshape(T, -1) @ _f32(lp["o_proj"]), p_sink


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def route(scores, bias, top_k: int, norm_topk_prob: bool = True,
          route_scale: float = 1.0):
    """The (tokens, E) weight of every expert for every token: selection on
    ``scores + bias`` (``noaux_tc`` with ONE group: no group limit), weights
    from the UNBIASED scores of the chosen, renormalised; zero outside a
    token's ``top_k``."""
    _, top_e = jax.lax.top_k(scores + _f32(bias), top_k)
    w = jnp.take_along_axis(scores, top_e, -1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    w = w * route_scale
    onehot = jax.nn.one_hot(top_e, scores.shape[-1], dtype=scores.dtype)
    return jnp.einsum("nk,nke->ne", w, onehot)


def moe(m, lp, *, top_k: int, first_expert: int = 0):
    """``m`` (N, d) through the sparse MLP of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    scores + bias over all experts)``.  No shared expert."""
    scores = jax.nn.sigmoid(m @ _f32(lp["router"]))
    weights = route(scores, lp["e_score_correction_bias"], top_k)
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
    return out, scores + _f32(lp["e_score_correction_bias"])


def layer(h, lp, *, swa: bool, top_k: int, first_expert: int = 0, **attn):
    """The residual stream ``h`` (B, T, d) through one layer of the kind
    ``swa`` says (``attn``: :func:`attention`'s arguments); returns ``(h,
    the selection scores s + b (B*T, E) of an expert layer or None, the
    sink's probability a row of the FIRST sequence (T, H))``.  A layer is
    dense where its weights have no ``router``."""
    B, T, _ = h.shape
    a = rms_norm(h, lp["input_layernorm"])
    mixed = [attention(a[b], lp, swa=swa, **attn) for b in range(B)]
    h = h + jnp.stack([o for o, _ in mixed])
    m = rms_norm(h, lp["post_attention_layernorm"]).reshape(B * T, -1)
    picked = None
    if "router" in lp:
        f, picked = moe(m, lp, top_k=top_k, first_expert=first_expert)
    else:
        f = gated_mlp(m, lp["mlp.gate_proj"], lp["mlp.up_proj"],
                      lp["mlp.down_proj"])
    return h + f.reshape(h.shape), picked, mixed[0][1]


def embed(weights: dict, tokens):
    return _f32(weights["embed_tokens"][tokens])


def hidden(weights: dict, tokens, *, pattern, **model):
    """``tokens`` (B, T) through the layers (``pattern[i]`` 1 = SWA): the
    residual stream (B, T, d) before the final norm, and each EXPERT layer's
    selection scores."""
    h = embed(weights, tokens)
    picked = []
    for lp, kind in zip(weights["layers"], pattern):
        h, picked_l, _ = layer(h, lp, swa=bool(kind), **model)
        if picked_l is not None:
            picked.append(picked_l)
    return h, picked


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets, rows: int = 1024):
    """Mean next-token NLL of the stream ``h`` (B, T, d), ``rows`` rows of
    logits at a time (computed again for the gradient, not kept)."""
    d = h.shape[-1]
    h, targets = h.reshape(-1, d), targets.reshape(-1)
    n = h.shape[0]
    rows = math.gcd(n, rows)

    @jax.checkpoint
    def block(weights, h, targets):
        logp = jax.nn.log_softmax(head(weights, h), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=-1))

    total, _ = jax.lax.scan(
        lambda acc, x: (acc + block(weights, *x), None),
        jnp.zeros((), jnp.float32),
        (h.reshape(n // rows, rows, d), targets.reshape(n // rows, rows)),
    )
    return total / n


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL, nothing else.  ``jax.grad`` of it gives the reference
    gradients (the bias's is zero: selection has no gradient)."""
    h, _ = hidden(weights, tokens, **model)
    return nll_from_hidden(weights, h, targets)


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


def sink_facts(p_sink, window: int):
    """Of the sink's probability a row of one sliding layer (T, H): the
    quantiles 0, 0.1, 0.5, 0.9, 1 over rows and heads, and its mean over
    the rows whose window is not yet full against those where it is."""
    T = p_sink.shape[0]
    early = min(window, T // 2)
    return {
        "quantiles": jnp.quantile(
            p_sink.reshape(-1), jnp.array([0.0, 0.1, 0.5, 0.9, 1.0])
        ),
        "mean_window_filling": jnp.mean(p_sink[:early]),
        "mean_window_full": jnp.mean(p_sink[early:]),
    }
