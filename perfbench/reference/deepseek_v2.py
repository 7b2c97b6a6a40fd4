"""Plain reference of the DeepSeek-V2 decoder (deepseek-ai/DeepSeek-V2), as
ONE chip of its 8-way expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no sort, no buffer;
written from ``config.json``'s keys and, where they say nothing, from
memory of the model's ``modeling_deepseek.py`` and of the DeepSeek-V2
paper (the configuration file lists each such point under ``assumed``);
independent of ``accl_tpu.models`` and ``accl_tpu.ops``:

    h = embed_tokens[tokens]
    for each layer l:
        a    = RMSNorm(h; input_layernorm)                       (eps 1e-6)
        cq   = RMSNorm(a @ q_a_proj; q_a_layernorm)               (T, 1536)
        q    = cq @ q_b_proj  -> (T, 128, 128 + 64) = [q_n | q_r]
        [ckv | k_r] = a @ kv_a_proj_with_mqa       (T, 512 + 64), k_r ONE head
        kv   = RMSNorm(ckv; kv_a_layernorm) @ kv_b_proj
                              -> (T, 128, 128 + 128) = [k_n | v]
        q_r, k_r = rope(q_r), rope(k_r)     (YaRN frequencies, rotate_half)
        s    = (q_n . k_n + q_r . k_r) * 192 ** -0.5 * m ** 2,  keys j <= i
        o    = softmax(s) v                                (float32 softmax)
        h    = h + concat_h(o) @ o_proj
        m_   = RMSNorm(h; post_attention_layernorm)
        l < first_k_dense_replace:
            f = (silu(m_ gate_proj) * (m_ up_proj)) down_proj   (width 12288)
        else:
            p    = softmax(m_ @ gate) over ALL 160 experts, float32
            g_d  = max of p over group d's 20 experts;  keep the 3 best of 8
            sel  = top6(p where the group is kept, else 0)
            w    = 16 * p[sel]                     (norm_topk_prob false)
            f    = shared(m_) + sum_{e in sel, e held} w_e expert_e(m_)
        h    = h + f
    logits = RMSNorm(h; norm) @ lm_head                         (untied head)

    loss = mean next-token NLL + sum over the expert layers of
           a1 L_exp + a2 L_dev + a3 L_comm        (:func:`balance_losses`)

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 160 (a whole routing group on this
chip); the router, its groups, its top 6 and the weights are over all of
them, and what an expert that is not held would have added is left out
(the model-configs guide, section 4).  With all of them held this is the
whole model.

Departures from the published code, none of which changes a value:

* a linear weight is stored (in, out) and applied as ``x @ w``;
* the rope columns are rotated as two halves: the checkpoint stores them
  interleaved and the model de-interleaves before ``rotate_half``, which
  on seeded weights is a relabelling of ``q_b_proj``'s and
  ``kv_a_proj_with_mqa``'s columns;
* the held experts' matrices are stacked on a leading axis and every held
  expert is applied to EVERY token under a dense (tokens, held) weight
  mask that is zero outside a token's top 6, in a plain loop;
* attention is computed in blocks of query rows against the whole context;
* a batch is a loop over its sequences, and a caller short of memory runs
  :func:`layer` a layer at a time (weights are upcast where they are used).

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6          # rms_norm_eps


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


silu = jax.nn.silu       # hidden_act


def yarn_get_mscale(factor: float, mscale: float) -> float:
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_max: int):
    """The ``dim / 2`` inverse frequencies of the rope columns under
    YaRN: ``f_extra[i] = theta ** (-2 i / dim)`` where a pair turns often
    in the original context, ``f_extra[i] / factor`` where it hardly
    turns, a linear ramp over the pairs between the two corrections."""
    f_extra = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    f_inter = [f / factor for f in f_extra]

    def correction(turns):
        return (
            dim * math.log(original_max / (turns * 2 * math.pi))
            / (2 * math.log(theta))
        )

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        ramp = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        out.append(f_inter[i] * ramp + f_extra[i] * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def rope(x, inv_freq, table_scale: float = 1.0):
    """x: (T, H, dr).  ``rotate_half``: the two HALVES of the columns pair
    up."""
    T, _, dr = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x * cos + rotated * sin) * table_scale


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


def attention(a, lp, *, n_head: int, qk_nope_head_dim: int,
              qk_rope_head_dim: int, v_head_dim: int, kv_lora_rank: int,
              rope_theta: float, rope_scaling: dict, q_block: int,
              scale_without_mscale: bool = False):
    """One sequence ``a`` (T, d) through the latent attention of a layer
    (``scale_without_mscale`` is a way of getting it wrong, for the
    tests)."""
    T = a.shape[0]
    dn, dr = qk_nope_head_dim, qk_rope_head_dim
    rs = rope_scaling
    inv_freq = yarn_inv_freq(
        dr, rope_theta, rs["factor"], rs["beta_fast"], rs["beta_slow"],
        rs["original_max_position_embeddings"],
    )
    table_scale = (
        yarn_get_mscale(rs["factor"], rs["mscale"])
        / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    )
    m = yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = (dn + dr) ** -0.5 * (1.0 if scale_without_mscale else m * m)

    cq = rms_norm(a @ _f32(lp["q_a_proj"]), lp["q_a_layernorm"])
    q = (cq @ _f32(lp["q_b_proj"])).reshape(T, n_head, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    ckv = a @ _f32(lp["kv_a_proj_with_mqa"])
    k_r = ckv[:, kv_lora_rank:].reshape(T, 1, dr)
    kv = rms_norm(ckv[:, :kv_lora_rank], lp["kv_a_layernorm"]) @ _f32(
        lp["kv_b_proj"]
    )
    kv = kv.reshape(T, n_head, dn + v_head_dim)
    k_n, v = kv[..., :dn], kv[..., dn:]
    q_r, k_r = rope(q_r, inv_freq, table_scale), rope(k_r, inv_freq, table_scale)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, (T, n_head, dr))], axis=-1)
    out = causal_attention(q, k, v, scale, q_block)
    return out.reshape(T, n_head * v_head_dim) @ _f32(lp["o_proj"])


def gated_mlp(m, gate_proj, up_proj, down_proj):
    return (silu(m @ _f32(gate_proj)) * (m @ _f32(up_proj))) @ _f32(down_proj)


def kept_groups(p, n_group: int, topk_group: int):
    """(tokens, n_group) 1/0: the ``topk_group`` groups of largest score,
    a group's score being the largest ``p`` of its consecutive experts."""
    N, E = p.shape
    score = p.reshape(N, n_group, E // n_group).max(axis=-1)
    _, keep = jax.lax.top_k(score, topk_group)
    return jnp.sum(jax.nn.one_hot(keep, n_group, dtype=p.dtype), axis=1)


def route(p, top_k: int, n_group: int, topk_group: int, scale: float,
          renormalise: bool = False):
    """The (tokens, E) weight of every expert for every token under
    ``group_limited_greedy``: zero outside the kept groups' top ``top_k``,
    ``scale * p`` on them (``renormalise`` is a way of getting it wrong,
    for the tests: ``norm_topk_prob`` is false)."""
    N, E = p.shape
    keep = jnp.repeat(kept_groups(p, n_group, topk_group), E // n_group, axis=1)
    w, top_e = jax.lax.top_k(p * keep, top_k)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    onehot = jax.nn.one_hot(top_e, E, dtype=p.dtype)
    return jnp.einsum("nk,nke->ne", w * scale, onehot)


def balance_losses(p, chosen, seqs: int, top_k: int, n_group: int,
                   topk_group: int):
    """``(L_exp, L_dev, L_comm)`` of one layer WITHOUT their weights:
    ``p`` (N, E) the router's probabilities, ``chosen`` (N, E) 1/0 a
    token's top 6, ``N = seqs * T``.  ``seq_aux``: each is computed for a
    sequence and averaged over the sequences.  For one sequence: ``c_e``
    its entries sent to expert ``e``, ``f_e = E / (k T) c_e``, ``P_e`` the
    mean of ``p[:, e]`` over its tokens, ``E_d`` group ``d``'s experts,
    ``n_d`` its tokens with at least one entry in ``E_d``:

        L_exp  = sum_e f_e P_e
        L_dev  = sum_d (mean_{e in E_d} f_e) (sum_{e in E_d} P_e)
        L_comm = sum_d (n_group / (topk_group T)) n_d (sum_{e in E_d} P_e)

    The counts are constants of the gradient."""
    N, E = p.shape
    T, per = N // seqs, E // n_group
    chosen = jax.lax.stop_gradient(chosen)
    exp = dev = comm = 0.0
    for s in range(seqs):
        ps, cs = p[s * T:(s + 1) * T], chosen[s * T:(s + 1) * T]
        f = cs.sum(axis=0) * (E / (top_k * T))
        P = ps.mean(axis=0)
        exp = exp + jnp.sum(f * P)
        for d in range(n_group):
            members = slice(d * per, (d + 1) * per)
            group_p = jnp.sum(P[members])
            n_d = jnp.sum(cs[:, members].sum(axis=1) > 0)
            dev = dev + jnp.mean(f[members]) * group_p
            comm = comm + n_group / (topk_group * T) * n_d * group_p
    return exp / seqs, dev / seqs, comm / seqs


def moe(m, lp, *, seqs: int, top_k: int, n_group: int, topk_group: int,
        routed_scaling_factor: float, first_expert: int = 0,
        shared: bool = True, **how):
    """``m`` (N, d), ``seqs`` sequences, through the sparse MLP of a chip
    that holds experts ``first_expert ..`` (as many as ``experts.*``
    stack).  Returns ``(out, router logits over all experts, the three
    balance losses)``.  ``shared=False`` leaves the shared experts out
    (for the sum over the shares)."""
    logits = m @ _f32(lp["gate"])
    p = jax.nn.softmax(logits, axis=-1)
    weights = route(p, top_k, n_group, topk_group, routed_scaling_factor, **how)
    balance = balance_losses(
        p, (weights > 0).astype(p.dtype), seqs, top_k, n_group, topk_group
    )
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
    return out, logits, balance


def layer(h, lp, *, q_block: int = 512, moe_how=None, attn_how=None, **model):
    """The residual stream ``h`` (B, T, d) through one layer (dense where
    its weights have no ``gate``): ``(h, router logits (B*T, E) or None,
    (L_exp, L_dev, L_comm) or None)``."""
    B, T, _ = h.shape
    attn = {k: model[k] for k in (
        "n_head", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "kv_lora_rank", "rope_theta", "rope_scaling",
    )}
    a = rms_norm(h, lp["input_layernorm"])
    h = h + jnp.stack([
        attention(a[b], lp, q_block=q_block, **attn, **(attn_how or {}))
        for b in range(B)
    ])
    m = rms_norm(h, lp["post_attention_layernorm"]).reshape(B * T, -1)
    if "gate" not in lp:
        f = gated_mlp(m, lp["mlp.gate_proj"], lp["mlp.up_proj"],
                      lp["mlp.down_proj"])
        return h + f.reshape(h.shape), None, None
    f, logits, balance = moe(
        m, lp, seqs=B, top_k=model["top_k"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        routed_scaling_factor=model["routed_scaling_factor"],
        first_expert=model.get("first_expert", 0), **(moe_how or {}),
    )
    return h + f.reshape(h.shape), logits, balance


def embed(weights: dict, tokens):
    return _f32(weights["embed_tokens"][tokens])


def hidden(weights: dict, tokens, *, q_block: int = 512, **model):
    """``tokens`` (B, T) through the layers: the residual stream (B, T, d)
    before the final norm, each EXPERT layer's router logits (B*T, E), and
    the three balance losses summed over the expert layers."""
    h = embed(weights, tokens)
    logits, sums = [], [0.0, 0.0, 0.0]
    for lp in weights["layers"]:
        h, layer_logits, balance = layer(h, lp, q_block=q_block, **model)
        if layer_logits is not None:
            logits.append(layer_logits)
            sums = [s + b for s, b in zip(sums, balance)]
    return h, logits, tuple(sums)


def head(weights: dict, h):
    return rms_norm(h, weights["norm"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def weighted(balance, alphas) -> jax.Array:
    """``a1 L_exp + a2 L_dev + a3 L_comm``."""
    return sum(a * b for a, b in zip(alphas, balance))


def loss(weights: dict, tokens, targets, *, alphas, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL plus the weighted balance losses of every expert layer.
    ``jax.grad`` of it gives the reference gradients."""
    h, _, balance = hidden(weights, tokens, **model)
    return nll_from_hidden(weights, h, targets) + weighted(balance, alphas)


def routing_facts(router_logits, top_k: int, n_group: int, topk_group: int):
    """From one layer's router logits (N, E), by the same rule as
    :func:`route` (softmax is monotone in a token's logits, so groups and
    experts rank alike): tokens an expert (E,); tokens whose kept groups
    include each group (n_group,); and a token's distance from a tie in
    bf16 spacings (2^-8) of the layer's logit RMS, the smaller of the gap
    between its ``topk_group``-th and next GROUP and the gap between its
    ``top_k``-th and next expert among the kept."""
    N, E = router_logits.shape
    per = E // n_group
    group = router_logits.reshape(N, n_group, per).max(axis=-1)
    top_g, keep = jax.lax.top_k(group, topk_group + 1)
    kept = jnp.sum(
        jax.nn.one_hot(keep[:, :topk_group], n_group, dtype=jnp.int32), axis=1
    )
    masked = jnp.where(
        jnp.repeat(kept, per, axis=1) > 0, router_logits, -jnp.inf
    )
    top, top_e = jax.lax.top_k(masked, top_k + 1)
    counts = jnp.sum(
        jax.nn.one_hot(top_e[:, :top_k], E, dtype=jnp.int32), axis=(0, 1)
    )
    spacing = 2.0 ** -8 * jnp.sqrt(jnp.mean(router_logits ** 2))
    gap = jnp.minimum(
        top_g[:, topk_group - 1] - top_g[:, topk_group],
        top[:, top_k - 1] - top[:, top_k],
    ) / spacing
    return counts, kept.sum(axis=0), gap
