"""Plain reference of the Nemotron-H hybrid decoder
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type``
``nemotron_h``), as ONE chip of its 8-way expert-parallel group computes it.

Straightforward ``jax.numpy`` in float32, no kernels, no chunks, no sort, no
buffer; written from ``config.json``'s keys and, where they say nothing, from
the Mamba-2 paper (arXiv:2405.21060) and the family's conventions (the
configuration file lists each such point under ``assumed``); independent of
``accl_tpu.models`` and ``accl_tpu.ops``.  Every block has ONE sub-layer,
named by its letter of ``hybrid_override_pattern``:

    h = embeddings[tokens]
    for each block:
        u = RMSNorm(h; norm)                                      (eps 1e-5)
        'M', Mamba-2 (128 heads of 64, 8 groups, state 128; head i in group
        i // 16):
            [z | xBC | dt] = u in_proj     widths 8192 | 8192 + 2 x 1024 | 128
            xBC  = silu(conv4(xBC) + conv_bias)  (causal, depthwise, 4 taps)
            x, B, C = xBC -> (T, 128, 64), (T, 8, 128), (T, 8, 128)
            dt   = softplus(dt + dt_bias)                   a value a head
            S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     A = -exp(A_log)
            y_t  = S_t C_t + D x_t         TOKEN BY TOKEN (:func:`ssm_recurrence`)
            f    = RMSNorm_group(y * silu(z); groups of 1024) out_proj
        '*', attention (32 query heads on 2 KV heads of 128, NO position):
            f    = softmax(q k^T * 128 ** -0.5, keys j <= i) v o_proj
        'E', LatentMoE:
            s    = sigmoid(u @ gate) over ALL 512 experts, float32
            sel  = top22(s + bias)
            w    = 5.0 * s[sel] / (sum s[sel] + 1e-20)         (without the bias)
            l    = u fc1_latent_proj                           (4096 -> 1024)
            r    = sum_{e in sel, e held} w_e relu(l up_e)^2 down_e
            f    = r fc2_latent_proj + relu(u shared_up)^2 shared_down
        h = h + f
    logits = RMSNorm(h; norm_f) @ lm_head                        (untied head)
    loss   = mean next-token NLL   (no auxiliary term; no prediction module)

THE SHARE.  ``experts.*`` hold the matrices of experts ``first_expert ..
first_expert + E_held`` of the router's 512; the router, its top 22 and the
weights are over all of them, and what an expert that is not held would
have added is left out (the model-configs guide, section 4).  ``W_up``
(``fc2_latent_proj``) is linear, so the shares' parts add up after it as
they would before it.  With all of them held this is the whole model.

Departures from the published code, none of which changes a value: a linear
weight is stored (in, out) and applied as ``x @ w``; a convolution's taps
are stored (tap, channel), the last tap the current token's; the held
experts are stacked on a leading axis and every held expert is applied to
EVERY token under a dense (tokens, held) weight mask, in a plain loop;
attention is computed in blocks of query rows; a batch is a loop over its
sequences, and a caller short of memory runs :func:`layer` a block at a time
(weights are upcast where they are used) and takes the gradients the same
way, last block first (``jax.vjp`` of :func:`layer`); three
``jax.checkpoint`` say what the gradient computes again in place of keeping
it (a stretch of the recurrence's states, a block of attention scores, an
expert's hidden rows).

Callers wrap calls in ``jax.default_matmul_precision("highest")``: on a TPU
a float32 matmul is otherwise done in one bf16 pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-5          # layer_norm_epsilon / norm_eps


def _f32(x):
    return x.astype(jnp.float32)


def rms_norm(x, weight):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + RMS_EPS) * _f32(weight)


silu = jax.nn.silu       # mamba_hidden_act


def relu2(x):            # mlp_hidden_act
    return jnp.square(jnp.maximum(x, 0.0))


def short_conv(x, taps, bias):
    """Causal depthwise convolution: ``x`` (T, C), ``taps`` (K, C), ``bias``
    (C,), zero left padding; ``y_t = bias + sum_i taps[i] x_{t - (K - 1) +
    i}``."""
    K = taps.shape[0]
    T = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return _f32(bias) + sum(_f32(taps[i]) * padded[i:i + T] for i in range(K))


def ssm_recurrence(x, B, C, dt, A, D):
    """The selective state-space recurrence, a token at a time: ``x`` (T, H,
    P), ``B`` and ``C`` (T, G, N), head ``i`` in group ``i // (H / G)``,
    ``dt`` (T, H), ``A`` and ``D`` (H,); ``S_0 = 0``; returns ``y`` (T, H,
    P)."""
    T, H, P = x.shape
    per = H // B.shape[1]
    of_head = lambda v: jnp.repeat(v, per, axis=1)         # (T, H, N)

    def token(S, xs):
        x_t, B_t, C_t, dt_t = xs
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        )
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    # the same tokens in the same order, in stretches whose states are
    # computed again for the gradient rather than kept (one state is H x P
    # x N: 8,192 of them are 34 GB at the published widths)
    stretch = math.gcd(T, 128)
    stretches = jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs))
    _, y = jax.lax.scan(
        stretches, jnp.zeros((H, P, B.shape[-1]), jnp.float32),
        jax.tree.map(
            lambda v: v.reshape(T // stretch, stretch, *v.shape[1:]),
            (x, of_head(B), of_head(C), dt),
        ),
    )
    return y.reshape(T, H, P) + D[None, :, None] * x


def mamba2(u, lp, *, mamba_num_heads: int, mamba_head_dim: int, n_groups: int,
           ssm_state_size: int, no_decay: bool = False, no_conv: bool = False,
           no_skip: bool = False, norm_before_gate: bool = False):
    """One sequence ``u`` (T, d) through the Mamba-2 mixer of a block
    (``no_decay``: the state never forgets, ``A = 0``; ``no_conv``: the
    projection straight into the SiLU; ``no_skip``: ``D = 0``;
    ``norm_before_gate``: the gate after the grouped norm: four ways of
    getting it wrong, for the tests and the chip's controls)."""
    T = u.shape[0]
    H, P, G, N = mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size
    inner = H * P
    zxbcdt = u @ _f32(lp["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N], axis=-1)
    if not no_conv:
        xBC = short_conv(xBC, lp["conv1d"], lp["conv1d_bias"])
    xBC = silu(xBC)
    x, B, C = jnp.split(xBC, [inner, inner + G * N], axis=-1)
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))          # no clamp
    A = -jnp.exp(_f32(lp["A_log"]))
    D = _f32(lp["D"])
    y = ssm_recurrence(
        x.reshape(T, H, P), B.reshape(T, G, N), C.reshape(T, G, N), dt,
        jnp.zeros_like(A) if no_decay else A,
        jnp.zeros_like(D) if no_skip else D,
    ).reshape(T, inner)
    grouped = lambda v: rms_norm(
        v.reshape(T, G, inner // G), jnp.ones((), jnp.float32)
    ).reshape(T, inner) * _f32(lp["mixer_norm"])
    if norm_before_gate:
        y = grouped(y) * silu(z)
    else:
        y = grouped(y * silu(z))
    return y @ _f32(lp["out_proj"])


def causal_attention(q, k, v, scale: float, q_block: int):
    """q: (T, H, d); k, v: (T, Hkv, d); one sequence, query rows ``q_block``
    at a time against all keys ``j <= i``; query head ``i`` on KV head ``i
    // (H / Hkv)``."""
    T, H, _ = q.shape
    per = H // k.shape[1]
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)
    cols = jnp.arange(T)

    @jax.checkpoint       # a block's scores again for the gradient, not kept
    def rows_from(start, qb):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(q_block)
        mask = rows[:, None] >= cols[None, :]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    # one block after another (rows past the end, which see every key, fill
    # the last block and are dropped)
    blocks = -(-T // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - T), (0, 0), (0, 0)))
    out = jax.lax.map(
        lambda xs: rows_from(*xs),
        (jnp.arange(blocks) * q_block, q.reshape(blocks, q_block, H, -1)),
    )
    return out.reshape(blocks * q_block, H, -1)[:T]


def attention(u, lp, *, n_head: int, n_kv_head: int, q_block: int):
    """One sequence ``u`` (T, d) through the attention mixer: grouped-query
    causal softmax attention, no position encoding, no bias."""
    T = u.shape[0]
    q = (u @ _f32(lp["q_proj"])).reshape(T, n_head, -1)
    k = (u @ _f32(lp["k_proj"])).reshape(T, n_kv_head, -1)
    v = (u @ _f32(lp["v_proj"])).reshape(T, n_kv_head, -1)
    out = causal_attention(q, k, v, q.shape[-1] ** -0.5, q_block)
    return out.reshape(T, -1) @ _f32(lp["o_proj"])


def route(scores, bias, top_k: int, scale: float, biased_weights: bool = False):
    """The (tokens, E) weight of every expert for every token: the choice on
    ``scores + bias``, the weights from ``scores`` alone, divided by their
    sum, times ``scale`` (``biased_weights``: a way of getting it wrong)."""
    E = scores.shape[1]
    pick = scores + _f32(bias)
    _, top_e = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(pick if biased_weights else scores, top_e, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return jnp.einsum("nk,nke->ne", w, jax.nn.one_hot(top_e, E, dtype=w.dtype))


def latent_moe(u, lp, *, top_k: int, routed_scaling_factor: float,
               first_expert: int = 0, shared: bool = True, up: bool = True,
               plain_relu: bool = False, no_latent: bool = False, **how):
    """``u`` (N, d) through the LatentMoE of a chip that holds experts
    ``first_expert ..`` (as many as ``experts.*`` stack); returns ``(out,
    scores + bias over all experts)``.  ``shared=False`` leaves the shared
    expert out and ``up=False`` returns the routed sum in the LATENT, before
    ``fc2_latent_proj`` (for the sum over the shares); ``plain_relu``: relu
    in place of its square; ``no_latent``: the first columns of ``u`` in
    place of ``u fc1_latent_proj`` and the routed sum padded with zeros in
    place of ``fc2_latent_proj`` (two ways of getting it wrong)."""
    act = (lambda v: jnp.maximum(v, 0.0)) if plain_relu else relu2
    scores = jax.nn.sigmoid(u @ _f32(lp["gate"]))
    weights = route(scores, lp["expert_bias"], top_k, routed_scaling_factor,
                    **how)
    held = lp["experts.up_proj"].shape[0]
    weights = weights[:, first_expert:first_expert + held]
    width = lp["experts.up_proj"].shape[1]
    l = u[:, :width] if no_latent else u @ _f32(lp["fc1_latent_proj"])

    @jax.checkpoint       # its hidden rows again for the gradient, not kept
    def expert(l, up_proj, down_proj, w):
        return w[:, None] * (act(l @ _f32(up_proj)) @ _f32(down_proj))

    def one_expert(acc, xs):
        return acc + expert(l, *xs), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(l),
        (lp["experts.up_proj"], lp["experts.down_proj"], weights.T),
    )
    if no_latent:
        out = jnp.pad(out, ((0, 0), (0, u.shape[1] - width)))
    elif up:
        out = out @ _f32(lp["fc2_latent_proj"])
    if shared:
        out = out + act(u @ _f32(lp["shared_experts.up_proj"])) @ _f32(
            lp["shared_experts.down_proj"]
        )
    return out, scores + _f32(lp["expert_bias"])


_MAMBA = ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size")


def layer(h, lp, *, q_block: int = 512, moe_how=None, mamba_how=None,
          **model):
    """The residual stream ``h`` (B, T, d) through one block: Mamba-2 where
    its weights have an ``A_log``, the LatentMoE where they have a ``gate``,
    else attention.  Returns ``(h, the router's scores + bias (B*T, E) or
    None)``."""
    B, T, _ = h.shape
    u = rms_norm(h, lp["norm"])
    if "gate" in lp:
        f, picked = latent_moe(
            u.reshape(B * T, -1), lp, top_k=model["top_k"],
            routed_scaling_factor=model["routed_scaling_factor"],
            first_expert=model.get("first_expert", 0), **(moe_how or {}),
        )
        return h + f.reshape(h.shape), picked
    if "A_log" in lp:
        mix = lambda x: mamba2(
            x, lp, **{k: model[k] for k in _MAMBA}, **(mamba_how or {}),
        )
    else:
        mix = lambda x: attention(
            x, lp, n_head=model["n_head"], n_kv_head=model["n_kv_head"],
            q_block=q_block,
        )
    return h + jnp.stack([mix(u[b]) for b in range(B)]), None


def embed(weights: dict, tokens):
    return _f32(weights["embeddings"][tokens])


def hidden(weights: dict, tokens, **model):
    """``tokens`` (B, T) through the blocks: the residual stream (B, T, d)
    before the final norm, and each EXPERT block's ``scores + bias``."""
    h = embed(weights, tokens)
    picked = []
    for lp in weights["layers"]:
        h, layer_picked = layer(h, lp, **model)
        if layer_picked is not None:
            picked.append(layer_picked)
    return h, picked


def head(weights: dict, h):
    return rms_norm(h, weights["norm_f"]) @ _f32(weights["lm_head"])


def nll_from_hidden(weights: dict, h, targets):
    logp = jax.nn.log_softmax(head(weights, h), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def loss(weights: dict, tokens, targets, **model):
    """The training loss of a batch ``tokens``, ``targets`` (B, T): mean
    next-token NLL (the sigmoid router adds no term; the prediction module
    is left out).  ``jax.grad`` of it gives the reference gradients."""
    h, _ = hidden(weights, tokens, **model)
    return nll_from_hidden(weights, h, targets)


def moved_bias(bias, counts, rate: float):
    """The bias after a step that sent ``counts`` tokens to each expert:
    towards the experts that got fewer than the mean."""
    c = _f32(counts)
    return _f32(bias) + rate * jnp.sign(jnp.mean(c) - c)


def routing_facts(picked, top_k: int):
    """From one block's ``scores + bias`` (N, E): tokens an expert (E,), and
    a token's distance from a tie in bf16 spacings (2^-8) of the block's
    score RMS, the gap between its ``top_k``-th and next expert."""
    E = picked.shape[1]
    top, top_e = jax.lax.top_k(picked, top_k + 1)
    counts = jnp.sum(
        jax.nn.one_hot(top_e[:, :top_k], E, dtype=jnp.int32), axis=(0, 1)
    )
    spacing = 2.0 ** -8 * jnp.sqrt(jnp.mean(picked ** 2))
    return counts, (top[:, top_k - 1] - top[:, top_k]) / spacing
