"""Operations and bytes of the Ling-3.0 hybrid block AS ONE CHIP OF ITS
EXPERT-PARALLEL GROUP EXECUTES IT, from shapes (``config.json``'s keys) and
from the program's own count of the routing entries held here: nothing asks
the compiler, so no change to the program can move a figure.

As ``flops_deepseek_v2.py``: ``num_experts`` of ``num_router_experts`` are
held, so a token's eight experts cost what the COUNTED held entries cost
(about one in eight), the mixers and the shared expert are whole, and the
head is the held slice of the vocabulary.  What recomputation (``remat``)
and the flash backward's rebuilt scores execute again is counted nowhere.

THE KDA CORE (the device scope ``accl.attn::kda``: from normalised q, k, v,
the log-decay and beta to o) is counted by its MATHEMATICS in the chunked
form at a chunk of ``KDA_CHUNK`` = 64 tokens, whatever computes it (XLA's
lowering today, a kernel later: ROADMAP M5), a head a chunk, forward, with
``C`` the chunk, ``dk = dv = head_dim`` and 2 FLOP a multiply-add:

* ``A = tril(K~ K~^T, -1)`` and ``P = tril(Q~ K~^T)`` (the decayed
  products, only the triangle that is used): ``dk C (C - 1)`` and
  ``dk C (C + 1)``;
* ``(I + A) [U~ | W] = [V | Gamma K]``, a unit lower triangular solve of
  ``dv + dk`` columns by substitution: ``(dk + dv) C (C - 1)`` (the
  program's explicit inverse by six squarings is its own business);
* ``U = U~ - W S``, ``S' = diag S + K^^T U`` and ``(Gamma Q) S``: three
  products of ``2 C dk dv``;
* ``P U``: ``dv C (C + 1)``;

and the backward twice the forward (each product has two transposes).  The
decays' exponentials and the elementwise products are not counted.  Its
least bytes: q, k, v and o in the activations' type, the log-decay in
float32 (the configuration keeps the gate in float32), beta a head.

A layer at the published widths on this chip: a KDA mixer 62.99 M
parameters in matmuls (wq, wk, wv, wf, wg, wo of 2560 x 4096 and wbeta
2560 x 32), the latent mixer 31.97 M (wq 15.73, wkv_a 1.47, wkv_b 4.19, wo
10.49, the gate 0.08), the router 1.31 M, the shared expert 5.90 M, 64
held experts of 5.90 M; the dense layer's FFN 47.19 M; the head 50.30 M.
"""

from __future__ import annotations

#: the chunk the KDA core's count is defined at
KDA_CHUNK = 64


def layer_kinds(cfg: dict):
    """``(mixer, ffn)`` of each layer kept, from its PUBLISHED index: the
    latent mixer iff ``(i + 1) % layer_group_size == 0``, else KDA; the
    dense FFN iff ``i < first_k_dense_replace``."""
    return [
        (
            "latent" if (i + 1) % cfg["layer_group_size"] == 0 else "kda",
            "dense" if i < cfg["first_k_dense_replace"] else "moe",
        )
        for i in cfg["layers_kept"]
    ]


def kda_matmul_params(cfg: dict) -> int:
    """wq, wk, wv, the decay gate wf, the output gate wg, wo and wbeta of
    one KDA layer (the convolutions' taps are no matmul)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return 6 * d * H * cfg["head_dim"] + d * H


def latent_matmul_params(cfg: dict) -> int:
    """wq (no q latent), wkv_a (latent and the rope key), wkv_b, wo and
    the head-wise gate of one latent layer."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    rkv = cfg["kv_lora_rank"]
    return (
        d * H * (dn + dr) + d * (rkv + dr) + rkv * H * (dn + dv)
        + H * dv * d + d * H
    )


def expert_params(cfg: dict) -> int:
    """One routed gated-SiLU expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes
    through on this chip: each layer's mixer, the dense layers' FFN, each
    expert layer's router (all ``num_router_experts`` outputs) and shared
    expert, and the held slice of the untied head.  Not the routed experts
    (counted by entry), the embedding lookup, the norms or the taps."""
    d = cfg["hidden_size"]
    shared = 3 * d * (
        cfg["num_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    )
    total = d * cfg["vocab_size"]
    for mixer, ffn in layer_kinds(cfg):
        total += (
            kda_matmul_params(cfg) if mixer == "kda"
            else latent_matmul_params(cfg)
        )
        total += (
            3 * d * cfg["intermediate_size"] if ffn == "dense"
            else d * cfg["num_router_experts"] + shared
        )
    return total


def kda_core_train_flops(cfg: dict, seq: int) -> float:
    """The KDA core of ONE sequence through ONE layer, forward and
    backward, by the module docstring's count."""
    C, H, dk = KDA_CHUNK, cfg["num_attention_heads"], cfg["head_dim"]
    dv = dk
    a_chunk = (
        dk * C * (C - 1) + dk * C * (C + 1) + (dk + dv) * C * (C - 1)
        + 3 * 2 * C * dk * dv + dv * C * (C + 1)
    )
    return 3.0 * H * -(-seq // C) * a_chunk


def kda_core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v, the log-decay
    and beta and writes o; backward reads them and do and writes their
    five gradients."""
    H, dk = cfg["num_attention_heads"], cfg["head_dim"]
    dv = dk
    inputs = (2 * dk + dv) * itemsize + dk * 4 + 4    # q, k, v; g; beta
    return float(seq * H * (
        (inputs + dv * itemsize) + (inputs + dv * itemsize) + inputs
    ))


def core_train_flops(cfg: dict, seq: int) -> float:
    """The latent attention core of ONE sequence through ONE layer,
    forward and backward, at its REAL widths by ``flops.py``'s product
    count (as ``flops_deepseek_v2.core_train_flops``)."""
    pairs = seq * (seq + 1) // 2
    width = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    return 3 * 2.0 * pairs * width


def core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same; the rope key is ONE head."""
    H = cfg["num_attention_heads"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    q = seq * H * (dn + dr) * itemsize
    k = seq * (H * dn + dr) * itemsize
    v = seq * H * dv * itemsize
    return (q + k + 2 * v) + (q + k + 3 * v) + (q + k + v)


def train_flops_per_token(cfg: dict, seq: int, held_entries: float) -> float:
    """FLOPs this chip's model does for one trained token: 6 x the
    resident matmul parameters (forward 2, backward 4), 6 x an expert's
    parameters for each of the ``held_entries`` routing entries a token
    has on this chip (summed over the expert layers, as counted), and the
    two kinds of core, each in its layers; no recomputation, no optimizer,
    none of the sort, gather or sigmoid round the experts."""
    mixers = [mixer for mixer, _ in layer_kinds(cfg)]
    cores = (
        mixers.count("kda") * kda_core_train_flops(cfg, seq)
        + mixers.count("latent") * core_train_flops(cfg, seq)
    ) / seq
    return (
        6.0 * resident_matmul_params(cfg)
        + 6.0 * expert_params(cfg) * held_entries
        + cores
    )


def expert_train_flops(cfg: dict, entries: float) -> float:
    """The grouped matmuls over ``entries`` held routing entries, forward
    and backward: 3 x 2 x entries x 3 x d x f."""
    return 3 * 2.0 * entries * expert_params(cfg)


def expert_train_bytes(cfg: dict, entries: float, itemsize: int = 2) -> float:
    """Least HBM traffic of the nine grouped matmuls a layer (each of the
    three matrices forward, for its input's gradient and for its own):
    each reads two of {rows in, rows out, the held matrices} and writes
    the third; ``entries`` over all the expert layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = sum(ffn == "moe" for _, ffn in layer_kinds(cfg))
    one = entries * (d + f) + layers * cfg["num_experts"] * d * f
    return 3 * 3 * one * itemsize
