"""Operations and bytes of the DeepSeek-V2 block AS ONE CHIP OF ITS
EXPERT-PARALLEL GROUP EXECUTES IT, from shapes (``config.json``'s keys)
and from the program's own count of the routing entries held here: nothing
asks the compiler, so no change to the program can move a figure.

As ``flops_afmoe.py``: ``n_routed_experts`` of ``num_router_experts`` are
held, so a token's six experts cost what the COUNTED held entries cost
(about one in eight), the latent attention and the shared experts are
whole, and the head is the held slice of the vocabulary.  What
recomputation (``remat``) and the flash backward's rebuilt scores execute
again is not counted anywhere here.

A layer at the published widths on this chip: the latent mixer 149.2 M
parameters in matmuls (q_a 7.86, q_b 37.75, kv_a 2.95, kv_b 16.78, o
83.89), the router 0.82 M, the two shared experts 47.19 M, 20 held experts
of 23.59 M; the dense layer's FFN 188.7 M; the head 65.5 M.
"""

from __future__ import annotations


def latent_matmul_params(cfg: dict) -> int:
    """q_a, q_b, kv_a (latent and the rope key), kv_b and o of one layer."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    )
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (
        d * rq + rq * H * (dn + dr) + d * (rkv + dr)
        + rkv * H * (dn + dv) + H * dv * d
    )


def expert_params(cfg: dict) -> int:
    """One routed gated-SiLU expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes
    through on this chip: the latent mixer, the dense layers' FFN, each
    expert layer's router (all ``num_router_experts`` outputs) and shared
    experts, and the held slice of the untied head.  Not the routed
    experts (counted by entry), the embedding lookup or the norms."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    sparse = d * cfg["num_router_experts"] + (
        cfg["n_shared_experts"] * expert_params(cfg)
    )
    return (
        layers * latent_matmul_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + (layers - dense) * sparse
        + d * cfg["vocab_size"]
    )


def core_train_flops(cfg: dict, seq: int) -> float:
    """The attention core of ONE sequence through ONE layer, forward and
    backward, at its REAL widths by ``flops.py``'s product count: scores
    over ``qk_nope_head_dim + qk_rope_head_dim`` columns and values over
    ``v_head_dim`` forward (2 FLOP a multiply-add, every ``j <= i``, every
    head), four such products backward; padding columns count as nothing."""
    pairs = seq * (seq + 1) // 2
    width = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    )
    return 3 * 2.0 * pairs * width


def core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv; the rope key is
    ONE head."""
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
    attention core at its real widths; no recomputation, no optimizer,
    none of the sort, gather or softmax round the experts."""
    core = cfg["num_hidden_layers"] * core_train_flops(cfg, seq) / seq
    return (
        6.0 * resident_matmul_params(cfg)
        + 6.0 * expert_params(cfg) * held_entries
        + core
    )


def expert_train_flops(cfg: dict, entries: float) -> float:
    """The grouped matmuls over ``entries`` held routing entries, forward
    and backward: 3 x 2 x entries x 3 x d x f."""
    return 3 * 2.0 * entries * expert_params(cfg)


def expert_train_bytes(cfg: dict, entries: float, layers: int,
                       itemsize: int = 2) -> float:
    """Least HBM traffic of the nine grouped matmuls a layer (each of the
    three matrices forward, for its input's gradient and for its own):
    each reads two of {rows in, rows out, the held matrices} and writes
    the third; ``entries`` over ``layers`` expert layers.  The held
    matrices are 20 x 47 MB a layer a pass: they, not the rows, are most
    of it at 154 rows an expert."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    one = entries * (d + f) + layers * cfg["n_routed_experts"] * d * f
    return 3 * 3 * one * itemsize
