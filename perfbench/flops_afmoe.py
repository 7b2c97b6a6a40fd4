"""Operations and bytes of the afmoe block AS ONE CHIP OF AN
EXPERT-PARALLEL GROUP EXECUTES IT, from shapes (``config.json``'s keys)
and from the program's own count of the routing entries held here: nothing
asks the compiler, so no change to the program can move a figure.

``flops_olmoe.py`` assumes every routed entry is computed on the chip;
here ``num_experts`` of ``num_router_experts`` are held, so a token's
eight experts cost what the COUNTED held entries cost (about one in
eight), attention and the shared expert are whole, and the head is the
held slice of the vocabulary.

A layer at the published widths on this chip: attention 27.3 M parameters
in matmuls (q 8.39, k 1.05, v 1.05, gate 8.39, o 8.39), the router 0.26 M,
the shared expert 6.29 M, 16 held experts of 6.29 M; the dense layer's FFN
37.7 M; the head 51.2 M.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def attention_matmul_params(cfg: dict) -> int:
    """q, k, v, the gate and o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return 3 * d * q + 2 * d * kv


def expert_params(cfg: dict) -> int:
    """One gated-SiLU expert, routed or shared: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes
    through on this chip: attention, the dense layers' FFN, each expert
    layer's router (all ``num_router_experts`` outputs) and shared
    experts, and the held slice of the untied head.  Not the routed
    experts (counted by entry), the embedding lookup or the norms."""
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    sparse = d * cfg["num_router_experts"] + (
        cfg["num_shared_experts"] * expert_params(cfg)
    )
    return (
        layers * attention_matmul_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + (layers - dense) * sparse
        + d * cfg["vocab_size"]
    )


def attended_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head of one ``seq``-long sequence: every
    ``j <= i``, or under a window the ``0 <= i - j < window`` of them."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def attention_train_flops(cfg: dict, seq: int, window=None) -> float:
    """Attention of ONE sequence through ONE layer, forward and backward,
    by ``flops.py``'s product count: QK^T and PV forward (2 FLOP a
    multiply-add, ``head_dim`` of them a pair, every query head) and four
    such products backward; what the flash backward recomputes is not
    counted."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    forward = 2 * 2.0 * attended_pairs(seq, window) * width
    return 3 * forward


def attention_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    hd = cfg["head_dim"]
    q = seq * cfg["num_attention_heads"] * hd * itemsize
    k = seq * cfg["num_key_value_heads"] * hd * itemsize
    return (2 * q + 2 * k) + (4 * q + 2 * k) + (q + 2 * k)


def layer_windows(cfg: dict) -> list:
    """The window of each layer's attention (``None`` = full)."""
    return [
        cfg["sliding_window"] if kind == SLIDING else None
        for kind in cfg["layer_types"]
    ]


def train_flops_per_token(cfg: dict, seq: int, held_entries: float) -> float:
    """FLOPs this chip executes for one trained token: 6 x the resident
    matmul parameters (forward 2, backward 4), 6 x an expert's parameters
    for each of the ``held_entries`` routing entries a token has on this
    chip (summed over the expert layers, as counted), and window-exact
    attention; no recomputation, no optimizer, none of the sort, gather
    or sigmoid round the experts."""
    attention = sum(
        attention_train_flops(cfg, seq, w) for w in layer_windows(cfg)
    ) / seq
    return (
        6.0 * resident_matmul_params(cfg)
        + 6.0 * expert_params(cfg) * held_entries
        + attention
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
    the third; ``entries`` over ``layers`` expert layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    one = entries * (d + f) + layers * cfg["num_experts"] * d * f
    return 3 * 3 * one * itemsize
