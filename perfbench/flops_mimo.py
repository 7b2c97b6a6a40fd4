"""Parameters, operations and bytes of the mimo_v2 block (MiMo-V2.5) AS ONE
CHIP OF AN EXPERT-PARALLEL GROUP EXECUTES IT, from shapes (``config.json``'s
keys) and from the program's own count of the routing entries held here:
nothing asks the compiler, so no change to the program can move a figure.

A layer at the published widths: a FULL mixer (4 KV heads) ``wq`` 4096 x
12,288 = 50.33 M, ``wk`` 4096 x 768 = 3.15 M, ``wv`` 4096 x 512 = 2.10 M,
``wo`` 8192 x 4096 = 33.55 M: 89.13 M; a SLIDING one (8 KV heads) 94.37 M;
one routed expert 3 x 4096 x 2048 = 25.17 M; the router 4096 x 256 = 1.05 M;
layer 0's dense MLP 3 x 4096 x 16,384 = 201.33 M; an eighth of the untied
vocabulary 2 x 19,072 x 4096 = 156.24 M.  Whole: 308,778,780,864 (the
family's "309B"); held here 3,429,892,096 in matrices.

Attention is counted by the pairs a query SEES (``T W - W (W - 1) / 2`` under
the window of 128, ``T (T + 1) / 2`` on a full layer) at scores over 192
columns and values of 128; the tiles' masked pairs, the flash backward's
rebuilt scores and ``remat``'s second forward are work the kernels do and
the model does not.
"""

from __future__ import annotations


def layer_kinds(cfg: dict, layers=None) -> list:
    """``(swa, moe)`` of each layer kept (``layers``: of those published
    indices instead), from ``hybrid_layer_pattern`` and ``moe_layer_freq``."""
    kept = cfg["layers_kept"] if layers is None else layers
    return [
        (bool(cfg["hybrid_layer_pattern"][i]), bool(cfg["moe_layer_freq"][i]))
        for i in kept
    ]


def kv_heads(cfg: dict, swa: bool) -> int:
    return cfg["swa_num_key_value_heads" if swa else "num_key_value_heads"]


def mixer_params(cfg: dict, swa: bool) -> int:
    """q, k, v and o of one layer of that kind."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, v = cfg["head_dim"], cfg["v_head_dim"]
    return d * heads * qk + d * kv_heads(cfg, swa) * (qk + v) + heads * v * d


def expert_params(cfg: dict) -> int:
    """One routed gated-SiLU expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg: dict, layers=None, experts=None, vocab=None) -> int:
    """Parameters in matrices: the mixers, the dense MLP, each expert layer's
    router and ``experts`` experts (the file's held count where not given),
    the embedding and the untied head over ``vocab`` rows."""
    d = cfg["hidden_size"]
    experts = cfg["n_routed_experts"] if experts is None else experts
    vocab = cfg["vocab_size"] if vocab is None else vocab
    total = 2 * vocab * d
    for swa, moe in layer_kinds(cfg, layers):
        total += mixer_params(cfg, swa)
        if moe:
            total += d * cfg["num_router_experts"] + experts * expert_params(cfg)
        else:
            total += dense_mlp_params(cfg)
    return total


def parameter_count(cfg: dict, layers=None, experts=None, vocab=None) -> int:
    """Every parameter of the tree: the matrices, two norms a layer and the
    final one, a sink a query head in each sliding layer, a selection bias
    an expert of the router's in each expert layer."""
    kinds = layer_kinds(cfg, layers)
    d = cfg["hidden_size"]
    return (
        matmul_params(cfg, layers, experts, vocab)
        + (2 * len(kinds) + 1) * d
        + sum(swa for swa, _ in kinds) * cfg["num_attention_heads"]
        + sum(moe for _, moe in kinds) * cfg["num_router_experts"]
    )


def whole_model(cfg: dict) -> dict:
    """The arguments that make the counts the PUBLISHED model's."""
    p = cfg["published"]
    return dict(
        layers=range(p["num_hidden_layers"]), experts=p["n_routed_experts"],
        vocab=p["vocab_size"],
    )


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes through
    on this chip: the mixers, layer 0's MLP, each expert layer's router (all
    256 outputs) and the held slice of the untied head.  Not the routed
    experts (counted by entry), the embedding lookup, the norms, sinks or
    biases."""
    return (
        matmul_params(cfg, experts=0)
        - cfg["vocab_size"] * cfg["hidden_size"]
    )


def attended_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one head of one ``seq``-long sequence: every
    ``j <= i``, or under a window the ``0 <= i - j < window`` of them."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return seq * window - window * (window - 1) // 2


def attention_train_flops(cfg: dict, seq: int, swa: bool) -> float:
    """The attention core of ONE sequence through ONE layer of that kind,
    forward and backward: QK^T over ``head_dim`` columns and PV over
    ``v_head_dim`` forward (2 FLOP a multiply-add, every query head), twice
    that again backward."""
    window = cfg["sliding_window"] if swa else None
    columns = cfg["head_dim"] + cfg["v_head_dim"]
    forward = 2.0 * attended_pairs(seq, window) * (
        cfg["num_attention_heads"] * columns
    )
    return 3 * forward


def attention_train_bytes(cfg: dict, seq: int, swa: bool,
                          itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    heads, n_kv = cfg["num_attention_heads"], kv_heads(cfg, swa)
    q = seq * heads * cfg["head_dim"] * itemsize
    o = seq * heads * cfg["v_head_dim"] * itemsize
    k = seq * n_kv * cfg["head_dim"] * itemsize
    v = seq * n_kv * cfg["v_head_dim"] * itemsize
    return (q + k + v + o) + (q + k + v + 2 * o) + (q + k + v)


def train_flops_per_token(cfg: dict, seq: int, held_entries: float) -> float:
    """FLOPs the model does on this chip for one trained token, counted
    ONCE: 6 x the resident matmul parameters (forward 2, backward 4), 6 x an
    expert's parameters for each of the ``held_entries`` routing entries a
    token has on this chip (summed over the expert layers, as counted), and
    attention by the pairs a query sees; no recomputation, no optimizer,
    none of the sort, gather or sigmoid round the experts."""
    attention = sum(
        attention_train_flops(cfg, seq, swa) for swa, _ in layer_kinds(cfg)
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
    three matrices forward, for its input's gradient and for its own): each
    reads two of {rows in, rows out, the held matrices} and writes the
    third; ``entries`` over ``layers`` expert layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    one = entries * (d + f) + layers * cfg["n_routed_experts"] * d * f
    return 3 * 3 * one * itemsize
