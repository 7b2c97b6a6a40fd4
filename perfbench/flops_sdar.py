"""Operations and bytes of SDAR-30B-A3B-Chat's block under block-diffusion
training AS ONE CHIP OF ITS EXPERT-PARALLEL GROUP EXECUTES IT, from shapes
(``config.json``'s keys, the block length, the traffic's batch and length)
and from the program's own count of the routing entries held here: nothing
asks the compiler, so no change to the program can move a figure.

A step runs ``[noisy ; clean]``: ``2 L`` ROWS a sequence through every
layer, the head over the noisy half's ``L`` rows only, and tokens/s counts
the ``L`` DATA tokens; so the figures here are a STEP's, and a token's is
the step's over ``batch * L``.  As ``flops_afmoe.py``: ``num_experts`` of
``num_router_experts`` are held, so a row's eight experts cost what the
COUNTED held entries cost (about one in eight), attention is whole, and
the head is the held slice of the vocabulary.  What the flash backward
rebuilds is not counted anywhere here.

A layer at the published widths on this chip: attention 18.87 M parameters
in matmuls (q 8.39, k and v 1.05 each, o 8.39), the router 0.26 M, 16 held
experts of 4.72 M; the head 38.9 M.  The attention core by its LIVE (query,
key) pairs under the layout: ``L B`` noisy on noisy, ``B^2 n (n - 1) / 2``
noisy on clean and ``B^2 n (n + 1) / 2`` clean on clean over ``n = L / B``
blocks: 16,793,600 a head a sequence at L = 4096, B = 4.
"""

from __future__ import annotations


def block_length(cfg: dict) -> int:
    return int(cfg["assumed"]["block_length"])


def attention_matmul_params(cfg: dict) -> int:
    """q, k, v and o of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(cfg: dict) -> int:
    """One routed gated-SiLU expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def row_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY ROW of ``[noisy ;
    clean]`` passes through on this chip: each layer's attention matrices
    and router (all ``num_router_experts`` outputs).  Not the routed
    experts (counted by entry), the head (the noisy half's rows only), the
    embedding lookup or the norms."""
    return cfg["num_hidden_layers"] * (
        attention_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["num_router_experts"]
    )


def head_params(cfg: dict) -> int:
    """The held slice of the untied head."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def live_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the layout leaves live for one head of one
    sequence of ``seq`` ids in blocks of ``block``."""
    n = seq // block
    return (
        seq * block
        + block * block * (n * (n - 1) // 2)
        + block * block * (n * (n + 1) // 2)
    )


def core_train_flops(cfg: dict, seq: int) -> float:
    """The attention core of ONE sequence (``2 seq`` rows) through ONE
    layer, forward and backward, by ``flops.py``'s product count: scores
    and values forward (2 FLOP a multiply-add, every live pair, every
    head), four such products backward."""
    width = cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    return 3 * 2.0 * live_pairs(seq, block_length(cfg)) * width


def core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv; ``2 seq`` rows."""
    hd, rows = cfg["head_dim"], 2 * seq
    q = rows * cfg["num_attention_heads"] * hd * itemsize
    k = rows * cfg["num_key_value_heads"] * hd * itemsize
    return (2 * q + 2 * k) + (4 * q + 2 * k) + (q + 2 * k)


def train_flops_per_step(cfg: dict, seq: int, batch: int,
                         held_entries: float) -> float:
    """FLOPs this chip's model does for one step of ``batch`` sequences of
    ``seq`` ids: 6 x (forward 2, backward 4) the row parameters for each
    of the ``2 batch seq`` rows, the head's for the noisy half's ``batch
    seq`` rows, an expert's for each of the step's ``held_entries``
    routing entries held here (summed over the layers, as counted), and
    the attention core by its live pairs; no recomputation, no optimizer,
    none of the sort, gather or softmax round the experts."""
    rows = 2 * batch * seq
    return (
        6.0 * row_matmul_params(cfg) * rows
        + 6.0 * head_params(cfg) * batch * seq
        + 6.0 * expert_params(cfg) * held_entries
        + cfg["num_hidden_layers"] * batch * core_train_flops(cfg, seq)
    )


def expert_train_flops(cfg: dict, entries: float) -> float:
    """The grouped matmuls over ``entries`` held routing entries, forward
    and backward: 3 x 2 x entries x 3 x d x f."""
    return 3 * 2.0 * entries * expert_params(cfg)


def expert_train_bytes(cfg: dict, entries: float, itemsize: int = 2) -> float:
    """Least HBM traffic of the nine grouped matmuls a layer (each of the
    three matrices forward, for its input's gradient and for its own):
    each reads two of {rows in, rows out, the held matrices} and writes
    the third; ``entries`` over all the layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    one = entries * (d + f) + (
        cfg["num_hidden_layers"] * cfg["num_experts"] * d * f
    )
    return 3 * 3 * one * itemsize
