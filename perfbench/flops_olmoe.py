"""Operations and bytes of the OLMoE block from shapes (``OlmoeConfig``
keys), as ``flops.py`` has them for the dense decoder: nothing here asks
the compiler, so no change to the program can move a figure.

A layer at the published widths: 419.6 M parameters (4 x 2048^2 in the
attention projections, 2048 x 64 in the router, 64 x 3 x 2048 x 1024 in
the experts, 8,192 in the four norms), of which 67.2 M sit in a matmul a
token passes through; 151.3 MFLOP forward a token at T=4096 (33.6 in the
projections, 0.3 in the router, 100.7 in the eight experts, 16.8 in
causal attention).
"""

from __future__ import annotations


def layer_params(cfg: dict) -> int:
    """Every parameter of one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = hd * cfg["num_key_value_heads"]
    attn = 2 * d * d + 2 * d * kv
    norms = 2 * d + d + kv                 # two block norms, q_norm, k_norm
    return attn + d * cfg["num_experts"] + cfg["num_experts"] * 3 * d * f + norms


def active_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that ONE token passes
    through: q, k, v, o, the router, ``num_experts_per_tok`` experts of
    three matrices a layer, and the untied LM head.  The embedding lookup
    and the norms are not matmuls."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    kv = hd * cfg["num_key_value_heads"]
    layer = (
        2 * d * d + 2 * d * kv
        + d * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * 3 * d * f
    )
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def attention_forward_flops_per_token(cfg: dict, seq: int) -> float:
    """QK^T and PV of causal attention, one layer, a token of a
    ``seq``-long sequence: 2 x 2*seq*d, halved by the mask."""
    return 2 * (2.0 * seq * cfg["hidden_size"]) / 2


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """One LAYER's forward FLOPs a token (no head)."""
    layer = (active_matmul_params(cfg)
             - cfg["hidden_size"] * cfg["vocab_size"]) / cfg["num_hidden_layers"]
    return 2.0 * layer + attention_forward_flops_per_token(cfg, seq)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token: 6 x active matmul parameters
    (forward 2, backward 4) plus causal attention forward and backward
    (3 x forward); no recomputation, no optimizer, and none of the
    top-k, sort, gather or softmax round the experts."""
    attn = 3 * attention_forward_flops_per_token(cfg, seq)
    return 6.0 * active_matmul_params(cfg) + cfg["num_hidden_layers"] * attn


def expert_train_flops(cfg: dict, tokens: int) -> float:
    """The grouped matmuls of ONE layer's expert bank over ``tokens``
    tokens, forward and backward: 3 x 2 x tokens x k x 3 x d x f."""
    return (3 * 2.0 * tokens * cfg["num_experts_per_tok"] * 3
            * cfg["hidden_size"] * cfg["intermediate_size"])


def expert_train_bytes(cfg: dict, tokens: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same nine grouped matmuls (each of the
    three matrices forward, for its input's gradient and for its own):
    each reads two of {rows in, rows out, the 64 matrices} and writes the
    third, all three once for every matrix and direction."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    rows = tokens * cfg["num_experts_per_tok"]
    one = rows * (d + f) + cfg["num_experts"] * d * f
    return 3 * 3 * one * itemsize
