"""Operations, bytes and parameter counts of the Solar Open 2 hybrid block AS
ONE CHIP OF ITS EXPERT-PARALLEL GROUP EXECUTES IT, from shapes
(``config.json``'s keys) and from the program's own count of the routing
entries held here: nothing asks the compiler, so no change to the program
can move a figure.

As ``flops_ling3.py`` (a copy of its counts where the mathematics is the
same, not an import): ``n_routed_experts`` of ``num_router_experts`` are
held, so a token's eight experts cost what the COUNTED held entries cost
(about one in eight), the mixers and the shared expert are whole, and the
head is the held slice of the vocabulary.  What recomputation (``remat``)
and the flash backward's rebuilt scores execute again is counted nowhere.

THE KDA CORE (the device scope ``accl.attn::kda``: from normalised q, k, v,
the log-decay and beta to o) is counted by its MATHEMATICS in the chunked
form at a chunk of ``KDA_CHUNK`` = 64 tokens, whatever computes it and
however it splits its exponents (the unbounded gate's split by halving
takes seven masked products where the count has one triangle: its own
business, as the explicit inverse is), a head a chunk, forward, with ``C``
the chunk, ``dk = dv = head_dim`` and 2 FLOP a multiply-add:

* ``A = tril(K~ K~^T, -1)`` and ``P = tril(Q~ K~^T)``: ``dk C (C - 1)`` and
  ``dk C (C + 1)``;
* ``(I + A) [U~ | W] = [V | Gamma K]`` by substitution: ``(dk + dv) C (C -
  1)``;
* ``U = U~ - W S``, ``S' = diag S + K^^T U`` and ``(Gamma Q) S``: three
  products of ``2 C dk dv``;
* ``P U``: ``dv C (C + 1)``;

and the backward twice the forward.  Its least bytes: q, k, v and o in the
activations' type, the log-decay in float32, beta a head.

A layer at the published widths on this chip, parameters in matmuls: a KDA
mixer 137.63 M (wq, wk, wv, wo of 4096 x 8192, the two gates' 4096 x 128 and
128 x 8192 each, beta 4096 x 64), a GQA mixer 109.05 M (wq and the gate 4096
x 8192, wk and wv 4096 x 1024, wo), the router 1.31 M, the shared expert
15.73 M, 40 held experts of 15.73 M; the head 100.66 M.
"""

from __future__ import annotations

#: the chunk the KDA core's count is defined at
KDA_CHUNK = 64


def layer_mixers(cfg: dict) -> list:
    """``"gqa"`` or ``"kda"`` of each layer kept, from its PUBLISHED index:
    softmax attention iff the index is in ``gqa_layers``."""
    return ["gqa" if i in cfg["gqa_layers"] else "kda" for i in cfg["layers_kept"]]


def kda_matmul_params(cfg: dict) -> int:
    """wq, wk, wv, wo, the decay gate's and the output gate's two matrices
    through the rank, and wbeta of one KDA layer (the taps are no matmul)."""
    d = cfg["hidden_size"]
    la = cfg["linear_attn_config"]
    H, hd = la["num_heads"], la["head_dim"]
    rank = hd                              # kda_use_full_proj false
    return 4 * d * H * hd + 2 * (d * rank + rank * H * hd) + d * H


def gqa_matmul_params(cfg: dict) -> int:
    """wq, the gate a channel, wk, wv and wo of one softmax layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 3 * d * q + 2 * d * kv


def expert_params(cfg: dict) -> int:
    """One routed gated-SiLU expert: three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["n_shared_experts"] * expert_params(cfg)


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes through
    on this chip: each layer's mixer, router (all ``num_router_experts``
    outputs) and shared expert, and the held slice of the untied head.  Not
    the routed experts (counted by entry), the embedding lookup, the norms
    or the taps."""
    d = cfg["hidden_size"]
    total = d * cfg["vocab_size"]
    for mixer in layer_mixers(cfg):
        total += kda_matmul_params(cfg) if mixer == "kda" else gqa_matmul_params(cfg)
        total += d * cfg["num_router_experts"] + shared_params(cfg)
    return total


def parameter_count(cfg: dict, layers=None, experts=None, vocab=None,
                    active: bool = False) -> int:
    """Every parameter of the model the keys describe: ``layers`` published
    indices (the file's ``layers_kept``), ``experts`` routed experts a layer
    (its ``n_routed_experts``) and ``vocab`` rows of the table and of the
    head (its ``vocab_size``) unless given; ``active``: a token's, the
    experts per token in the routed experts' place.  Norms, taps, ``A_log``,
    ``dt_bias``, the head norm and the expert bias count."""
    d = cfg["hidden_size"]
    la = cfg["linear_attn_config"]
    wide = la["num_heads"] * la["head_dim"]
    layers = cfg["layers_kept"] if layers is None else layers
    experts = cfg["n_routed_experts"] if experts is None else experts
    vocab = cfg["vocab_size"] if vocab is None else vocab
    kda = (
        kda_matmul_params(cfg) + 3 * la["short_conv_kernel_size"] * wide
        + la["num_heads"] + wide + la["head_dim"]
    )
    routed = cfg["num_experts_per_tok"] if active else experts
    a_layer = (
        2 * d + d * cfg["num_router_experts"] + cfg["num_router_experts"]
        + shared_params(cfg) + routed * expert_params(cfg)
    )
    total = 2 * vocab * d + d
    for i in layers:
        total += a_layer + (gqa_matmul_params(cfg) if i in cfg["gqa_layers"] else kda)
    return total


def kda_core_train_flops(cfg: dict, seq: int) -> float:
    """The KDA core of ONE sequence through ONE layer, forward and
    backward, by the module docstring's count."""
    la = cfg["linear_attn_config"]
    C, H, dk = KDA_CHUNK, la["num_heads"], la["head_dim"]
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
    la = cfg["linear_attn_config"]
    H, dk = la["num_heads"], la["head_dim"]
    dv = dk
    inputs = (2 * dk + dv) * itemsize + dk * 4 + 4    # q, k, v; g; beta
    return float(seq * H * (
        (inputs + dv * itemsize) + (inputs + dv * itemsize) + inputs
    ))


def gqa_core_train_flops(cfg: dict, seq: int) -> float:
    """Causal attention of ONE sequence through ONE softmax layer, forward
    and backward, by ``flops.py``'s product count (what ``flash_roofline_
    share`` counts): QK^T and PV forward over the pairs ``j <= i``, 2 FLOP a
    multiply-add, ``head_dim`` of them a pair, every query head, and four
    such products backward; what the flash backward rebuilds is not
    counted."""
    pairs = seq * (seq + 1) // 2
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 3 * 2 * 2.0 * pairs * width


def gqa_core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv."""
    hd = cfg["head_dim"]
    q = seq * cfg["num_attention_heads"] * hd * itemsize
    k = seq * cfg["num_key_value_heads"] * hd * itemsize
    return (2 * q + 2 * k) + (4 * q + 2 * k) + (q + 2 * k)


def train_flops_per_token(cfg: dict, seq: int, held_entries: float) -> float:
    """FLOPs this chip's model does for one trained token: 6 x the resident
    matmul parameters (forward 2, backward 4), 6 x an expert's parameters
    for each of the ``held_entries`` routing entries a token has on this
    chip (summed over the layers, as counted), and the two kinds of core,
    each in its layers; no recomputation, no optimizer, none of the sort,
    gather or sigmoid round the experts."""
    mixers = layer_mixers(cfg)
    cores = (
        mixers.count("kda") * kda_core_train_flops(cfg, seq)
        + mixers.count("gqa") * gqa_core_train_flops(cfg, seq)
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
    three matrices forward, for its input's gradient and for its own): each
    reads two of {rows in, rows out, the held matrices} and writes the
    third; ``entries`` over all the layers."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    one = entries * (d + f) + len(cfg["layers_kept"]) * cfg["n_routed_experts"] * d * f
    return 3 * 3 * one * itemsize
