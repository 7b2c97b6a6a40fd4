"""Operations, bytes and parameter counts of the Olmo Hybrid block (three
Gated DeltaNet layers to one full-attention layer, dense) from shapes
(``config.json``'s keys): nothing asks the compiler, so no change to the
program can move a figure.  What recomputation (``remat``) and the flash
backward's rebuilt scores execute again is counted nowhere.

THE GATED DELTANET CORE (the device scope ``accl.attn::kda``: from
normalised q, k, v, the log-decay a head and beta to o) is counted by its
MATHEMATICS in the SCALAR chunked form at a chunk of ``GDN_CHUNK`` = 64
tokens, whatever computes it (the program today pads keys of 96 to 128 and
values of 192 to 256, puts the head's decay on every channel and splits the
exponents by halving, 1.78 times these products and seven masked products
where the count has one triangle: its own business, as the explicit inverse
is), a head a chunk, forward, with ``C`` the chunk, ``dk`` 96, ``dv`` 192 and
2 FLOP a multiply-add:

* ``A = tril(D * K K^T, -1)`` and ``P = tril(D * Q K^T)``, ``D[t, j] =
  exp(G_t - G_j)`` applied after the products: ``dk C (C - 1)`` and ``dk C
  (C + 1)``;
* ``(I + A) [U~ | W] = [V | Gamma K]`` by substitution: ``(dk + dv) C (C -
  1)``;
* ``U = U~ - W S``, ``S' = Gamma_C S + K^^T U`` and ``(Gamma Q) S``: three
  products of ``2 C dk dv``;
* ``P U``: ``dv C (C + 1)``;

and the backward twice the forward.  Its least bytes: q, k, v and o in the
activations' type, the log-decay ONE float32 a head a token, beta a head.

A layer at the published widths, parameters in matmuls: a Gated DeltaNet
mixer 88.70 M (wq, wk 3840 x 2880; wv, the gate 3840 x 5760; wo 5760 x 3840;
the decay's and beta's 3840 x 30), a full-attention mixer 58.98 M (four of
3840 x 3840), the MLP 126.81 M (three of 3840 x 11008); the head 385.35 M.
"""

from __future__ import annotations

#: the chunk the core's count is defined at
GDN_CHUNK = 64


def layer_mixers(cfg: dict) -> list:
    """``"full"`` or ``"linear"`` of each layer kept, from ``layer_types`` at
    its PUBLISHED index (``layers_kept``)."""
    kinds = {"linear_attention": "linear", "full_attention": "full"}
    return [kinds[cfg["layer_types"][i]] for i in cfg["layers_kept"]]


def _heads(cfg: dict):
    """``(key heads, key width, value width)`` of a Gated DeltaNet layer."""
    return (
        cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
        cfg["linear_value_head_dim"],
    )


def linear_matmul_params(cfg: dict) -> int:
    """wq, wk, wv, the output gate, wo, the decay's and beta's matrices of
    one Gated DeltaNet layer (the taps are no matmul)."""
    d = cfg["hidden_size"]
    H, dk, dv = _heads(cfg)
    return d * H * (2 * dk + 3 * dv) + 2 * d * H


def full_matmul_params(cfg: dict) -> int:
    """wq, wk, wv and wo of one full-attention layer."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    return 2 * d * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    ) * hd


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that every token passes
    through: each kept layer's mixer and MLP, and the untied head.  Not the
    embedding lookup, the norms or the taps."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for mixer in layer_mixers(cfg):
        total += mlp_params(cfg) + (
            linear_matmul_params(cfg) if mixer == "linear"
            else full_matmul_params(cfg)
        )
    return total


def parameter_count(cfg: dict, layers=None) -> int:
    """Every parameter of the model the keys describe over the published
    indices ``layers`` (the file's ``layers_kept`` unless given): matrices,
    taps, ``A_log``, ``dt_bias``, the head norm's scale, the QK-norms', two
    norms a layer, the final norm, the table and the untied head."""
    d = cfg["hidden_size"]
    H, dk, dv = _heads(cfg)
    layers = cfg["layers_kept"] if layers is None else layers
    linear = (
        linear_matmul_params(cfg)
        + cfg["linear_conv_kernel_dim"] * H * (2 * dk + dv) + 2 * H + dv
    )
    full = full_matmul_params(cfg) + 2 * d
    total = 2 * cfg["vocab_size"] * d + d
    for i in layers:
        kind = cfg["layer_types"][i]
        total += mlp_params(cfg) + 2 * d + (
            linear if kind == "linear_attention" else full
        )
    return total


def gdn_core_train_flops(cfg: dict, seq: int) -> float:
    """The Gated DeltaNet core of ONE sequence through ONE layer, forward
    and backward, by the module docstring's count."""
    H, dk, dv = _heads(cfg)
    C = GDN_CHUNK
    a_chunk = (
        dk * C * (C - 1) + dk * C * (C + 1) + (dk + dv) * C * (C - 1)
        + 3 * 2 * C * dk * dv + dv * C * (C + 1)
    )
    return 3.0 * H * -(-seq // C) * a_chunk


def gdn_core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v, the log-decay
    and beta and writes o; backward reads them and do and writes their five
    gradients."""
    H, dk, dv = _heads(cfg)
    inputs = (2 * dk + dv) * itemsize + 4 + 4         # q, k, v; g; beta
    return float(seq * H * (
        (inputs + dv * itemsize) + (inputs + dv * itemsize) + inputs
    ))


def attn_core_train_flops(cfg: dict, seq: int) -> float:
    """Causal attention of ONE sequence through ONE full-attention layer,
    forward and backward, by ``flops.py``'s product count (what
    ``flash_roofline_share`` counts): QK^T and PV forward over the pairs ``j
    <= i``, 2 FLOP a multiply-add, a head's width of them a pair, every
    head, and four such products backward; what the flash backward rebuilds
    is not counted."""
    pairs = seq * (seq + 1) // 2
    return 3 * 2 * 2.0 * pairs * cfg["hidden_size"]


def attn_core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (as many KV heads as
    query heads)."""
    one = seq * cfg["hidden_size"] * itemsize
    return 4 * one + 8 * one


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """FLOPs the model does for one trained token: 6 x the matmul
    parameters (forward 2, backward 4) and the two kinds of core, each in
    its layers; no recomputation, no optimizer."""
    mixers = layer_mixers(cfg)
    cores = (
        mixers.count("linear") * gdn_core_train_flops(cfg, seq)
        + mixers.count("full") * attn_core_train_flops(cfg, seq)
    ) / seq
    return 6.0 * matmul_params(cfg) + cores
