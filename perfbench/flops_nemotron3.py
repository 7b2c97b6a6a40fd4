"""Operations and bytes of the Nemotron-H hybrid block AS ONE CHIP OF ITS
EXPERT-PARALLEL GROUP EXECUTES IT, from shapes (``config.json``'s keys) and
from the program's own count of the routing entries held here: nothing asks
the compiler, so no change to the program can move a figure.

As ``flops_ling3.py``: ``n_routed_experts`` of ``num_router_experts`` are
held, so a token's 22 experts cost what the COUNTED held entries cost (about
one in eight), the mixers, the latent projections and the shared expert are
whole, and the head is the held slice of the vocabulary.  What recomputation
(``remat``) and the flash backward's rebuilt scores execute again is counted
nowhere.

THE SSD CORE (the device scope ``accl.attn::ssd``: from x, B, C and dt to y)
is counted by its MATHEMATICS in the chunked form at the published
``chunk_size`` of 128 tokens, whatever computes it (XLA's lowering today, a
kernel later: ROADMAP M5), a head a chunk, forward, with ``L`` the chunk,
``P`` the head's width, ``N`` the state's and 2 FLOP a multiply-add:

* ``tril(C B^T)``, only the triangle that is used and ONCE A GROUP (its 16
  heads share it): ``N L (L + 1) / 16``;
* the masked ``(C B^T . decay) x``: ``P L (L + 1)``;
* what the chunk writes to the state, ``x^T B``, and what the state it
  started from answers, ``C S``: two products of ``2 L P N``;

and the backward twice the forward (each product has two transposes).  The
decays' exponentials, the cumulative sums and the elementwise products are
not counted.  Its least bytes: x, B, C and y in the activations' type, dt in
float32 a head (the configuration keeps the step in float32).

A block at the published widths on this chip: a Mamba-2 mixer 109.58 M
parameters in matmuls (W_in 4096 x 18,560, W_out 8192 x 4096), the attention
block 35.65 M (wq and wo 16.78 each, wk and wv 1.05 each), an expert block
outside its routed experts 54.53 M (the router 2.10, W_down and W_up 4.19
each, the shared expert 44.04), 64 held experts of 5.51 M; the head 67.11 M.
"""

from __future__ import annotations


def layer_letters(cfg: dict) -> str:
    """The letter of each block kept, from its PUBLISHED index in
    ``hybrid_override_pattern`` (``M`` Mamba-2, ``E`` the expert layer,
    ``*`` attention, ``-`` a dense MLP)."""
    return "".join(cfg["hybrid_override_pattern"][i] for i in cfg["layers_kept"])


def mamba_matmul_params(cfg: dict) -> int:
    """``W_in`` ([z | x | B | C | dt]) and ``W_out`` of one Mamba-2 block
    (the convolution's taps are no matmul)."""
    d, H = cfg["hidden_size"], cfg["mamba_num_heads"]
    inner = H * cfg["mamba_head_dim"]
    groups = cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (2 * inner + 2 * groups + H) + inner * d


def attention_matmul_params(cfg: dict) -> int:
    """wq, wk, wv and wo of one attention block."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(cfg: dict) -> int:
    """One routed relu2 expert in the latent: two matrices."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_block_resident_params(cfg: dict) -> int:
    """What EVERY token passes through in an expert block on this chip: the
    router (all ``num_router_experts`` outputs), ``W_down`` and ``W_up``,
    and the shared expert's two matrices."""
    d = cfg["hidden_size"]
    shared = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
    return (
        d * cfg["num_router_experts"] + 2 * d * cfg["moe_latent_size"]
        + 2 * d * shared
    )


def resident_matmul_params(cfg: dict) -> int:
    """Parameters in a matrix multiplication that EVERY token passes
    through on this chip: each block's own and the held slice of the untied
    head.  Not the routed experts (counted by entry), the embedding lookup,
    the norms or the taps."""
    d = cfg["hidden_size"]
    a_block = {
        "M": mamba_matmul_params(cfg),
        "*": attention_matmul_params(cfg),
        "E": expert_block_resident_params(cfg),
        "-": 2 * d * cfg["intermediate_size"],
    }
    return d * cfg["vocab_size"] + sum(
        a_block[letter] for letter in layer_letters(cfg)
    )


def ssd_core_train_flops(cfg: dict, seq: int) -> float:
    """The SSD core of ONE sequence through ONE block, forward and
    backward, by the module docstring's count."""
    L, H = cfg["chunk_size"], cfg["mamba_num_heads"]
    P, N = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    per = H // cfg["n_groups"]
    a_chunk = N * L * (L + 1) / per + P * L * (L + 1) + 2 * 2 * L * P * N
    return 3.0 * H * -(-seq // L) * a_chunk


def ssd_core_train_bytes(cfg: dict, seq: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same: forward reads x, B, C and dt and
    writes y; backward reads them and dy and writes their four gradients
    (``A`` and ``D`` are scalars a head)."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups = cfg["n_groups"] * cfg["ssm_state_size"]
    inputs = (H * P + 2 * groups) * itemsize + H * 4      # x, B, C; dt
    y = H * P * itemsize
    return float(seq * ((inputs + y) + (inputs + y) + inputs))


def attention_train_flops(cfg: dict, seq: int) -> float:
    """Causal attention of ONE sequence through ONE block, forward and
    backward, by ``flops.py``'s product count (QK^T and PV forward over the
    pairs ``j <= i``, four such products backward)."""
    pairs = seq * (seq + 1) // 2
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 3 * 2 * 2.0 * pairs * width


def train_flops_per_token(cfg: dict, seq: int, held_entries: float) -> float:
    """FLOPs this chip's model does for one trained token: 6 x the resident
    matmul parameters (forward 2, backward 4), 6 x an expert's parameters
    for each of the ``held_entries`` routing entries a token has on this
    chip (summed over the expert blocks, as counted), and the two kinds of
    core, each in its blocks; no recomputation, no optimizer, none of the
    sort, gather or sigmoid round the experts."""
    letters = layer_letters(cfg)
    cores = (
        letters.count("M") * ssd_core_train_flops(cfg, seq)
        + letters.count("*") * attention_train_flops(cfg, seq)
    ) / seq
    return (
        6.0 * resident_matmul_params(cfg)
        + 6.0 * expert_params(cfg) * held_entries
        + cores
    )


def expert_train_flops(cfg: dict, entries: float) -> float:
    """The grouped matmuls over ``entries`` held routing entries, forward
    and backward: 3 x 2 x entries x 2 x latent x f."""
    return 3 * 2.0 * entries * expert_params(cfg)


def expert_train_bytes(cfg: dict, entries: float, itemsize: int = 2) -> float:
    """Least HBM traffic of the six grouped matmuls a block (each of the two
    matrices forward, for its input's gradient and for its own): each reads
    two of {rows in, rows out, the held matrices} and writes the third;
    ``entries`` over all the expert blocks."""
    l, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    blocks = layer_letters(cfg).count("E")
    one = entries * (l + f) + blocks * cfg["n_routed_experts"] * l * f
    return 2 * 3 * one * itemsize
