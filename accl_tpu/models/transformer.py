"""A tensor+data-parallel transformer LM where every cross-device edge is an
accl_tpu collective.

Parallelism plan (Megatron-style TP over mesh axis ``tp``, DP over ``dp``):

* attention QKV projections column-parallel (head-sharded over tp),
  output projection row-parallel -> partial sums combined with
  ``ops.collectives.allreduce(..., 'tp')``;
* MLP up-projection column-parallel, down-projection row-parallel ->
  tp-allreduce;
* batch sharded over dp; gradients averaged with
  ``ops.collectives.allreduce(..., 'dp')``.

The whole train step runs inside one ``shard_map`` over the 2-D mesh, so
every collective is explicit and ours — the model is an application of the
collectives engine, the way the reference's host tests are applications of
the CCLO.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..constants import ReduceFunction
from ..ops import collectives
from ..utils.profiling import device_scope
from ..utils.remat import KEPT_UNDER_REMAT, kept_under_remat


@dataclasses.dataclass(frozen=True)
class HeadGeometry:
    """The geometry of an ``"attention"`` mixer's heads where it is not the
    plain one (``LayerKind.heads``): ``rope_dim``, the FIRST columns of a q
    or k head that rotate, the rest carrying no position (a published
    ``partial_rotary_factor``; ``None``: every column; the two parts go to
    the attention lowerings apart, the rotating one as the scores' second
    part, so a head of 128 + 64 is two operands of whole lanes to the flash
    kernels and not one of 192 padded to 256); ``v_dim``, the width of a v
    head, of the output a head and of ``wo``'s rows a head where it is not
    q's and k's (``None``: theirs); ``v_scale`` multiplies v (a published
    ``attention_value_scale``)."""

    rope_dim: Optional[int] = None
    v_dim: Optional[int] = None
    v_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One layer of a pattern (``TransformerConfig.layers``): its mixer
    and its FFN.  ``window``: how many keys a query sees, its own among
    them (``None`` = every earlier key).  ``rope``: whether this layer's
    q and k rotate (a ``pos_embedding="rope"`` model may leave some
    layers without position, NoPE).  ``ffn``: ``"dense"``, ``"moe"``
    (the expert bank of the config's ``n_experts``) or ``"none"``;
    ``d_ff``: the dense FFN's width, or one expert's.  ``mixer``:
    ``"attention"`` (wq, wk, wv), ``"latent"``
    (``TransformerConfig.latent``'s sizes), ``"kda"``
    (``TransformerConfig.kda``'s: a linear-attention layer) or
    ``"mamba2"`` (``TransformerConfig.mamba``'s: a state-space layer;
    neither has a position encoding or a window, so ``rope`` and
    ``window`` say nothing of them), or ``"none"``; ``None`` is the
    config's own, the latent mixer where it has one and attention
    elsewhere (``TransformerConfig.mixer``).  A block with ``"none"`` for
    one of the two has ONE sub-layer, ``h + f(norm h)`` with one norm and
    one residual (``ln1`` a mixer's, ``ln2`` an FFN's: the other is not
    in the tree); it cannot have ``"none"`` for both.

    What differs by kind among ``"attention"`` mixers of one stack:
    ``kv_heads``, this kind's K/V heads (``None``: the config's
    ``n_kv_heads``; the tree's ``wk`` / ``wv`` are that wide and the mixer
    reads the count off them); ``rope_base``, this kind's rotary base
    (``None``: the config's); ``sink``, one learned float32 scalar a QUERY
    head (the tree's ``sink``, ``(n_heads,)``) that stands in every row's
    softmax as a key without a value, after the scale; ``heads``, the
    heads' geometry (:class:`HeadGeometry`; ``None``: every column rotates,
    v as wide as q and k, no value scale)."""

    window: Optional[int] = None
    rope: bool = True
    ffn: str = "dense"
    d_ff: int = 0
    mixer: Optional[str] = None
    kv_heads: Optional[int] = None
    rope_base: Optional[float] = None
    sink: bool = False
    heads: Optional[HeadGeometry] = None


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The sizes of a latent mixer (multi-head latent attention, MLA;
    ``TransformerConfig.latent``): q comes up from a normed latent of
    ``q_rank`` (``None``: straight from the hidden state, one matrix
    ``wq`` and no q norm, a published ``q_lora_rank`` null), k's content
    part and v from a normed latent of
    ``kv_rank``; a head's q and k are ``nope_dim`` columns without
    position beside ``rope_dim`` that rotate (k's rotating part is ONE
    head, projected straight from the input and shared by every query
    head); v and the output are ``v_dim`` a head."""

    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's rescaling of the rotary frequencies (the published
    ``rope_scaling`` of type ``yarn``; ``TransformerConfig.rope_yarn``):
    :func:`yarn_inv_freq` has the formula."""

    factor: float
    original_max_seq: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class DeltaAttention:
    """The sizes of a KDA mixer (Kimi Delta Attention, arXiv:2510.26692;
    ``TransformerConfig.kda``; the layers whose ``LayerKind.mixer`` is
    ``"kda"``): ``n_heads`` heads whose q, k and v are ``head_dim`` wide,
    each ``silu(conv(h w))`` under a causal depthwise convolution of
    ``conv`` taps; q and k L2-normalised a head; a log-decay a CHANNEL
    ``lower_bound * sigmoid(exp(a_log) (h wf + dt_bias))`` and a write
    strength a head ``beta_scale * sigmoid(h wbeta)`` into the gated delta
    rule (``ops.kda``); the output RMS-normed a head and gated a channel by
    ``sigmoid(h wg)`` before ``wo``.  A ``lower_bound`` must stay within
    what ``ops.kda``'s sub-blocks keep inside float32 (``-80 / SUB``);
    ``None`` is the PUBLISHED gate without a bound, ``-exp(a_log)
    softplus(h wf + dt_bias)``, any value below 0, under which the core
    splits its decays by halving (``ops.kda``, ANY ``g <= 0``; chosen here,
    statically, so a bounded model's program is untouched).  ``beta_scale``
    2 lets the transition's eigenvalue along k be negative (the family's
    ``allow_neg_eigval``).  ``gate_rank``: the two gate projections ``wf``
    and ``wg`` through that rank, ``wf_a wf_b`` and ``wg_a wg_b`` in the
    tree (``None``: full rank, one matrix each).

    GATED DELTANET (arXiv:2412.06464) is the same rule with a decay a
    HEAD, three more properties of this description: ``v_dim`` is the width
    of a head's v, output gate and ``wo`` rows where it is not ``head_dim``
    (the state is ``head_dim x v_dim``; q and k stay ``head_dim``);
    ``head_decay`` makes the log-decay ONE value a head a token, ``-exp(a_log)
    softplus(h wa + dt_bias)`` with ``wa`` ``(d_model, n_heads)`` and
    ``dt_bias`` a head in ``wf``'s place (no bound to set: ``lower_bound``
    None, ``gate_rank`` None; ``ops.kda`` runs a gate of one column through
    the same core, its heads padded to whole lanes where that costs at most
    half again); ``out_gate`` ``"silu"`` gates the normed output by ``SiLU(h
    wg)`` in the sigmoid's place."""

    head_dim: int
    conv: int = 4
    lower_bound: Optional[float] = -5.0
    beta_scale: float = 1.0
    gate_rank: Optional[int] = None
    v_dim: Optional[int] = None
    head_decay: bool = False
    out_gate: str = "sigmoid"

    def value_dim(self) -> int:
        return self.head_dim if self.v_dim is None else self.v_dim


#: the softplus values ``init_params`` draws a head's ``dt_bias`` for under
#: ``DeltaAttention.head_decay``, log-uniform (the family's initial range)
KDA_HEAD_DECAY_DT = (1e-3, 0.1)

#: the softplus values ``init_params`` draws the unbounded KDA gate's
#: ``dt_bias`` for, log-uniform a channel (its docstring there says why)
KDA_UNBOUNDED_DT = (1e-3, 16.0)

@dataclasses.dataclass(frozen=True)
class Mamba2:
    """The sizes of a Mamba-2 mixer (state-space duality, arXiv:2405.21060;
    ``TransformerConfig.mamba``; the layers whose ``LayerKind.mixer`` is
    ``"mamba2"``): ``n_heads`` heads (its own count, not the attention's)
    of ``head_dim`` columns with a state of ``head_dim x state`` each, in
    ``groups`` groups of consecutive heads that share the state's input and
    output directions B and C.  ``[z | x | B | C | dt] = u W_in``; x, B and
    C through ``silu(conv(.) + bias)``, a causal depthwise convolution of
    ``conv`` taps; ``dt = softplus(dt + dt_bias)`` and a rate ``A =
    -exp(a_log)``, scalars a head, into ``S_t = exp(dt_t A) S_{t-1} + dt_t
    x_t B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops.ssd``, in chunks of
    ``chunk``); ``y silu(z)`` RMS-normed a group (the gate BEFORE the norm)
    before ``wo``.  ``dt_min``, ``dt_max`` and ``dt_floor`` are
    INITIALISATION (``dt_bias`` is the inverse softplus of a log-uniform
    draw between the first two, floored): the step has no clamp."""

    n_heads: int
    head_dim: int
    state: int
    groups: int
    conv: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Block-diffusion training (BD3-LM's objective, as the SDAR family
    adopts it; ``TransformerConfig.diffusion``).  A sequence of ``L`` ids
    is ``L / block`` blocks; each block of each sequence draws a level
    ``t = eps + (1 - eps) u``, ``u ~ U[0, 1)``, and each of its positions
    becomes ``mask_id`` with probability ``t`` (:func:`diffusion_noise`).
    The model runs on ``[noisy ; clean]``, ``2 L`` rows at positions
    ``0..L`` twice, under the block layout of
    ``ops.attention.block_diffusion_visible``; logits are taken on the
    noisy half only and predict the id AT the position (no shift):
    ``loss = sum over masked positions of (1 / t) nll / (sequences * L)``."""

    block: int
    mask_id: int
    eps: float = 1e-3


def hybrid_layers(pattern: str, d_ff: int = 0, moe_d_ff: int = 0):
    """One :class:`LayerKind` a letter of a published
    ``hybrid_override_pattern`` (the Nemotron-H family's): every block has
    ONE sub-layer, ``M`` a Mamba-2 mixer, ``*`` attention without
    position, ``E`` the expert bank (experts ``moe_d_ff`` wide), ``-`` a
    dense FFN ``d_ff`` wide."""
    kinds = {
        "M": LayerKind(mixer="mamba2", rope=False, ffn="none"),
        "*": LayerKind(mixer="attention", rope=False, ffn="none"),
        "E": LayerKind(mixer="none", rope=False, ffn="moe", d_ff=moe_d_ff),
        "-": LayerKind(mixer="none", rope=False, ffn="dense", d_ff=d_ff),
    }
    if unknown := set(pattern) - set(kinds):
        raise ValueError(
            f"hybrid pattern {pattern!r}: unknown block {sorted(unknown)}; "
            f"a block is one of {sorted(kinds)}"
        )
    return tuple(kinds[letter] for letter in pattern)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, yarn: YarnScaling):
    """The ``dim // 2`` inverse frequencies of a rotary embedding over
    ``dim`` columns under YaRN, float32 (numpy): pair ``i`` keeps
    ``base ** (-2 i / dim)`` where it turns more than ``beta_fast`` times
    in the original context, is divided by ``factor`` where fewer than
    ``beta_slow``, and is blended linearly between the two corrections'
    pair indices ``low`` and ``high``."""
    import numpy as np

    half = dim // 2
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor

    def corr(turns):
        return (
            dim * math.log(yarn.original_max_seq / (turns * 2 * math.pi))
            / (2 * math.log(base))
        )

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    span = (high - low) or 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / span, 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_seq: int = 128
    dtype: jnp.dtype = jnp.float32
    # grouped-query attention: number of K/V heads (None = n_heads, the
    # classic MHA form; 1 = MQA).  Query heads share kv head h // G with
    # G = n_heads // n_kv_heads.  Shrinks the decode KV cache (the
    # serving memory ceiling) and the wk/wv params by the same factor;
    # under tp, n_kv_heads must stay divisible by the tp size so every
    # chip owns whole kv heads.
    n_kv_heads: Optional[int] = None
    # position encoding: "learned" (an additive max_seq x d_model table)
    # or "rope" (rotary embeddings applied to q/k per head — no pos
    # table, relative-position attention, and no max_seq cliff baked
    # into the params; requires an even head dim)
    pos_embedding: str = "learned"
    rope_base: float = 10000.0
    # YaRN (:class:`YarnScaling`): the rotary tables take its inverse
    # frequencies (:func:`yarn_inv_freq`) in place of ``rope_base``'s own,
    # times ``m(mscale) / m(mscale_all_dim)``; a latent mixer's softmax
    # scale is times ``m(mscale_all_dim) ** 2``.  ``None``: the plain
    # frequencies, computed in the program as they always were.
    rope_yarn: Optional[YarnScaling] = None
    # Megatron vocab parallelism: the embedding table shards its VOCAB
    # rows over tp.  Lookup becomes mask + tp-allreduce; the LM loss
    # computes a fused vocab-parallel cross-entropy on the SHARDED
    # logits (pmax/psum over tp) so the (B, T, vocab) logits matrix —
    # the last replicated memory hog — never materializes in the train
    # step.  Requires vocab divisible by tp.
    vocab_parallel: bool = False
    # ring/context parallelism (long-context training): the tp mesh axis
    # becomes a SEQUENCE ring — weights are fully replicated over it,
    # activations stay sequence-sharded (T/cp per chip) through the
    # whole stack in the STRIPED (round-robin) layout, and attention is
    # striped causal ring attention (K/V blocks rotate by neighbor
    # ppermute — ICI hops — folding into each rank's online-softmax
    # state; under GQA the UNEXPANDED kv heads rotate, G x less wire).
    # The loss is computed on the local shard and psum-averaged, so no
    # rank ever materializes full-sequence activations: per-chip memory
    # for T scales as T/cp — the long-context axis.  Training-only
    # (decode serves with context_parallel=False: the params are
    # replicated, so they re-shard directly); incompatible with
    # seq_parallel and vocab_parallel, which give the tp axis other jobs.
    context_parallel: bool = False
    # rematerialize each block on the backward pass (jax.checkpoint):
    # trades ~30% more FLOPs in exchange for activation memory that no
    # longer scales with n_layers — the standard TPU recipe for fitting
    # larger models/batches (HBM is the bottleneck, MXU has headroom).
    # A block's forward is replayed but for the values its mixer NAMES
    # (``utils.remat.KEPT_UNDER_REMAT``, a static property of the mixer's
    # and the core's code, no option): the KDA mixer's five bf16 input
    # projections (q, k, v, decay gate, output gate: 5 x B T d_inner x 2
    # bytes a layer, 671 MB at 8,192 x 8,192) and the Mamba-2 mixer's five
    # (z, x, B, C, dt: 304 MB a block at 8,192 x 18,560), which their
    # chains' kernels save as their only residual anyway and whose replay
    # is a matmul at the MXU's peak; a softmax mixer's q, k and v as its
    # core takes them (head-major, after the norms, the rope and a head's
    # split, ``q_rope`` / ``k_rope`` with them), and the flash core's ``o``
    # and ``lse`` (the forward rule of ``ops.pallas.attention`` names them,
    # for the latent mixer too): what ``flash_bwd`` reads, 0.38 GB a layer
    # at 64 heads of 192 x 8,192 rows, so that the backward runs neither the
    # products, the relayouts nor ``flash_fwd`` a second time.  ``wo``'s
    # product, a gate's, the latent mixer's expanded q, k, v, the KDA and
    # SSD cores' saved sets and the FFNs name nothing (``_layer_blocks``)
    remat: bool = False
    # Megatron-style sequence parallelism: between blocks, activations
    # live SEQUENCE-sharded over tp (T/tp per chip), the row-parallel
    # allreduce becomes a reduce-scatter, and an all-gather precedes each
    # column-parallel matmul — same wire bytes as the two allreduces
    # (AR = RS + AG), but layernorm/residual compute and inter-block
    # activation memory drop by the tp factor
    seq_parallel: bool = False
    # the block's kinds, alike in every layer: ``norm`` is "layernorm"
    # (mean-centred) or "rmsnorm", both a scale and no bias at eps 1e-5;
    # ``ffn`` is "gelu" (two matrices), "swiglu" (gated SiLU, three
    # matrices: ``(silu(x w1) * (x w3)) w2``) or "relu2" (two matrices,
    # ``relu(x w1) ** 2 w2``), the dense FFN, each expert and a shared
    # expert alike; ``qk_norm`` puts an RMSNorm on the projected q and k:
    # ``True`` over the WHOLE projection before the split into heads,
    # ``"head"`` over each head's ``head_dim`` after it (one learned
    # scale of ``head_dim`` for q, one for k); ``tie_head=False`` gives
    # the LM head its own (d_model, vocab) matrix instead of the
    # embedding's transpose; ``attn_gate`` multiplies the attention
    # output by ``sigmoid(h wg)`` before ``wo`` (``True``: a value a
    # channel, the attention mixer's; ``"head"``: a value a head, ``wg``
    # ``(d_model, n_heads)``, the latent mixer's); ``post_norm`` norms each
    # half's output once more before the residual add (``ln1_post``,
    # ``ln2_post``: four norms a layer; ``"only"``: that norm ALONE, none
    # before the sub-layer, ``x + norm(f(x))`` with ``f`` reading the
    # residual stream itself, Olmo 2's reordered block: the tree then holds
    # ``ln1_post`` / ``ln2_post`` and no ``ln1`` / ``ln2``); ``embed_scale``
    # multiplies the embedding rows (a muP model's ``sqrt(d_model)``);
    # ``head_dim`` is
    # the heads' width where it is not ``d_model // n_heads`` (q and o
    # are then ``n_heads * head_dim`` wide, :meth:`head_size`).  The
    # train and forward paths honour all of them; prefill/generate, the
    # context- and sequence-parallel blocks, the encoder and the
    # composed pipeline take what :meth:`plain` allows.  ``norm_eps`` is
    # the epsilon of the block's norms, the final norm, QK-norm and a
    # latent mixer's (the published ``rms_norm_eps`` /
    # ``layer_norm_epsilon``).
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    ffn: str = "gelu"
    qk_norm: Union[bool, str] = False
    tie_head: bool = True
    attn_gate: Union[bool, str] = False
    post_norm: Union[bool, str] = False
    embed_scale: float = 1.0
    head_dim: Optional[int] = None
    # the latent mixer (:class:`LatentAttention`, DeepSeek's MLA) in place
    # of wq/wk/wv: ``q = RMSNorm(h wq_a) wq_b`` a head ``[nope | rope]``,
    # ``[latent | k_rope] = h wkv_a`` with ONE rope key head, ``[k_nope |
    # v] = RMSNorm(latent) wkv_b`` a head; rope (``rope_yarn``'s
    # frequencies) on the rope columns only; scores over ``nope + rope``
    # columns at ``(nope + rope) ** -0.5 * m(mscale_all_dim) ** 2``, v and
    # the output ``v_dim`` wide, ``wo`` from ``n_heads * v_dim``.  The
    # flash kernels take the two widths and the shared rope key as they
    # are.  Honoured by the train and forward paths (``make_sharded_train_
    # step``, ``make_sharded_forward``, the router probe; tp splits the
    # heads: ``wq_b``, ``wkv_b`` column-parallel, ``wo`` row-parallel, the
    # ``_a`` matrices and their norms replicated).  REFUSED by name by
    # prefill/generate (no latent cache yet), the context- and
    # sequence-parallel blocks, the encoder and the pipelines.  Needs
    # ``pos_embedding="rope"``; ``n_kv_heads``, ``head_dim`` and ``qk_norm``
    # do not apply to it, and of ``attn_gate`` only ``"head"``.  It is the
    # mixer of every layer whose ``LayerKind.mixer`` does not say otherwise.
    latent: Optional[LatentAttention] = None
    # the KDA mixer's sizes (:class:`DeltaAttention`), for the layers of
    # the pattern whose ``LayerKind.mixer`` is ``"kda"``: a chunked gated
    # delta rule with a decay a channel, or a head under ``head_decay``
    # (Gated DeltaNet; ``ops.kda``), forward and backward, on the train and
    # forward paths (tp splits the heads: every matrix but ``wo``
    # column-parallel, ``wa`` and ``wbeta`` with the heads themselves, the
    # convolutions' taps, ``a_log`` and ``dt_bias`` with their channels).
    # REFUSED by name where the latent mixer is, and for the same reason:
    # prefill/generate would need the recurrent state as a cache, the ring
    # would hand a state from rank to rank.
    kda: Optional[DeltaAttention] = None
    # the Mamba-2 mixer's sizes (:class:`Mamba2`), for the layers of the
    # pattern whose ``LayerKind.mixer`` is ``"mamba2"``: the chunked
    # selective state-space recurrence with a scalar decay a head
    # (``ops.ssd``), forward and backward, on the train and forward paths
    # (tp splits its heads AND its B/C groups together: every matrix but
    # ``wo`` column-parallel, the taps, biases and scalars with their
    # channels and heads).  REFUSED by name where the KDA mixer is, for
    # the same reasons.
    mamba: Optional[Mamba2] = None
    # the objective where it is not next-token prediction
    # (:class:`BlockDiffusion`): ``loss_fn`` and the train step noise the
    # ids from a key (the step's third argument, in ``targets``' place)
    # and run ``[noisy ; clean]``; ``forward`` and the router probe take
    # the ``2 L`` ids as they are and return the noisy half's logits.
    # Needs ``pos_embedding="rope"``; no window, no latent mixer.  REFUSED
    # by name by prefill/generate (generation denoises a block at a time:
    # ROADMAP), the context- and sequence-parallel blocks, the encoder and
    # the pipelines.
    diffusion: Optional[BlockDiffusion] = None
    # the layer pattern (ROADMAP M1): one :class:`LayerKind` a layer —
    # the mixer's window and whether it rotates, the FFN's kind and
    # width.  ``None`` is the pattern the other fields describe, every
    # layer alike: no window, rope as ``pos_embedding`` says, the expert
    # bank where ``n_experts`` and the dense FFN elsewhere, ``d_ff``
    # wide (:meth:`pattern`).
    layers: Optional[Tuple[LayerKind, ...]] = None
    # Mixture-of-Experts: n_experts > 0 replaces every block's dense FFN
    # with a top-k routed expert FFN (models/moe.py, any k; k=1 is Switch
    # routing).  ``moe_capacity_factor`` a number: fixed capacity, static
    # shapes, entries past capacity dropped; expert parallelism rides the
    # DP mesh axis, each dp rank owns n_experts/dp experts and tokens
    # travel to their expert's chip through the all-to-all (dispatch +
    # return), the fourth parallelism axis composed into the flagship.
    # ``moe_capacity_factor=None`` MEANS dropless: entries sorted by
    # expert through a grouped matmul, float32 router softmax, nothing
    # dropped; every expert on the chip (an expert axis larger than one
    # raises: ROADMAP R2(b)).  ``moe_norm_topk_prob`` is the published
    # key: renormalise the k chosen probabilities (k > 1) or keep them
    # raw.  loss_fn adds the router health terms (Switch load-balance
    # aux + ST-MoE z-loss) averaged over layers.  Requires n_experts
    # divisible by dp; decoder train/forward/decode paths (not
    # encoder/pipeline, and not combined with seq_parallel yet).
    n_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: Optional[float] = 1.5
    moe_norm_topk_prob: bool = True
    moe_aux_weight: float = 0.01
    moe_router_z_weight: float = 1e-3
    # Beyond softmax top-k (dropless only; models/moe.py says how each is
    # computed).  ``moe_router="sigmoid"``: float32 sigmoid scores,
    # selection on ``score + bias``, weights from the unbiased scores,
    # renormalised (``moe_norm_topk_prob``) and times ``moe_route_scale``;
    # the bias is state no gradient touches, moved after each train step
    # by ``moe_bias_rate * sign(mean load - load)`` (0 = no bias).
    # ``moe_shared_d_ff``: one expert of that width (``ffn``'s kind, as
    # the routed ones) beside them, every token through it (0 = none).
    # ``moe_router_experts``: the router's width where this chip HOLDS
    # only ``n_experts`` of them, ``moe_first_expert`` on (one chip's
    # share of an expert-parallel group, without its exchange): routing
    # is over all of them, the held experts' part of the result is
    # computed, the rest is left out.  The held entries' rows have a
    # static buffer, ``moe_held_row_factor`` times the balanced share
    # ``tokens * k * n_experts / moe_router_experts``; an entry past it is
    # dropped and counted.
    # Group-limited routing (dropless): the router's
    # experts in ``moe_n_group`` groups of consecutive experts, a group's
    # score the largest of its experts' under the softmax router
    # (``group_limited_greedy``) and the SUM of its two largest ``score +
    # bias`` under the sigmoid one (``noaux_tc``), the ``moe_topk_group`` best
    # groups kept and the top-k taken over what stays (1, 1 = no limit).
    # ``moe_route_scale`` multiplies the chosen weights on either router.
    # ``moe_latent``: the routed experts work on ANOTHER WIDTH than the
    # router's (a LatentMoE): the router and a shared expert read the
    # hidden state, the routed experts ``x w_down`` (``moe_latent`` wide,
    # and so are the rows that are sorted, gathered, multiplied and placed),
    # their weighted sum going through ``w_up`` once a token, after the
    # combine (0 = the token's own width).
    # ``moe_balance_weights``: DeepSeek-V2's expert-, device- and
    # communication-level balance losses (models/moe.py), each a
    # sequence's, averaged over the batch, summed over the expert layers
    # and added to the loss with these weights (all zero = not computed).
    moe_router: str = "softmax"
    moe_route_scale: float = 1.0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_balance_weights: Tuple[float, float, float] = (0.0, 0.0, 0.0)
    moe_bias_rate: float = 0.0
    moe_shared_d_ff: int = 0
    moe_router_experts: Optional[int] = None
    moe_first_expert: int = 0
    moe_held_row_factor: float = 2.0
    moe_latent: int = 0
    # which mesh axis the expert bank shards over.  "dp" (default) is the
    # DeepSpeed-MoE welded layout: expert parallelism rides the data
    # axis.  Naming a DEDICATED axis (conventionally "ep", on a
    # ('dp', 'ep', 'tp') mesh) un-welds them: experts shard over ep while
    # the batch shards over (dp x ep) — ep acts as a sub-axis of data
    # parallelism for the dense params, so ep can be sized to the expert
    # count independently of how much plain data parallelism dp carries.
    # The dispatch/return all-to-alls ride this axis either way.
    moe_mesh_axis: str = "dp"
    # Opt-in: let a DENSE (or welded-MoE) config treat a mesh axis named
    # "ep" as extra data parallelism, so one ('dp', 'ep', 'tp') mesh can
    # serve an unwelded MoE and a dense model side by side.  Off by
    # default: a caller-built mesh that happens to reuse the name "ep"
    # for another purpose must not silently get its batch sharded (and
    # its dense grads psummed) over that axis.  Unwelded MoE configs
    # (moe_mesh_axis="ep") don't need this — their batch shards over
    # (dp x ep) by construction.
    ep_extends_dp: bool = False
    # attention lowering: "auto" (default) picks per sequence length and
    # backend — measured on v5e with the block=512 flash kernel: flash
    # wins the full train step at T=1024 (75.4% vs naive's 69.5% MFU)
    # and at T=4096 (69.6%; naive OOMs on score residuals there), so
    # auto picks the Pallas "flash" kernel on TPU from T=1024 up while
    # its K/V fit VMEM, the XLA "blockwise" fold on other backends, and
    # the materialized-scores "naive" form only below the crossover
    # (tiny-T regimes where kernel padding overhead dominates).
    # "blockwise" forces the XLA online-softmax tile fold (no (T, T)
    # matrix in HBM, ops/attention.py); "flash" forces the Pallas
    # kernel — trainable via its custom_vjp backward kernel
    # (ops/pallas/attention.py); "naive" forces materialized scores
    # through jax.nn.softmax.
    attention: str = "auto"

    def kv_heads(self, kind: Optional[LayerKind] = None) -> int:
        """The K/V heads of an attention layer of ``kind`` (its own count
        where it has one), or the config's."""
        n_kv = self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        if kind is not None and kind.kv_heads is not None:
            n_kv = kind.kv_heads
        if n_kv <= 0 or self.n_heads % n_kv:
            raise ValueError(
                f"n_kv_heads ({n_kv}) must divide n_heads ({self.n_heads})"
            )
        return n_kv

    def uses_rope(self) -> bool:
        if self.pos_embedding not in ("learned", "rope"):
            raise ValueError(
                f"unknown pos_embedding {self.pos_embedding!r}"
            )
        if self.pos_embedding == "rope" and self.rope_width() % 2:
            raise ValueError("rope needs an even head dim")
        return self.pos_embedding == "rope"

    def rope_width(self) -> int:
        """The columns of a head that rotate."""
        return self.latent.rope_dim if self.latent else self.head_size()

    def rope_inv_freq(self):
        """The rotary tables' inverse frequencies where the config gives
        them (YaRN), else ``None``: ``rope_base``'s own, computed in the
        program."""
        if self.rope_yarn is None:
            return None
        return yarn_inv_freq(self.rope_width(), self.rope_base, self.rope_yarn)

    def rope_table_scale(self) -> float:
        """What YaRN multiplies cos and sin by."""
        y = self.rope_yarn
        if y is None:
            return 1.0
        return yarn_mscale(y.factor, y.mscale) / yarn_mscale(
            y.factor, y.mscale_all_dim
        )

    def attn_scale(self) -> Optional[float]:
        """The softmax scale where it is not ``head_size() ** -0.5``."""
        if self.latent is None:
            return None
        y = self.rope_yarn
        m = 1.0 if y is None else yarn_mscale(y.factor, y.mscale_all_dim)
        return self.head_size() ** -0.5 * m * m

    def head_size(self) -> int:
        """One head's width, the one its scores are taken over:
        ``head_dim``, or ``d_model // n_heads``; ``nope_dim + rope_dim``
        of a latent mixer."""
        if self.latent is not None:
            return self.latent.nope_dim + self.latent.rope_dim
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    def router_experts(self) -> int:
        """The router's width: all of a layer's experts, held here or not."""
        if self.moe_router_experts is None:
            return self.n_experts
        return self.moe_router_experts

    def pattern(self) -> Tuple[LayerKind, ...]:
        """One :class:`LayerKind` a layer: ``layers``, or the pattern the
        other fields describe."""
        if self.layers is not None:
            return self.layers
        kind = LayerKind(
            window=None, rope=self.pos_embedding == "rope",
            ffn="moe" if self.n_experts else "dense", d_ff=self.d_ff,
        )
        return (kind,) * self.n_layers

    def mixer(self, kind: LayerKind) -> str:
        """The mixer of a layer of ``kind``: its own, or the config's."""
        if kind.mixer is not None:
            return kind.mixer
        return "latent" if self.latent is not None else "attention"

    def plain(self) -> bool:
        """Every layer alike and nothing of the later kinds (a pattern, a
        head width of its own, the gate, per-head QK-norm, post-norms, a
        scaled embedding, the sigmoid router, a shared expert, a held
        share, a latent, a KDA or a Mamba-2 mixer, grouped top-k, the
        balance losses, a latent expert bank, relu2, an objective of its
        own): what prefill/generate and the context- and sequence-parallel
        blocks compute."""
        return (
            self.layers is None and self.head_dim is None
            and self.latent is None and self.diffusion is None
            and self.kda is None and self.mamba is None
            and not self.moe_latent and self.ffn != "relu2"
            and self.moe_n_group == 1
            and not any(self.moe_balance_weights)
            and not self.attn_gate and not self.post_norm
            and self.qk_norm in (False, True) and self.embed_scale == 1.0
            and self.moe_router == "softmax" and not self.moe_shared_d_ff
            and self.moe_router_experts is None
        )

    def __post_init__(self):
        if self.norm not in _NORMS:
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.ffn not in ("gelu", "swiglu", "relu2"):
            raise ValueError(f"unknown ffn {self.ffn!r}")
        if self.qk_norm not in (False, True, "head"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}")
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if self.attn_gate not in (False, True, "head"):
            raise ValueError(f"unknown attn_gate {self.attn_gate!r}")
        if self.post_norm not in (False, True, "only"):
            raise ValueError(f"unknown post_norm {self.post_norm!r}")
        if self.latent is not None and (
            self.pos_embedding != "rope" or self.n_kv_heads is not None
            or self.head_dim is not None or self.qk_norm
            or self.attn_gate is True
        ):
            raise ValueError(
                "a latent mixer rotates (pos_embedding='rope') and has no "
                "n_kv_heads, head_dim, qk_norm or attn_gate of its own "
                "(its gate is a value a head: attn_gate='head')"
            )
        if self.attn_gate == "head" and self.latent is None:
            raise ValueError(
                "attn_gate='head' is the latent mixer's gate; the attention "
                "mixer's is a value a channel (attn_gate=True)"
            )
        kinds = {self.mixer(kind) for kind in self.layers or ()}
        if self.kda is not None:
            from ..ops.kda import SUB

            d = self.kda
            if (
                "kda" not in kinds or d.head_dim < 1 or d.conv < 1
                or not (
                    d.lower_bound is None
                    or -80.0 / SUB <= d.lower_bound < 0.0
                )
                or d.beta_scale not in (1.0, 2.0)
                or (d.gate_rank is not None and d.gate_rank < 1)
                or d.value_dim() < 1
                or d.out_gate not in ("sigmoid", "silu")
                or (d.head_decay and (
                    d.lower_bound is not None or d.gate_rank is not None
                ))
            ):
                raise ValueError(
                    "a KDA mixer (TransformerConfig.kda) is some layer's of "
                    "the pattern (LayerKind.mixer='kda'), with a head_dim "
                    f"and a convolution of at least 1, a lower_bound in "
                    f"[{-80.0 / SUB}, 0) or None (the gate without a bound), "
                    "a beta_scale of 1 or 2, a gate_rank of at least 1 or "
                    "None, a v_dim of at least 1 or None, an out_gate "
                    "'sigmoid' or 'silu' and, under head_decay, neither a "
                    f"lower_bound nor a gate_rank; got {d}"
                )
        if self.mamba is not None:
            m = self.mamba
            if (
                "mamba2" not in kinds
                or min(m.n_heads, m.head_dim, m.state, m.groups, m.conv,
                       m.chunk) < 1
                or m.n_heads % m.groups
                or not 0.0 < m.dt_floor <= m.dt_min <= m.dt_max
            ):
                raise ValueError(
                    "a Mamba-2 mixer (TransformerConfig.mamba) is some "
                    "layer's of the pattern (LayerKind.mixer='mamba2'), with "
                    "heads in whole groups, sizes of at least 1 and 0 < "
                    f"dt_floor <= dt_min <= dt_max; got {m}"
                )
        if self.rope_yarn is not None and self.pos_embedding != "rope":
            raise ValueError("rope_yarn rescales a rotary embedding")
        if self.diffusion is not None:
            d = self.diffusion
            if (
                self.pos_embedding != "rope" or self.latent is not None
                or self.kda is not None or self.mamba is not None
                or any(k.window is not None for k in self.layers or ())
                or any(
                    "none" in (k.mixer, k.ffn) for k in self.layers or ()
                )
                or d.block < 1 or not 0 <= d.mask_id < self.vocab
                or not 0.0 < d.eps < 1.0
            ):
                raise ValueError(
                    "block diffusion (TransformerConfig.diffusion) rotates "
                    "(pos_embedding='rope'), has no window and no latent, "
                    "KDA mixer, Mamba-2 mixer or block of one sub-layer, "
                    "blocks of at least 1, a mask_id inside the "
                    f"vocabulary and 0 < eps < 1; got {d}"
                )
        if self.moe_n_group != 1 or self.moe_topk_group != 1:
            of = self.router_experts()
            if (
                self.moe_n_group < 1 or of % self.moe_n_group
                or not 1 <= self.moe_topk_group <= self.moe_n_group
                or self.moe_topk_group * (of // self.moe_n_group)
                < self.moe_top_k
                # the sigmoid router's group score is of two experts
                or (self.moe_router == "sigmoid"
                    and of // self.moe_n_group < 2)
            ):
                raise ValueError(
                    f"grouped top-k: {self.moe_n_group} groups must divide "
                    f"the router's {of} experts (into groups of at least two "
                    f"under the sigmoid router) and the "
                    f"{self.moe_topk_group} kept must hold {self.moe_top_k}"
                )
        if self.layers is not None:
            if len(self.layers) != self.n_layers:
                raise ValueError(
                    f"layers has {len(self.layers)} kinds for n_layers "
                    f"{self.n_layers}"
                )
            for i, kind in enumerate(self.layers):
                if kind.ffn not in ("dense", "moe", "none"):
                    raise ValueError(f"layer {i}: unknown ffn {kind.ffn!r}")
                if kind.ffn == "moe" and not self.n_experts:
                    raise ValueError(f"layer {i}: an moe layer needs n_experts")
                if kind.window is not None and kind.window < 1:
                    raise ValueError(f"layer {i}: window {kind.window}")
                mixer = self.mixer(kind)
                if mixer not in ("attention", "latent", "kda", "mamba2", "none"):
                    raise ValueError(f"layer {i}: unknown mixer {mixer!r}")
                if mixer == "none":
                    if kind.ffn == "none":
                        raise ValueError(
                            f"layer {i}: a block has a mixer, an FFN or both"
                        )
                    continue
                if mixer == "kda":
                    if self.kda is None or kind.window is not None:
                        raise ValueError(
                            f"layer {i}: a KDA layer needs "
                            "TransformerConfig.kda and has no window"
                        )
                    continue  # no position encoding: ``rope`` says nothing
                if mixer == "mamba2":
                    if self.mamba is None or kind.window is not None:
                        raise ValueError(
                            f"layer {i}: a Mamba-2 layer needs "
                            "TransformerConfig.mamba and has no window"
                        )
                    continue
                if (mixer == "latent") != (self.latent is not None):
                    raise ValueError(
                        f"layer {i}: the {mixer} mixer in a stack whose "
                        "TransformerConfig.latent is "
                        f"{'set' if self.latent is not None else 'None'} (a "
                        "stack holds the latent mixer or attention, beside "
                        "KDA and Mamba-2 layers)"
                    )
                if kind.rope and self.pos_embedding != "rope":
                    raise ValueError(
                        f"layer {i} rotates but pos_embedding is "
                        f"{self.pos_embedding!r}"
                    )
                self._check_attention_kind(i, kind, mixer)
        beyond = (
            self.moe_router != "softmax" or self.moe_shared_d_ff
            or self.moe_router_experts is not None or self.moe_n_group != 1
            or any(self.moe_balance_weights) or self.moe_latent
        )
        if beyond and (not self.n_experts or self.moe_capacity_factor is not None):
            raise ValueError(
                "the sigmoid router, a shared expert, a held share, grouped "
                "top-k, a latent expert bank and the balance losses are "
                "the dropless path's (n_experts > 0, "
                "moe_capacity_factor=None)"
            )
        if self.moe_router_experts is not None and not (
            0 <= self.moe_first_expert
            <= self.moe_router_experts - self.n_experts
        ):
            raise ValueError(
                f"experts {self.moe_first_expert}.."
                f"{self.moe_first_expert + self.n_experts} are not among "
                f"the router's {self.moe_router_experts}"
            )

    def _check_attention_kind(self, i: int, kind: LayerKind, mixer: str):
        """What a kind says of its OWN attention heads (``kv_heads``,
        ``rope_base``, ``sink``, ``heads``) is the ``"attention"`` mixer's."""
        g = kind.heads
        own = (kind.kv_heads, kind.rope_base, g)
        if not kind.sink and all(x is None for x in own):
            return
        if mixer != "attention" or self.diffusion is not None:
            raise ValueError(
                f"layer {i}: kv_heads, rope_base, sink and heads are the "
                f"attention mixer's under a causal mask, not the {mixer} "
                "mixer's or block diffusion's"
            )
        self.kv_heads(kind)
        if g is None:
            return
        hd = self.head_size()
        if (
            self.qk_norm or self.attn_gate or g.v_scale <= 0.0
            or (g.v_dim is not None and g.v_dim < 1)
            or (g.rope_dim is not None
                and not (0 < g.rope_dim <= hd and g.rope_dim % 2 == 0))
        ):
            raise ValueError(
                f"layer {i}: a head geometry (LayerKind.heads) rotates an "
                f"even number of a head's {hd} columns, has a v_dim of at "
                "least 1 and a v_scale above 0, and is not built beside "
                f"QK-norm or the attention gate; got {g}"
            )

    def default_block(self) -> bool:
        """The block the encoder and the composed pipeline compute."""
        return (
            self.norm == "layernorm" and self.ffn == "gelu"
            and not self.qk_norm and self.tie_head and self.plain()
        )


def _check_axis_compat(cfg) -> None:
    """context_parallel turns the tp axis into the sequence ring —
    it cannot share that axis with the strategies that give tp other
    jobs (head-sharded weights + sequence/vocab sharding)."""
    if (cfg.context_parallel or cfg.seq_parallel) and not cfg.plain():
        raise ValueError(
            "context_parallel and seq_parallel take the plain block only "
            "(TransformerConfig.plain): no layer pattern, window, head_dim, "
            "gate, per-head QK-norm, post-norm, scaled embedding, sigmoid "
            "router, shared expert, held share, K/V heads, rope base, sink or "
            "head geometry of a layer kind's own, latent mixer (MLA), KDA "
            "mixer or Mamba-2 mixer (a recurrent state is not handed round "
            "the ring yet), block of one sub-layer, latent expert bank, "
            "relu2, "
            "grouped top-k, balance losses or block diffusion (its layout is "
            "not in the ring yet)"
        )
    if cfg.diffusion is not None and cfg.vocab_parallel:
        raise ValueError(
            "block diffusion (TransformerConfig.diffusion) has no "
            "vocab_parallel loss: its weighted loss is over full logits of "
            "the noisy half"
        )
    if cfg.context_parallel and (cfg.seq_parallel or cfg.vocab_parallel):
        raise ValueError(
            "context_parallel is incompatible with seq_parallel and "
            "vocab_parallel: the tp mesh axis becomes the sequence ring "
            "(weights replicated over it)"
        )
    if cfg.n_experts and cfg.seq_parallel:
        raise ValueError(
            "n_experts (MoE) does not compose with seq_parallel — the "
            "MLP entry would need a sequence gather in front of every "
            "routed dispatch; use context_parallel for sequence sharding "
            "with MoE (experts on the expert axis, ring on tp)"
        )
    if cfg.n_experts and cfg.moe_mesh_axis == "tp":
        raise ValueError(
            "moe_mesh_axis cannot be 'tp': tp carries the within-expert "
            "column/row split (and the cp ring) — put experts on 'dp' or "
            "a dedicated 'ep' mesh axis"
        )


def _check_moe_mesh(cfg, mesh) -> None:
    """Friendly divisibility errors for the MoE sharding (the generic
    device_put failure names neither n_experts nor the axis)."""
    if not cfg.n_experts:
        return
    ep_ax = cfg.moe_mesh_axis
    if ep_ax not in mesh.axis_names:
        raise ValueError(
            f"moe_mesh_axis {ep_ax!r} is not an axis of this mesh "
            f"({mesh.axis_names}) — expert parallelism needs its axis "
            "in the mesh"
        )
    ep = mesh.shape[ep_ax]
    tp = mesh.shape["tp"]
    if cfg.moe_capacity_factor is None and ep > 1:
        raise NotImplementedError(
            f"dropless MoE (moe_capacity_factor=None) with {ep_ax} = {ep}:"
            " the expert exchange would need a different count a peer, "
            "which the fixed-count all-to-all does not carry (ROADMAP "
            "R2(b)); keep every expert on the chip or give a capacity"
        )
    if cfg.n_experts % ep:
        raise ValueError(
            f"n_experts ({cfg.n_experts}) must divide by {ep_ax} ({ep}) "
            "— expert parallelism shards the expert bank over "
            f"the {ep_ax!r} axis"
        )
    if not cfg.context_parallel and cfg.d_ff % tp:
        # under cp the tp axis is the sequence ring (experts replicated
        # over it), so there is no within-expert tp split to divide for
        raise ValueError(
            f"d_ff ({cfg.d_ff}) must divide by tp ({tp}) — each "
            "expert's FFN is column/row-split over tp"
        )


def _data_axes(cfg, mesh) -> tuple:
    """Mesh axes the batch (and the loss mean) shards over: always
    'dp', plus the dedicated expert axis when the mesh carries one —
    the DeepSpeed-MoE layout where ep is a sub-axis of data parallelism
    for every non-expert param (dense params replicate over ep and their
    grads psum over it, exactly like dp)."""
    ep_ax = getattr(cfg, "moe_mesh_axis", "dp")
    if cfg.n_experts and ep_ax != "dp" and ep_ax in mesh.axis_names:
        return ("dp", ep_ax)
    if getattr(cfg, "ep_extends_dp", False) and "ep" in mesh.axis_names:
        # EXPLICITLY opted in (cfg.ep_extends_dp): the dedicated ep axis
        # is extra data parallelism for this dense config, so one mesh
        # serves both model kinds.  Without the flag an axis named "ep"
        # is left alone — the name is only reserved for configs that ask.
        return ("dp", "ep")
    return ("dp",)


def _batch_entry(axes: tuple):
    """PartitionSpec entry for the batch dim over the data axes."""
    return axes if len(axes) > 1 else axes[0]


def _mean_over_axes(local, axes: tuple, denom: int):
    """Global mean of a per-rank value: sum-allreduce over each data
    axis, then one divide.  THE shared reduction for every train-step
    maker (SGD and ZeRO, plain and accumulated) — one definition so the
    steps cannot diverge on axis handling."""
    for a in axes:
        local = collectives.allreduce(local, a, ReduceFunction.SUM)
    return local / denom


# parameter partition specs over ('dp', 'tp'): column-parallel weights shard
# their output dim on tp, row-parallel weights their input dim.
def _layer_specs(cfg: TransformerConfig, kind: LayerKind) -> Dict:
    """The specs of one layer of ``kind``."""
    gated = cfg.ffn == "swiglu"
    cp = cfg.context_parallel
    # context parallelism: the tp axis carries the SEQUENCE ring, so
    # every weight is replicated over it (dp still shards the batch)
    col = P(None, None) if cp else P(None, "tp")   # output dim on tp
    row = P(None, None) if cp else P("tp", None)   # input dim on tp
    mixer = cfg.mixer(kind)
    heads = None if cp else "tp"
    if mixer == "none":
        layer = {}
    elif mixer == "mamba2":
        layer = {
            # [z | x | B | C | dt] = u W_in as five matrices, so that tp
            # splits the heads (z, x, dt) AND the groups (B, C) together:
            # a chip's heads keep their own groups; the taps, the biases,
            # the scalars a head and the grouped norm's scale follow
            "wz": col, "wx": col, "wb": col, "wc": col, "wdt": col,
            "conv_x": col, "conv_b": col, "conv_c": col,
            "bias_x": P(heads), "bias_b": P(heads), "bias_c": P(heads),
            "dt_bias": P(heads), "a_log": P(heads), "d_skip": P(heads),
            "y_norm": P(heads), "wo": row,
        }
    elif mixer == "kda":
        layer = {
            # every projection's columns are heads (``wbeta``'s one a
            # head), and so are the channels of the taps and of ``dt_bias``;
            # the output norm's scale is one head wide, every head's
            "wq": col, "wk": col, "wv": col,
            "wbeta": col, "conv_q": col, "conv_k": col, "conv_v": col,
            "a_log": P(heads), "dt_bias": P(heads), "o_norm": P(None),
            "wo": row,
        }
        if cfg.kda.head_decay:
            # a decay a head: ``wa``'s columns are the heads themselves
            layer.update(wa=col, wg=col)
        elif cfg.kda.gate_rank is None:
            layer.update(wf=col, wg=col)
        else:
            # the way down to the rank is every chip's, the way up has the
            # heads' columns
            layer.update(
                wf_a=P(None, None), wf_b=col, wg_a=P(None, None), wg_b=col
            )
    elif mixer == "latent":
        layer = {
            # the down-projections and their norms are every chip's; the
            # up-projections' columns are heads, sharded as wq's are
            "wkv_a": P(None, None), "kv_a_norm": P(None), "wkv_b": col,
            "wo": row,  # (heads * v_dim / tp, d_model)
        }
        if cfg.latent.q_rank is None:
            layer["wq"] = col
        else:
            layer.update(wq_a=P(None, None), q_a_norm=P(None), wq_b=col)
    else:
        layer = {
            "wq": col,  # (d_model, heads * head_size / tp): heads sharded
            "wk": col,
            "wv": col,
            "wo": row,  # (heads * head_size / tp, d_model)
        }
        if kind.sink:
            layer["sink"] = P(heads)  # a scalar a query head
    # one norm a sub-layer: a block without a mixer has no ``ln1``, one
    # without an FFN no ``ln2``
    softmax_mixer = mixer in ("attention", "latent")
    if cfg.post_norm != "only":
        if mixer != "none":
            layer["ln1"] = P(None)
        if kind.ffn != "none":
            layer["ln2"] = P(None)
    if cfg.attn_gate and softmax_mixer:
        layer["wg"] = col  # the gate's columns follow q's heads
    if cfg.post_norm:
        if mixer != "none":
            layer["ln1_post"] = P(None)
        if kind.ffn != "none":
            layer["ln2_post"] = P(None)
    # a KDA layer's q and k are L2-normalised: no learned QK-norm
    qk_norm = softmax_mixer and cfg.qk_norm
    if qk_norm == "head":
        # one scale of head_size for every head: replicated
        layer["q_norm"] = P(None)
        layer["k_norm"] = P(None)
    elif qk_norm:
        # scales of the whole projected q and k: sharded like the
        # projections' output columns
        layer["q_norm"] = P(heads)
        layer["k_norm"] = P(heads)
    if kind.ffn == "none":
        return layer
    if kind.ffn == "dense":
        layer["w1"] = col  # (d_model, d_ff/tp)
        layer["w2"] = row  # (d_ff/tp, d_model)
        if gated:
            layer["w3"] = col  # the gate's twin of w1
        return layer
    # MoE: the dense FFN pair is replaced by the expert bank — the
    # EXPERT dim shards over the expert axis (cfg.moe_mesh_axis: "dp"
    # welded, or a dedicated "ep"); the router gate is replicated.
    # Under cp the tp axis is the sequence ring: experts (like every
    # other weight) replicate over it — only the expert dim shards.
    # Otherwise experts shard over the expert axis AND each expert's
    # d_ff over tp (Megatron column/row split within the expert), so
    # MoE keeps the dense layout's tp FLOP/memory sharding instead of
    # replicating expert compute across tp
    ep_ax = cfg.moe_mesh_axis
    tp = None if cp else "tp"
    moe = {
        "gate": P(None, None),
        "w1": P(ep_ax, None, tp),
        "w2": P(ep_ax, tp, None),
    }
    if gated:
        moe["w3"] = P(ep_ax, None, tp)
    if cfg.moe_bias_rate:
        moe["bias"] = P(None)
    if cfg.moe_shared_d_ff:
        moe["shared"] = {"w1": col, "w2": row}
        if gated:
            moe["shared"]["w3"] = col
    if cfg.moe_latent:
        # into and out of the experts' width: every chip's, whole
        moe["w_down"] = P(None, None)
        moe["w_up"] = P(None, None)
    layer["moe"] = moe
    return layer


def param_specs(cfg: TransformerConfig) -> Dict:
    _check_axis_compat(cfg)
    out = {
        # vocab parallelism shards the table's VOCAB rows over tp (the
        # pos table and everything fed by the tp-allreduced lookup stay
        # replicated)
        "embed": P("tp", None) if cfg.vocab_parallel else P(None, None),
        "ln_f": P(None),
        "layers": [_layer_specs(cfg, kind) for kind in cfg.pattern()],
    }
    if not cfg.uses_rope():
        out["pos"] = P(None, None)
    if not cfg.tie_head:
        # the head's VOCAB columns shard over tp as the table's rows do
        out["head"] = P(None, "tp") if cfg.vocab_parallel else P(None, None)
    return out


def init_params(key, cfg: TransformerConfig) -> Dict:
    k = jax.random.split(key, 2 + 4 * cfg.n_layers)
    scale = 0.02
    params = {
        "embed": jax.random.normal(k[0], (cfg.vocab, cfg.d_model), cfg.dtype) * scale,
        "ln_f": jnp.ones((cfg.d_model,), cfg.dtype),
        "layers": [],
    }
    if not cfg.uses_rope():  # rope has no learned position table
        params["pos"] = (
            jax.random.normal(k[1], (cfg.max_seq, cfg.d_model), cfg.dtype)
            * scale
        )
    if not cfg.tie_head:
        params["head"] = (
            jax.random.normal(
                jax.random.fold_in(k[0], 1), (cfg.d_model, cfg.vocab),
                cfg.dtype,
            ) * scale
        )
    gated = cfg.ffn == "swiglu"
    hd = cfg.head_size()
    d_q = cfg.n_heads * hd

    def normal(key, shape):
        return jax.random.normal(key, shape, cfg.dtype) * scale

    def latent_mixer(key, la):
        ks = jax.random.split(key, 5)
        H = cfg.n_heads
        d_q = H * (la.nope_dim + la.rope_dim)
        if la.q_rank is None:
            q = {"wq": normal(ks[0], (cfg.d_model, d_q))}
        else:
            q = {
                "wq_a": normal(ks[0], (cfg.d_model, la.q_rank)),
                "q_a_norm": jnp.ones((la.q_rank,), cfg.dtype),
                "wq_b": normal(ks[1], (la.q_rank, d_q)),
            }
        return {
            **q,
            "wkv_a": normal(ks[2], (cfg.d_model, la.kv_rank + la.rope_dim)),
            "kv_a_norm": jnp.ones((la.kv_rank,), cfg.dtype),
            "wkv_b": normal(ks[3], (la.kv_rank, H * (la.nope_dim + la.v_dim))),
            "wo": normal(ks[4], (H * la.v_dim, cfg.d_model)),
        }

    def kda_mixer(key, kda):
        """The matrices as every other (normal, 0.02); the taps normal at
        ``conv ** -0.5`` (a Conv1d's default range); ``a_log`` the log of
        a uniform draw from [1, 16) a head (the family's convention);
        ``dt_bias`` standard normal a channel, so that channels differ in
        how fast they forget.  Under the gate without a bound ``dt_bias``
        is the inverse softplus of a log-uniform draw from
        :data:`KDA_UNBOUNDED_DT` (the family's own range, [0.001, 0.1],
        would leave every seeded channel remembering for hundreds of
        tokens; a trained gate does not): log-decays from -0.001 to under
        -100 a token, channels on both sides of a bound of -5.  A
        ``gate_rank`` splits ``wf`` and ``wg`` in two, normal alike."""
        ks = jax.random.split(key, 12)
        wide = cfg.n_heads * kda.head_dim
        wide_v = cfg.n_heads * kda.value_dim()
        matrix = lambda key, n=wide: normal(key, (cfg.d_model, n))
        taps = lambda key, n=wide: (
            jax.random.normal(key, (kda.conv, n), cfg.dtype)
            * kda.conv ** -0.5
        )
        if kda.head_decay:
            gates = {
                "wa": matrix(ks[3], cfg.n_heads), "wg": matrix(ks[4], wide_v),
            }
        elif kda.gate_rank is None:
            gates = {"wf": matrix(ks[3]), "wg": matrix(ks[4])}
        else:
            down = lambda key: normal(key, (cfg.d_model, kda.gate_rank))
            up = lambda key: normal(
                jax.random.fold_in(key, 1), (kda.gate_rank, wide)
            )
            gates = {
                "wf_a": down(ks[3]), "wf_b": up(ks[3]),
                "wg_a": down(ks[4]), "wg_b": up(ks[4]),
            }
        if kda.lower_bound is None:
            low, high = KDA_HEAD_DECAY_DT if kda.head_decay else KDA_UNBOUNDED_DT
            dt = jnp.exp(jax.random.uniform(
                ks[10], (cfg.n_heads if kda.head_decay else wide,),
                jnp.float32, math.log(low), math.log(high),
            ))
            dt_bias = dt + jnp.log(-jnp.expm1(-dt))
        else:
            dt_bias = jax.random.normal(ks[10], (wide,), jnp.float32)
        return {
            "wq": matrix(ks[0]), "wk": matrix(ks[1]),
            "wv": matrix(ks[2], wide_v),
            **gates,
            "wbeta": normal(ks[5], (cfg.d_model, cfg.n_heads)),
            "conv_q": taps(ks[6]), "conv_k": taps(ks[7]),
            "conv_v": taps(ks[8], wide_v),
            "a_log": jnp.log(jax.random.uniform(
                ks[9], (cfg.n_heads,), jnp.float32, 1.0, 16.0
            )),
            "dt_bias": dt_bias,
            "o_norm": jnp.ones((kda.value_dim(),), cfg.dtype),
            "wo": normal(ks[11], (wide_v, cfg.d_model)),
        }

    def mamba_mixer(key, m):
        """The matrices as every other (normal, 0.02); taps and the
        convolution's bias normal at ``conv ** -0.5``; ``a_log`` the log of
        a uniform draw from [1, 16) a head, ``dt_bias`` the inverse
        softplus of a log-uniform draw from [dt_min, dt_max) floored at
        dt_floor, ``d_skip`` 1 (the family's conventions)."""
        ks = jax.random.split(key, 14)
        inner, bc = m.n_heads * m.head_dim, m.groups * m.state
        matrix = lambda key, n: normal(key, (cfg.d_model, n))
        taps = lambda key, n: (
            jax.random.normal(key, (m.conv, n), cfg.dtype) * m.conv ** -0.5
        )
        bias = lambda key, n: (
            jax.random.normal(key, (n,), cfg.dtype) * m.conv ** -0.5
        )
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            ks[12], (m.n_heads,), jnp.float32, math.log(m.dt_min),
            math.log(m.dt_max),
        )), m.dt_floor)
        return {
            "wz": matrix(ks[0], inner), "wx": matrix(ks[1], inner),
            "wb": matrix(ks[2], bc), "wc": matrix(ks[3], bc),
            "wdt": matrix(ks[4], m.n_heads),
            "conv_x": taps(ks[5], inner), "conv_b": taps(ks[6], bc),
            "conv_c": taps(ks[7], bc),
            "bias_x": bias(ks[8], inner), "bias_b": bias(ks[9], bc),
            "bias_c": bias(ks[10], bc),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "a_log": jnp.log(jax.random.uniform(
                ks[11], (m.n_heads,), jnp.float32, 1.0, 16.0
            )),
            "d_skip": jnp.ones((m.n_heads,), jnp.float32),
            "y_norm": jnp.ones((inner,), cfg.dtype),
            "wo": normal(ks[13], (inner, cfg.d_model)),
        }

    for i, kind in enumerate(cfg.pattern()):
        kk = k[2 + 4 * i : 6 + 4 * i]
        mixer = cfg.mixer(kind)
        if mixer == "none":
            layer = {}
        elif mixer == "mamba2":
            layer = mamba_mixer(kk[0], cfg.mamba)
        elif mixer == "kda":
            layer = kda_mixer(kk[0], cfg.kda)
        elif mixer == "latent":
            layer = latent_mixer(kk[0], cfg.latent)
        else:
            # the kind's own K/V heads and v width where it has them
            n_kv = cfg.kv_heads(kind)
            dv = (kind.heads and kind.heads.v_dim) or hd
            layer = {
                "wq": normal(kk[0], (cfg.d_model, d_q)),
                "wk": normal(
                    jax.random.fold_in(kk[0], 1), (cfg.d_model, n_kv * hd)
                ),
                "wv": normal(
                    jax.random.fold_in(kk[0], 2), (cfg.d_model, n_kv * dv)
                ),
                "wo": normal(kk[1], (cfg.n_heads * dv, cfg.d_model)),
            }
            if kind.sink:
                layer["sink"] = jnp.zeros((cfg.n_heads,), jnp.float32)
        softmax_mixer = mixer in ("attention", "latent")
        if cfg.post_norm != "only":
            if mixer != "none":
                layer["ln1"] = jnp.ones((cfg.d_model,), cfg.dtype)
            if kind.ffn != "none":
                layer["ln2"] = jnp.ones((cfg.d_model,), cfg.dtype)
        if cfg.attn_gate and softmax_mixer:
            layer["wg"] = normal(
                jax.random.fold_in(kk[1], 1),
                (cfg.d_model, cfg.n_heads if cfg.attn_gate == "head" else d_q),
            )
        if cfg.post_norm:
            if mixer != "none":
                layer["ln1_post"] = jnp.ones((cfg.d_model,), cfg.dtype)
            if kind.ffn != "none":
                layer["ln2_post"] = jnp.ones((cfg.d_model,), cfg.dtype)
        # a KDA layer's q and k are L2-normalised: no learned QK-norm
        qk_norm = softmax_mixer and cfg.qk_norm
        if qk_norm == "head":
            layer["q_norm"] = jnp.ones((hd,), cfg.dtype)
            layer["k_norm"] = jnp.ones((hd,), cfg.dtype)
        elif qk_norm:
            layer["q_norm"] = jnp.ones((d_q,), cfg.dtype)
            layer["k_norm"] = jnp.ones((layer["wk"].shape[1],), cfg.dtype)
        if kind.ffn == "moe":
            from .moe import init_moe_params

            layer["moe"] = init_moe_params(
                kk[2], cfg.d_model, kind.d_ff, cfg.n_experts, cfg.dtype,
                gated=gated, router_experts=cfg.router_experts(),
                shared_d_ff=cfg.moe_shared_d_ff, bias=bool(cfg.moe_bias_rate),
                latent=cfg.moe_latent,
            )
        elif kind.ffn == "dense":
            layer["w1"] = normal(kk[2], (cfg.d_model, kind.d_ff))
            layer["w2"] = normal(kk[3], (kind.d_ff, cfg.d_model))
            if gated:
                layer["w3"] = normal(
                    jax.random.fold_in(kk[2], 1), (cfg.d_model, kind.d_ff)
                )
        params["layers"].append(layer)
    return params


def _layernorm(x, scale, eps: float = 1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale


def _rmsnorm(x, scale, tp_axis=None, eps: float = 1e-5):
    """RMSNorm, statistics in f32 and the scale applied in the input's
    type.  ``tp_axis``: the last dim is a tp shard of the normed width
    (QK-norm over head-sharded projections), so the mean square is taken
    over the whole width with one allreduce of a scalar a row."""
    x32 = x.astype(jnp.float32)
    ss = jnp.sum(x32 * x32, axis=-1, keepdims=True)
    width = x.shape[-1]
    if tp_axis is not None:
        ss = collectives.allreduce(ss, tp_axis, ReduceFunction.SUM)
        width = width * jax.lax.axis_size(tp_axis)
    return (x32 * jax.lax.rsqrt(ss / width + eps)).astype(x.dtype) * scale


_NORMS = {"layernorm": _layernorm, "rmsnorm": _rmsnorm}


def _norm_fn(cfg):
    """The config's norm at its ``norm_eps``."""
    fn = _NORMS[cfg.norm]
    return fn if cfg.norm_eps == 1e-5 else partial(fn, eps=cfg.norm_eps)


def _qk_norm(q, k, lp, tp_axis, eps: float = 1e-5):
    """A layer with ``q_norm``/``k_norm`` scales RMS-normalises the whole
    projected q and k (every head at once, over ``tp_axis`` where the
    heads are sharded) before the split into heads, at the
    configuration's ``norm_eps``."""
    if "q_norm" not in lp:
        return q, k
    return (
        _rmsnorm(q, lp["q_norm"], tp_axis, eps),
        _rmsnorm(k, lp["k_norm"], tp_axis, eps),
    )


def _vp_active(cfg, tp_axis) -> bool:
    return bool(cfg.vocab_parallel) and tp_axis is not None


def _cp_active(cfg, tp_axis) -> bool:
    return bool(cfg.context_parallel) and tp_axis is not None


def _cp_positions(t_local: int, axis):
    """Global token positions of this rank's STRIPED sequence shard:
    local position ``t`` holds global token ``t * ring_size + rank``
    (see :func:`ring_attention.stripe_sequence`)."""
    from jax import lax

    return jnp.arange(t_local) * lax.axis_size(axis) + lax.axis_index(axis)


def _vp_local_ids(ids, vl: int, vocab: int, tp_axis):
    """Map global ids onto this rank's vocab shard of ``vl`` rows.
    Returns ``(local, mine)``: in-shard row indices and the ownership
    mask.  Ids are clipped to ``[0, vocab)`` FIRST so out-of-range ids
    resolve to the last vocab row on exactly one shard — the same clamp
    semantics as the replicated ``embed[ids]`` gather."""
    from jax import lax

    ids = jnp.clip(ids, 0, vocab - 1)
    local = ids - lax.axis_index(tp_axis) * vl
    mine = (local >= 0) & (local < vl)
    return jnp.clip(local, 0, vl - 1), mine


#: Widths at which XLA's scatter-add was measured to walk the TABLE, this
#: many ns a table row whatever the number of scattered rows, once more
#: than a tenth as many rows are scattered as the table has (a v5e;
#: ``PERF.md`` section 5, PR 37's grids: 13 widths from 1,024 to 16,384,
#: these five the ones with such a walk; why is not known).
_SCATTER_WALK_NS = {2560: 350, 3584: 150, 4608: 230, 5120: 1600, 7168: 520}


def _onehot_wins(V: int, N: int, D: int, itemsize: int) -> bool:
    """Whether the lookup's cotangent (:func:`_gathered_rows_bwd`) is
    cheaper as one matmul against the ids' one-hot than as XLA's
    scatter-add of ``N`` rows of ``D`` columns onto a table of ``V`` rows,
    by what the two were measured to take on a v5e (``PERF.md`` section 5,
    PR 37: 51 shapes, each form within a fifth of this model, bar the
    scatter-add at OLMoE's shape, which it reads at half).  The matmul's
    ``2 V N D`` FLOPs run at 86-98% of 197 TFLOP/s in bf16 (0.9 here), a
    float32 cotangent's in three passes.  The scatter-add writes the table
    once at about 275 GB/s, takes an ELEMENT of a scattered row, whatever
    its type, in 0.043 ns up to 4,096 columns and 0.085 ns past them, and
    at some widths walks the table besides (``_SCATTER_WALK_NS``: 20 of
    DeepSeek-V2's 22.8 ms).  The choice is the compiler's weakness at a
    shape, so it reads shapes and nothing else."""
    passes = 1 if itemsize <= 2 else 3
    matmul_s = passes * 2.0 * V * N * D / (0.9 * 197e12)
    element_ns = 0.043 if D <= 4096 else 0.085
    scatter_s = V * D * itemsize / 275e9 + N * D * element_ns * 1e-9
    if 10 * N > V:
        scatter_s += V * _SCATTER_WALK_NS.get(D, 0) * 1e-9
    return matmul_s < scatter_s


@jax.custom_vjp
def _gathered_rows(embed, ids):
    return embed[ids]


def _gathered_rows_fwd(embed, ids):
    # the gather with JAX's own transpose of it, the scatter-add XLA has
    # always been handed, kept for the backward to use or to leave
    rows, scatter_add = jax.vjp(lambda e: e[ids], embed)
    return rows, (scatter_add, ids)


def _gathered_rows_bwd(res, g):
    """The lookup's cotangent: ``dE[v] = sum of g[n] over ids[n] == v``,
    ``(V, D)`` in ``g``'s type, by one of two lowerings chosen by
    :func:`_onehot_wins`: XLA's scatter-add, or ``one_hot(ids, V)^T @ g``
    on the MXU (the one-hot in ``g``'s type, 0 and 1 exact; float32
    accumulation).  They differ in the order of a row's float32
    additions.  Ids are in ``[0, V)``, as ``embed[ids]`` promises."""
    scatter_add, ids = res
    V, D = jax.eval_shape(scatter_add, g)[0].shape[0], g.shape[-1]
    with device_scope("accl.embed::grad"):
        if not _onehot_wins(V, ids.size, D, g.dtype.itemsize):
            return scatter_add(g)[0], None
        hot = jax.nn.one_hot(ids.reshape(-1), V, dtype=g.dtype)
        placed = jax.lax.dot_general(
            hot, g.reshape(-1, D), (((0,), (0,)), ((), ())),
            # a float32 cotangent keeps its mantissa through the MXU
            precision=None if g.dtype.itemsize <= 2 else "highest",
            preferred_element_type=jnp.float32,
        )
        return placed.astype(g.dtype), None


_gathered_rows.defvjp(_gathered_rows_fwd, _gathered_rows_bwd)


def _table_rows(embed, ids) -> jax.Array:
    """``embed[ids]``; the cotangent is :func:`_gathered_rows_bwd`'s."""
    # inside a shard_map: the table varying over the axes the ids vary
    # over (dp), so that its cotangent is summed over those by the
    # cast's transpose, as the plain gather's is
    if missing := tuple(jax.typeof(ids).vma - jax.typeof(embed).vma):
        embed = jax.lax.pcast(embed, missing, to="varying")
    return _gathered_rows(embed, ids)


def _embed_rows(embed, ids, cfg, tp_axis) -> jax.Array:
    """Embedding lookup that understands a vocab-row-sharded table: each
    rank looks up the ids it owns (masked) and a tp-allreduce assembles
    the rest — the Megatron vocab-parallel embedding."""
    if not _vp_active(cfg, tp_axis):
        return _table_rows(embed, ids)
    local, mine = _vp_local_ids(ids, embed.shape[0], cfg.vocab, tp_axis)
    out = _table_rows(embed, local) * mine[..., None].astype(embed.dtype)
    return collectives.allreduce(out, tp_axis, ReduceFunction.SUM)


def _embed_tokens(params, tokens, cfg, tp_axis=None) -> jax.Array:
    """Token embeddings, plus the learned position table unless the
    config uses rotary embeddings (rope encodes position inside
    attention, so there is no table to add).  Under context parallelism
    ``tokens`` is this rank's STRIPED shard, so the pos rows are
    gathered at the shard's global positions."""
    x = _embed_rows(params["embed"], tokens, cfg, tp_axis)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale  # a Python float: x keeps its dtype
    if not cfg.uses_rope():
        if _cp_active(cfg, tp_axis):
            x = x + params["pos"][_cp_positions(tokens.shape[1], tp_axis)]
        else:
            x = x + params["pos"][: tokens.shape[1]]
    return x


def diffusion_noise(key, tokens, diffusion: BlockDiffusion, shard=None):
    """The noisy copy of ``tokens`` (S, L) under :class:`BlockDiffusion`,
    from ``key`` (a typed key or its two uint32 words): a level ``t = eps
    + (1 - eps) u`` a block a sequence, each position masked with its
    block's ``t``.  Returns ``(noisy ids, masked (S, L) bool, t (S, L)
    float32)``; the same key gives the same draw.  ``shard=(index,
    count)``: ``tokens`` are sequences ``index * S ..`` of a batch of
    ``count * S`` and get that batch's draw for them, so a batch is
    noised alike however it is sharded.  Device scope
    ``accl.diffusion::noise``."""
    S, L = tokens.shape
    block = diffusion.block
    if L % block:
        raise ValueError(
            f"block diffusion: {L} ids are no whole blocks of {block}"
        )
    index, count = (0, 1) if shard is None else shard

    def mine(draw):
        return draw if count == 1 else jax.lax.dynamic_slice_in_dim(
            draw, index * S, S
        )

    with device_scope("accl.diffusion::noise"):
        level_key, mask_key = jax.random.split(key)
        u = mine(jax.random.uniform(
            level_key, (count * S, L // block), jnp.float32
        ))
        t = jnp.repeat(diffusion.eps + (1.0 - diffusion.eps) * u, block, axis=1)
        masked = mine(jax.random.uniform(
            mask_key, (count * S, L), jnp.float32
        )) < t
        noisy = jnp.where(masked, diffusion.mask_id, tokens)
    return noisy.astype(tokens.dtype), masked, t


_BALANCE = ("balance_expert", "balance_device", "balance_comm")


def _moe_penalty(cfg, aux) -> jax.Array:
    """The router health penalty loss_fn adds for MoE configs: Switch
    load-balance aux + ST-MoE z-loss, averaged over layers (``aux``
    carries the layer SUMS from :func:`_final_hidden`); and where the
    config weighs them (``moe_balance_weights``) DeepSeek-V2's three
    balance losses, summed over the expert layers."""
    n = float(cfg.n_layers)
    penalty = (
        cfg.moe_aux_weight * aux["load_balance"] / n
        + cfg.moe_router_z_weight * aux["router_z"] / n
    )
    if any(cfg.moe_balance_weights):
        for weight, name in zip(cfg.moe_balance_weights, _BALANCE):
            penalty = penalty + weight * aux[name]
    return penalty


def _token_nll(logits, targets) -> jax.Array:
    """Per-token next-token NLL from full-vocab logits.  Softmax
    statistics run in f32 (bf16 logits overflow exp quickly — the same
    dtype policy as the fused vocab-parallel form)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(
        logp, targets[..., None], axis=-1
    ).squeeze(-1)


def _lm_logits(x, params, cfg, tp_axis, gather: bool = True) -> jax.Array:
    """LM head: tied ``x @ embed.T``, or ``x @ head`` where the tree has
    a head of its own.  Under vocab parallelism the product
    is VOCAB-SHARDED ``(..., vocab/tp)``; ``gather=True`` (the forward()
    API contract) reassembles the full vocab axis, ``gather=False``
    leaves the shards for the fused loss."""
    if "head" in params:
        z = x @ params["head"]
    else:
        z = x @ params["embed"].T
    if _vp_active(cfg, tp_axis) and gather:
        z = collectives.allgather_invariant(z, tp_axis, axis=z.ndim - 1)
    return z


def _rope_tables(positions, half: int, base: float, inv_freq=None,
                 table_scale: float = 1.0):
    """cos/sin tables for rotary embedding at the given absolute
    ``positions`` (shape (T,); traced values fine — decode passes its
    dynamic cursor).  Computed once per attention site and shared by
    the q and k rotations (and across layers on the decode path), so
    scanned/rematerialized blocks don't rebuild the pow/cos/sin chain
    per layer.  ``inv_freq`` (``half`` of them) are the configuration's
    own inverse frequencies (YaRN, ``TransformerConfig.rope_inv_freq``)
    in place of ``base``'s; ``table_scale`` multiplies both tables."""
    if inv_freq is None:
        freqs = jnp.asarray(base, jnp.float32) ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half
        )
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # (T, half)
    if table_scale != 1.0:
        return jnp.cos(ang) * table_scale, jnp.sin(ang) * table_scale
    return jnp.cos(ang), jnp.sin(ang)


def _rope_rotate(x, tables):
    """Rotary position embedding [RoFormer]: rotate each (i, i+half)
    feature pair of every head by position*freq_i.  ``x`` is
    (B, H, T, hd) with hd even; ``tables`` from :func:`_rope_tables`.
    Rotation runs in f32, the result is cast back so bf16 activations
    stay bf16 (the dtype-discipline rule everywhere in this file)."""
    cos, sin = tables
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# measured crossover on v5e (see TransformerConfig.attention): with the
# block=512 flash kernel the fused form wins the full train step from
# T=1024 up (75.4% vs 69.5% MFU at T=1024; at T=4096 it is the only
# form that fits HBM), so auto resolves to a fused form at/above this
# and to naive only below it (tiny-T padding-overhead regime)
_AUTO_FUSED_MIN_T = 1024
# flash holds whole K/V in VMEM per batch-head (and its one backward
# kernel whole Q, dO, the dq block and an f32 dq accumulator: six times
# K's bytes plus T x hd x 4, which that call computes from the shapes
# and passes as its own VMEM limit — 24 MiB at this gate's edge): auto
# uses it only while K+V fit this budget (4 MiB = T 8192 at hd<=128
# bf16; the gate scales with the PADDED head dim and dtype width, so
# wide-head or f32 configs fall back to the streaming XLA fold instead
# of failing Mosaic's VMEM allocation).  A head whose rotating part is
# passed APART (``q_rope`` / ``k_rope``: the latent mixer's, and a
# ``HeadGeometry.rope_dim``'s) is gated by each part's own padded width:
# the gate sees the first part alone (``_attention``), the second rides
# beside it on its own lanes.  The MiMo-V2.5 cell is the case: a head of
# 192 as ONE operand pads to 256 lanes and stops ``auto`` at T = 4,096;
# as 128 columns without position beside a rotating 64 (padded to 128)
# it runs the kernels at 8,192
_AUTO_FLASH_KV_BYTES = 4 * 2**20


def _auto_flash_fits(q) -> bool:
    import jax.numpy as jnp

    if q.dtype == jnp.float16:
        # Mosaic's TPU lowering rejects f16 matmul operands (ValueError
        # at compile), so auto must never route f16 into the flash
        # kernel — it falls through to the XLA blockwise fold instead.
        # Explicit
        # attention="flash" still surfaces the kernel's own f16 error.
        return False
    Dp = -(-q.shape[-1] // 128) * 128  # lane-padded head dim
    return 2 * q.shape[2] * Dp * q.dtype.itemsize <= _AUTO_FLASH_KV_BYTES


def resolve_attention(impl: str, q) -> str:
    """The lowering ``impl`` names for a per-device ``(B, H, T, hd)``
    query (anything with ``shape`` and ``dtype``).  ``"auto"`` resolves
    by sequence length and backend: naive under ``_AUTO_FUSED_MIN_T``;
    at/above it the Pallas flash kernel on TPU while its K/V tiles fit
    VMEM (:func:`_auto_flash_fits`), and the XLA blockwise fold off TPU
    or past that gate.  Callers that must know which one ran (the chip
    smoke) ask here instead of guessing."""
    if impl != "auto":
        return impl
    if q.shape[2] < _AUTO_FUSED_MIN_T:
        return "naive"
    if jax.default_backend() == "tpu" and _auto_flash_fits(q):
        return "flash"  # Mosaic-compiled; trainable via custom_vjp
    return "blockwise"


def _attention(q, k, v, impl: str = "naive", causal: bool = True,
               window: Optional[int] = None, scale: Optional[float] = None,
               q_rope=None, k_rope=None, block_diffusion=None, sink=None):
    """Attention; q,k,v: (B, H, T, hd); ``causal=False`` is the
    bidirectional (encoder) form; ``window`` (causal only) keeps a
    query's last ``window`` keys, its own among them, in every lowering.
    v (and the result) may be another width than q and k; ``scale``
    multiplies the scores (``hd ** -0.5`` where not given); ``q_rope``
    (B, H, T, dr) and ``k_rope`` (B, fewer heads, T, dr) are a second
    part of q and k whose product is added to the scores: the flash
    kernels take them as they are (the few key heads shared through the
    index map), the XLA forms get them joined onto q and k.
    ``block_diffusion=(L, B)`` is the block-diffusion layout of ``T = 2 L``
    rows in place of ``causal``: tile lists in the flash kernels, a dense
    mask in the XLA forms (``ops.attention.block_diffusion_visible``).
    ``sink`` (H,): one learned scalar a query head that stands in every
    row's softmax as a key without a value, in every lowering (the carry's
    first term in the two folds, one more column here).

    ``impl="auto"`` resolves through :func:`resolve_attention`;
    ``"blockwise"`` runs the fused online-softmax fold (no (T, T) score
    matrix in HBM); ``"naive"`` is the materialized-scores baseline."""
    # with a second score part the flash kernels hold, a head, k's first
    # part and v as they do without one, and beside them the second part
    # of as few heads as it has (one under MLA): ``auto`` is decided on the
    # first part's width
    impl = resolve_attention(impl, q)
    if q_rope is not None:
        if scale is None:
            scale = (q.shape[-1] + q_rope.shape[-1]) ** -0.5
        if impl != "flash":
            # k's second part on as many heads as k has (all of q's under
            # the latent mixer, whose ``expand(k)`` is then the identity)
            B, H, T = q.shape[0], k.shape[1], q.shape[2]
            expand = lambda t: jnp.broadcast_to(
                t[:, :, None], (B, t.shape[1], H // t.shape[1], T, t.shape[-1])
            ).reshape(B, H, T, t.shape[-1])
            q = jnp.concatenate([q, q_rope], axis=-1)
            k = jnp.concatenate([expand(k), expand(k_rope)], axis=-1)
            q_rope = k_rope = None
    # no window, no scale: each lowering is called as it was before there
    # was one (tests put a spy of the old signature in a lowering's place)
    windowed = {} if window is None else {"window": window}
    if scale is not None:
        windowed["scale"] = scale
    if block_diffusion is not None:
        if window is not None:
            raise ValueError("block diffusion has no window")
        windowed["block_diffusion"] = block_diffusion
    if sink is not None and impl != "naive":
        windowed["sink"] = sink
    if impl == "blockwise":
        from ..ops.attention import blockwise_attention

        return blockwise_attention(q, k, v, causal=causal, **windowed)
    if impl == "flash":
        # the Pallas kernel owns the fold schedule; its custom_vjp
        # backward kernel makes it trainable (rebuilds probability tiles
        # from the saved logsumexp — no (T, T) residual)
        from ..ops.pallas.attention import flash_attention

        if q_rope is not None:
            windowed.update(q_rope=q_rope, k_rope=k_rope)
        return flash_attention(q, k, v, causal=causal, **windowed)
    if impl != "naive":
        raise ValueError(f"unknown attention impl {impl!r}")
    B, H, T, hd = q.shape
    Hkv = k.shape[1]
    # grouped-query attention folds the group into the einsum (each kv
    # head broadcasts across its G query heads; k/v are never expanded)
    qg = q.reshape(B, Hkv, H // Hkv, T, hd)
    # matmuls stay in the input dtype (bf16 on the MXU's fast path) with
    # f32 accumulation; softmax statistics run in f32 and the probs cast
    # back down for the second matmul.  The scale is a PYTHON float — a
    # NumPy scalar (np.sqrt) is strongly typed and would silently promote
    # bf16 activations to f32 through the rest of the block.
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(hd) if scale is None else scale)
    if window is not None and not causal:
        raise ValueError("a window is causal")
    if block_diffusion is not None:
        from ..ops.attention import block_diffusion_visible

        pos = jnp.arange(T)
        mask = block_diffusion_visible(
            pos[:, None], pos[None, :], *block_diffusion
        )
        scores = jnp.where(mask, scores, -1e30)
    elif causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((T, T), bool), -window)
        scores = jnp.where(mask, scores, -1e30)
    if sink is not None:
        # one more column a row, the head's scalar; it takes its share of
        # the row's probability and has no value
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, Hkv, H // Hkv, 1, 1),
            (*scores.shape[:-1], 1),
        )
        scores = jnp.concatenate([scores, column], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :-1].astype(v.dtype)
    else:
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
    return out.reshape(B, H, T, v.shape[-1])


def _ffn_hidden(h, lp, relu2: bool = False):
    """The dense FFN's hidden activation: gated SiLU where the layer has
    a ``w3``, else GELU or, by the caller's word, ``relu ** 2``."""
    if "w3" in lp:
        return jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])
    if relu2:
        return jnp.square(jax.nn.relu(h @ lp["w1"]))
    return jax.nn.gelu(h @ lp["w1"])


def _mlp(x, lp, tp_axis, ep_axis=None, moe_cfg=None, with_aux=False,
         moe_no_drop=False, reduce_fn=None, fanout_fn=None,
         norm=_layernorm, relu2=False):
    """The block's MLP half (shared by train and decode paths): ln2 ->
    column-parallel up, row-parallel down -> tp-allreduce, residual.

    When the layer carries an expert bank (``lp["moe"]``) the dense pair
    is replaced by the top-k routed expert FFN: tokens dispatch to their
    expert's dp rank through the all-to-all over ``ep_axis`` and the
    outputs return the same way (models/moe.py).  ``with_aux=True``
    (training) additionally returns the router health terms; serving
    paths leave it off.  ``relu2``: the two-matrix FFN's activation is
    ``relu ** 2`` (``TransformerConfig.ffn``), dense, routed and shared.
    A tree without ``ln2`` (``post_norm="only"``) reads the stream itself."""
    h = norm(x, lp["ln2"]) if "ln2" in lp else x
    if "moe" in lp:
        from .moe import moe_ffn

        # decode steps route a handful of tokens at a time: a training
        # capacity_factor there could drop a token the full forward
        # would have kept (decode-vs-forward divergence), so serving
        # uses the no-drop capacity (cf = E covers even an all-tokens-
        # to-one-expert step at trivial memory); a dropless config
        # (None) drops nothing anywhere
        cf = moe_cfg.moe_capacity_factor
        if moe_no_drop and cf is not None:
            cf = float(moe_cfg.n_experts)
        beyond = {}
        if moe_cfg.moe_router != "softmax":
            beyond.update(router=moe_cfg.moe_router)
        if moe_cfg.moe_route_scale != 1.0:
            beyond.update(route_scale=moe_cfg.moe_route_scale)
        if moe_cfg.moe_n_group != 1:
            beyond.update(n_group=moe_cfg.moe_n_group,
                          topk_group=moe_cfg.moe_topk_group)
        if relu2:
            beyond.update(relu2=True)
        if with_aux and any(moe_cfg.moe_balance_weights):
            beyond.update(balance_groups=moe_cfg.moe_n_group)
        if moe_cfg.moe_router_experts is not None:
            beyond.update(first_expert=moe_cfg.moe_first_expert,
                          held_row_factor=moe_cfg.moe_held_row_factor)
            if with_aux and moe_cfg.moe_aux_weight:
                # the Switch term over all the router's outputs, computed
                # only where the loss weighs it
                beyond.update(switch_balance=True)
        out = moe_ffn(
            h, lp["moe"], ep_axis=ep_axis,
            capacity_factor=cf,
            k=moe_cfg.moe_top_k,
            return_aux=with_aux,
            tp_axis=tp_axis,
            renormalize=moe_cfg.moe_norm_topk_prob,
            **beyond,
        )
        y, aux = out if with_aux else (out, None)
        if "ln2_post" in lp:
            y = norm(y, lp["ln2_post"])
        return (x + y, aux) if with_aux else x + y
    if fanout_fn is not None and tp_axis is not None:
        h = fanout_fn(h, tp_axis)  # see _block: the w1 fan-out point
    partial_f = _ffn_hidden(h, lp, relu2) @ lp["w2"]
    if tp_axis is not None:
        if reduce_fn is None:
            partial_f = collectives.allreduce(
                partial_f, tp_axis, ReduceFunction.SUM
            )
        else:
            partial_f = reduce_fn(partial_f, tp_axis)
    if "ln2_post" in lp:
        partial_f = norm(partial_f, lp["ln2_post"])
    return (x + partial_f, None) if with_aux else x + partial_f


def _latent_attn_partial(h, lp, n_heads_local, attn_impl, causal, rope_base,
                         window, latent):
    """The latent mixer (``TransformerConfig.latent``) on a full-sequence
    activation, heads column-parallel: the row-parallel PARTIAL output.
    The sizes are the tree's (``wq_b`` and ``wkv_b`` hold this chip's
    heads); ``latent`` carries what the shapes do not say: the softmax
    ``scale``, the rotary ``inv_freq`` and ``table_scale``, the norms'
    ``eps``.  Projections, norms and rope run under the device scope
    ``accl.attn::latent``, the score/softmax/value core under
    ``accl.attn::mla``; the rope key goes to the core as ONE head.  What
    the tree holds picks the rest: a ``wq`` in place of ``wq_a`` is q
    straight from the hidden state (no q latent, no q norm), and a ``wg``
    ``(d_model, heads)`` gates each head's output by ``sigmoid(h wg)``
    before ``wo``.  Under ``cfg.remat`` the block keeps the flash core's
    ``o`` and ``lse`` (its forward rule names them) and nothing of this
    mixer's own: q, k and v are expanded from the latents on every head
    (0.4 GB more a layer at Ling-3.0's widths) and are replayed."""
    B, T, _ = h.shape
    H = n_heads_local
    rank = lp["wkv_b"].shape[0]
    dr = lp["wkv_a"].shape[1] - rank
    norm = partial(_rmsnorm, eps=latent["eps"])
    heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
    with device_scope("accl.attn::latent"):
        if "wq_a" in lp:
            q = heads(norm(h @ lp["wq_a"], lp["q_a_norm"]) @ lp["wq_b"], H)
        else:
            q = heads(h @ lp["wq"], H)
        dn = q.shape[-1] - dr
        ckv = h @ lp["wkv_a"]
        kv = heads(norm(ckv[..., :rank], lp["kv_a_norm"]) @ lp["wkv_b"], H)
        q_n, q_r = q[..., :dn], q[..., dn:]
        k_n, v = kv[..., :dn], kv[..., dn:]
        k_r = heads(ckv[..., rank:], 1)
        if rope_base is not None:
            tables = _rope_tables(
                jnp.arange(T), dr // 2, rope_base, latent["inv_freq"],
                latent["table_scale"],
            )
            q_r = _rope_rotate(q_r, tables)
            k_r = _rope_rotate(k_r, tables)
        # inside a shard_map: the one rope key varying over the axes the
        # heads vary over (tp), so that its cotangent is summed over the
        # chips' heads by the cast's transpose
        if missing := tuple(jax.typeof(q_r).vma - jax.typeof(k_r).vma):
            k_r = jax.lax.pcast(k_r, missing, to="varying")
    with device_scope("accl.attn::mla"):
        attn = _attention(
            q_n, k_n, v, impl=attn_impl, causal=causal, window=window,
            scale=latent["scale"], q_rope=q_r, k_rope=k_r,
        )
    with device_scope("accl.attn::latent"):
        if "wg" in lp:
            gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
            attn = attn * gate.astype(attn.dtype).transpose(0, 2, 1)[..., None]
        return attn.transpose(0, 2, 1, 3).reshape(B, T, -1) @ lp["wo"]


def _kda_partial(h, lp, n_heads_local, kda):
    """The KDA mixer (:class:`DeltaAttention`) on a full-sequence
    activation, heads column-parallel: the row-parallel PARTIAL output.
    The sizes are the tree's (gate projections through a rank where it
    holds ``wf_a`` / ``wf_b`` and ``wg_a`` / ``wg_b``); ``kda`` carries what
    the shapes do not say, the gate's ``lower_bound`` (``None``: the gate
    without a bound, and the core's split by halving), the write strength's
    ``beta_scale`` and the output norm's ``eps``.  The matmuls
    take the activations' type; the convolutions, SiLU, the L2 norms, the
    gate, beta, the core and the output norm are float32, in either
    lowering of the three chains round the core (``ops.kda``: ``conv_in``
    from a projection to q, k or v, ``decay_in`` to the log-decay,
    ``gated_out`` from ``o`` to what ``wo`` takes; at heads of whole lanes
    each is one Mosaic kernel forward and one backward that keep the
    chain's float32 values in VMEM and save only the projection, at any
    other shape XLA's fusions).  Everything but the core runs under the
    device scope ``accl.attn::kda_proj``, the core (from normalised q, k,
    v, the log-decay and beta to ``o``: ``ops.kda.kda_chunked``) under
    ``accl.attn::kda``.

    Under ``cfg.remat`` the block keeps the five bf16 projections the
    chains read (``h wq``, ``h wk``, ``h wv``, the decay gate's and the
    output gate's, the FINAL product where a gate goes through a rank:
    ``KEPT_UNDER_REMAT``; 5 x B T d_inner x 2 bytes, 671,088,640 a layer at
    2 x 8,192 x 4,096 or 1 x 8,192 x 8,192): the backward replays the
    chains and the core from them and multiplies none of them out twice.
    Nothing else is named: ``o`` and the core's saved set (1.07 GB a layer)
    do not fit six layers, ``wo``'s product and beta's are replayed.

    A tree with ``wa`` (``DeltaAttention.head_decay``: Gated DeltaNet) has
    ONE log-decay a head a token, ``g`` (B, H, T, 1), from a product of H
    columns that is replayed like beta's (four projections are kept: 8,192 x
    17,280 x 2 bytes at heads of 96 / 192); v, the output gate and ``wo``'s
    rows are as wide as ``wv`` says, and ``kda["out_gate"]`` ``"silu"`` gates
    the normed output by a SiLU.  The core then runs ``ops.kda``'s padded
    path and the chains their XLA forms (heads of 96 and 192 are no whole
    lanes)."""
    from ..ops.kda import conv_in, decay_in, gated_out, kda_chunked

    H = n_heads_local
    f32 = jnp.float32
    with device_scope("accl.attn::kda_proj"):
        proj = lambda w: kept_under_remat(h @ lp[w])
        q = conv_in(proj("wq"), lp["conv_q"], H, unit=True,
                    scale=(lp["wq"].shape[1] // H) ** -0.5)
        k = conv_in(proj("wk"), lp["conv_k"], H, unit=True)
        v = conv_in(proj("wv"), lp["conv_v"], H, unit=False)
        # a gate through a rank keeps its FINAL product
        through = lambda w: kept_under_remat(
            h @ lp[w] if w in lp else (h @ lp[w + "_a"]) @ lp[w + "_b"]
        )
        bound = kda["lower_bound"]
        # a decay a head (``wa``, H columns): one log-decay a head a token,
        # (B, H, T, 1), a product too small to keep
        gate = h @ lp["wa"] if "wa" in lp else through("wf")
        g = decay_in(gate, lp["dt_bias"], lp["a_log"], bound)
        beta = jax.nn.sigmoid((h @ lp["wbeta"]).astype(f32)).transpose(0, 2, 1)
        if kda.get("beta_scale", 1.0) != 1.0:
            beta = beta * kda["beta_scale"]
    with device_scope("accl.attn::kda"):
        # (B, H, T, dv) f32; without a bound, the split by halving
        o = kda_chunked(q, k, v, g, beta, safe=bound is None)
    with device_scope("accl.attn::kda_proj"):
        o = gated_out(o, through("wg"), lp["o_norm"], kda["eps"], h.dtype,
                      silu=kda.get("out_gate") == "silu")
        return o @ lp["wo"]


def _mamba2_partial(h, lp, mamba):
    """The Mamba-2 mixer (:class:`Mamba2`) on a full-sequence activation,
    heads and groups column-parallel: the row-parallel PARTIAL output.  The
    sizes are the tree's but what no shape says, which ``mamba`` carries:
    a head's width, the state's, the chunk and the norm's ``eps``.  The
    matmuls take the activations' type; the convolution, SiLU, softplus,
    the core, the gate and the grouped norm are float32.  Everything but
    the core runs under the device scope ``accl.attn::mamba_proj``: the
    matmuls, ``dt``'s softplus and the two float32 chains, ``ops.ssd``'s
    ``conv_silu`` (x, B and C) and ``gated_group_norm``, each of which
    picks from the shapes the Mosaic kernels of ``ops/pallas/
    mamba_mixer.py`` (``mamba_in_fwd`` / ``mamba_in_bwd``, ``mamba_out_fwd``
    / ``mamba_out_bwd``: one pass over HBM a chain, forward and backward)
    or XLA's fusions.  The core (from x, B, C and dt to y, all TOKEN-MAJOR,
    as the convolutions leave them and the norm takes them:
    ``ops.ssd.ssd_mixer``, which picks from the shapes the Mosaic kernels
    ``ssd_fwd`` / ``ssd_bwd`` that keep a chunk's decay squares and the
    running state in VMEM, or the XLA form round its head-major
    transposes) runs under ``accl.attn::ssd``.

    Under ``cfg.remat`` the block keeps the five bf16 projections (``h
    wz``, ``h wx``, ``h wb``, ``h wc``, ``h wdt``: ``KEPT_UNDER_REMAT``;
    B T (2 d_inner + 2 G N + H) x 2 bytes, 304,087,040 a block at 8,192 x
    18,560): the backward replays the chains and the core from them and
    multiplies none of them out twice; ``wo``'s product is replayed."""
    from ..ops.ssd import conv_silu, gated_group_norm, ssd_mixer

    N = mamba["state"]
    G = lp["wb"].shape[1] // N
    f32 = jnp.float32
    with device_scope("accl.attn::mamba_proj"):
        proj = lambda w: kept_under_remat(h @ lp[w])
        z = proj("wz")
        x = conv_silu(proj("wx"), lp["conv_x"], lp["bias_x"])
        b = conv_silu(proj("wb"), lp["conv_b"], lp["bias_b"])
        c = conv_silu(proj("wc"), lp["conv_c"], lp["bias_c"])
        dt = jax.nn.softplus(
            proj("wdt").astype(f32) + lp["dt_bias"].astype(f32)
        )                                                 # (B, T, H)
        a = -jnp.exp(lp["a_log"].astype(f32))
    with device_scope("accl.attn::ssd"):
        y = ssd_mixer(x, b, c, dt, a, lp["d_skip"], G, mamba["chunk"])
    with device_scope("accl.attn::mamba_proj"):
        y = gated_group_norm(y, z, lp["y_norm"], G, mamba["eps"], h.dtype)
        return y @ lp["wo"]


def _attn_partial(h, lp, n_heads_local, attn_impl="naive", causal=True,
                  rope_base=None, positions=None, attention_fn=None,
                  tp_axis=None, window=None, head_norm=False, latent=None,
                  qk_eps=1e-5, block_diffusion=None, kda=None, mamba=None,
                  geometry=None):
    """Column-parallel attention on a full-sequence activation: returns
    the row-parallel PARTIAL output (pre-reduction) and the (k, v) head
    tensors (B, Hkv_local, T, hd) for KV-cache prefill.  The kv head
    count comes from the wk shard's width (GQA: fewer kv heads than q
    heads; every attention lowering groups q heads onto kv head h//G).
    With ``rope_base`` set, q/k rotate by absolute position BEFORE
    attention (and before the kv tensors are returned, so the prefill
    cache stores rotated keys — decode appends consistently).

    ``positions`` overrides the rope positions (context parallelism
    passes its shard's global token positions); ``attention_fn``
    replaces the dense :func:`_attention` lowering (context parallelism
    passes the striped ring).  ``tp_axis`` is for :func:`_qk_norm`.

    What the layer's tree holds picks the rest: under ``head_norm`` the
    ``q_norm`` / ``k_norm`` scales are one head wide and norm each head
    AFTER the split (:func:`_qk_norm` is the whole projection's);
    a ``wg`` gates the attention output, ``attn * sigmoid(h wg)``, before
    ``wo``; a ``wkv_a`` is the latent mixer's (:func:`_latent_attn_partial`,
    which has no cache to return yet), a ``d_skip`` the Mamba-2 mixer's
    (:func:`_mamba2_partial`) and an ``a_log`` without one the KDA mixer's
    (:func:`_kda_partial`; both likewise).  ``window`` is the sliding window,
    run under the device scope ``accl.attn::window`` (full attention stays
    ``accl.attn::core``; where the stack has KDA layers, ``kda`` given, or
    the layer a head geometry, what is round the core runs under
    ``accl.attn::gqa_proj``).  A ``sink`` leaf (a float32 scalar a query
    head) stands in every row's softmax as a key without a value; v's width
    is ``wv``'s over the K/V heads that ``wk``'s gives.  ``geometry``
    (:class:`HeadGeometry`) is what the shapes cannot say: of a head's
    columns the FIRST ``rope_dim`` rotate and go to the core as the scores'
    second part (``q_rope`` / ``k_rope`` on the K/V heads), the others carry
    no position; v is times ``v_scale``.  ``qk_eps`` is QK-norm's epsilon.
    ``block_diffusion=(L, B)``: ``h`` is ``[noisy ; clean]``, ``2 L`` rows
    that rotate at positions ``0..L`` twice, and the core runs under that
    layout in the device scope ``accl.attn::blockdiff``.

    Under ``cfg.remat`` the block keeps q, k and v AS THE CORE TAKES THEM
    (``KEPT_UNDER_REMAT``: head-major, after the norms, the value scale, the
    rope and the split, ``q_rope`` / ``k_rope`` beside them where a head
    splits) and, where the core is the flash kernels, the core's ``o`` and
    ``lse`` (named in its forward rule): all that ``flash_bwd`` reads, so
    the backward's replay runs none of the three products, no transpose, no
    rope and no ``flash_fwd``; ``wo``'s product and a gate's are replayed."""
    if "d_skip" in lp:
        return _mamba2_partial(h, lp, mamba), None
    if "a_log" in lp:
        return _kda_partial(h, lp, n_heads_local, kda), None
    if "wkv_a" in lp:
        return _latent_attn_partial(
            h, lp, n_heads_local, attn_impl, causal, rope_base, window, latent
        ), None
    B, T, _ = h.shape
    # a softmax layer BESIDE KDA layers (``kda`` is the stack's: a gated
    # grouped-query layer without position among linear-attention ones)
    # runs what is round its core under a device scope of its own, as the
    # KDA layers do; every other stack's program is what it was
    proj_scope = (
        device_scope("accl.attn::gqa_proj")
        if kda is not None or geometry is not None
        else contextlib.nullcontext()
    )
    with proj_scope:
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]  # column-parallel
    # a head geometry's own work between the projections and the core (the
    # value scale, the split into the part that rotates and the part without
    # position, the sink's cast) goes under the same scope; without one the
    # lines below trace outside any scope, as they always did
    with proj_scope if geometry is not None else contextlib.nullcontext():
        if not head_norm:
            q, k = _qk_norm(q, k, lp, tp_axis, qk_eps)
        hd = q.shape[-1] // n_heads_local
        n_kv_local = k.shape[-1] // hd
        # v's heads are as wide as ``wv`` makes them
        heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
        q, k, v = (
            heads(q, n_heads_local), heads(k, n_kv_local), heads(v, n_kv_local)
        )
        if geometry is not None and geometry.v_scale != 1.0:
            v = v * geometry.v_scale
        # the scores' second part and the sink, where the layer has them
        more = {}
        if "sink" in lp:
            # inside a shard_map: the sink varying over every axis q varies
            # over (the batch's too), so that its cotangent is summed over
            # them by the cast's transpose, as the latent mixer's one rope
            # key is
            more["sink"] = sink = lp["sink"]
            if missing := tuple(jax.typeof(q).vma - jax.typeof(sink).vma):
                more["sink"] = jax.lax.pcast(sink, missing, to="varying")
        if head_norm and "q_norm" in lp:
            q = _rmsnorm(q, lp["q_norm"], eps=qk_eps)
            k = _rmsnorm(k, lp["k_norm"], eps=qk_eps)
        if rope_base is not None:
            if block_diffusion is not None:
                pos = jnp.tile(jnp.arange(block_diffusion[0]), 2)
            else:
                pos = jnp.arange(T) if positions is None else positions
            dr = hd if geometry is None else geometry.rope_dim or hd
            tables = _rope_tables(pos, dr // 2, rope_base)
            if dr == hd:
                q = _rope_rotate(q, tables)
                k = _rope_rotate(k, tables)
            else:
                rotated = lambda t: kept_under_remat(
                    _rope_rotate(t[..., :dr], tables)
                )
                more.update(q_rope=rotated(q), k_rope=rotated(k))
                q, k = q[..., dr:], k[..., dr:]
        # what the core's backward reads of the mixer's work, AS THE CORE
        # TAKES IT (the two rotated parts above with it): a rematerialised
        # block keeps these, so that its backward runs neither the products
        # nor the relayouts above a second time
        q, k, v = (kept_under_remat(t) for t in (q, k, v))
    if block_diffusion is not None:
        with device_scope("accl.attn::blockdiff"):
            attn = _attention(
                q, k, v, impl=attn_impl, block_diffusion=block_diffusion
            )
    elif window is None:
        with device_scope("accl.attn::core"):
            if attention_fn is not None:
                attn = attention_fn(q, k, v)
            else:
                attn = _attention(
                    q, k, v, impl=attn_impl, causal=causal, **more
                )
    else:
        with device_scope("accl.attn::window"):
            attn = _attention(
                q, k, v, impl=attn_impl, causal=causal, window=window, **more
            )
    with proj_scope:
        attn = attn.transpose(0, 2, 1, 3).reshape(B, T, -1)
        if "wg" in lp:
            gate = jax.nn.sigmoid((h @ lp["wg"]).astype(jnp.float32))
            attn = attn * gate.astype(attn.dtype)
        return attn @ lp["wo"], (k, v)


def _block(x, lp, n_heads_local, tp_axis, return_kv=False,
           attn_impl="naive", causal=True, rope_base=None,
           ep_axis=None, moe_cfg=None, with_aux=False,
           reduce_fn=None, fanout_fn=None, norm=_layernorm,
           window=None, head_norm=False, latent=None, qk_eps=1e-5,
           block_diffusion=None, kda=None, mamba=None, relu2=False,
           geometry=None):
    """One transformer block on tp-sharded weights.  ``lp['wqkv']`` etc. are
    the *local shards*; the tp-allreduce after each row-parallel matmul is
    the reference's fused-allreduce hot path in model form.

    What the tree holds says which sub-layers the block has: a mixer where
    it has an ``ln1``, an FFN where it has an ``ln2`` (``LayerKind``: a
    block of ONE sub-layer is ``x + f(norm x)``, one norm, one residual),
    or their ``_post`` twins alone (``post_norm="only"``: ``x + norm(f(x))``).

    ``return_kv=True`` additionally returns the (k, v) head tensors
    (B, H_local, T, hd) — the prefill path of the KV-cache decode.
    ``with_aux=True`` (MoE training) returns ``(out, aux)`` with the
    layer's router health terms.  ``reduce_fn`` overrides the
    row-parallel tp reduction (the composed 1F1B backward injects a
    custom_vjp psum whose transpose is identity — correct for a
    replicated cotangent — because its hand-written backward runs
    without the vma machinery that normally places that transpose)."""
    if reduce_fn is None:
        reduce_fn = lambda v, ax: collectives.allreduce(
            v, ax, ReduceFunction.SUM
        )
    kv = None
    if "ln1" in lp or "ln1_post" in lp:
        # ``post_norm="only"``: no norm before the mixer
        h = norm(x, lp["ln1"]) if "ln1" in lp else x
        if fanout_fn is not None and tp_axis is not None:
            # replicated h fans out into the tp-sharded q/k/v matmuls: the
            # manual-backward mode marks the fan-out so its transpose (a tp
            # psum of the branch cotangents) lands here and nowhere else
            h = fanout_fn(h, tp_axis)
        partial_o, kv = _attn_partial(
            h, lp, n_heads_local, attn_impl, causal, rope_base,
            tp_axis=tp_axis, window=window, head_norm=head_norm,
            latent=latent, qk_eps=qk_eps, block_diffusion=block_diffusion,
            kda=kda, mamba=mamba, geometry=geometry,
        )
        if tp_axis is not None:
            partial_o = reduce_fn(partial_o, tp_axis)
        if "ln1_post" in lp:
            # the tree's post-norm: the half's output normed once more
            partial_o = norm(partial_o, lp["ln1_post"])
        x = x + partial_o
    if "ln2" not in lp and "ln2_post" not in lp:
        out = (x, None) if with_aux else x    # the mixer alone
    else:
        out = _mlp(x, lp, tp_axis, ep_axis, moe_cfg, with_aux,
                   reduce_fn=reduce_fn, fanout_fn=fanout_fn, norm=norm,
                   relu2=relu2)
    return (out, kv) if return_kv else out


def _cp_block_k(t_local: int, attn_impl: str):
    """Within-hop sub-tiling for the ring fold, honoring the config's
    attention memory contract: "naive" folds whole visiting blocks
    ((Tq, T_local) score tiles); "blockwise"/"flash" always sub-tile
    (the (Tq, block_k) tile is those lowerings' whole point); "auto"
    sub-tiles at/above the measured fused crossover, like the dense
    auto lowering."""
    if attn_impl == "naive":
        return None
    if attn_impl == "auto" and t_local < _AUTO_FUSED_MIN_T:
        return None
    for b in (512, 256, 128, 64):
        if t_local % b == 0 and b < t_local:
            return b
    return None  # tiny/ragged shard: whole-hop fold is already small


def _block_cp(x, lp, n_heads, cp_axis, rope_base=None, attn_impl="auto",
              ep_axis=None, moe_cfg=None, with_aux=False, norm=_layernorm,
              qk_eps=1e-5):
    """Context-parallel block: ``x`` is (B, T/cp, D), this rank's STRIPED
    sequence shard over ``cp_axis``; weights are full (replicated over
    the axis).  QKV/MLP matmuls are purely local; attention is striped
    causal ring attention — K/V blocks (unexpanded kv heads under GQA)
    rotate around the ring folding into the local online-softmax state —
    so nothing in the block ever materializes the full sequence.  Rope
    rotates by the shard's GLOBAL token positions; ``attn_impl`` maps to
    the fold's within-hop sub-tiling (:func:`_cp_block_k`).

    With an expert bank on the layer (MoE x cp — long-context MoE), the
    MLP half routes this rank's sequence shard through the expert
    dispatch all-to-all over ``ep_axis`` while the K/V ring turns over
    tp: the two communication patterns ride DIFFERENT mesh axes, which
    is exactly why the composition is legal (tp_axis stays None — under
    cp the experts, like every weight, are replicated over the ring)."""
    from .ring_attention import striped_attention

    positions = _cp_positions(x.shape[1], cp_axis)
    block_k = _cp_block_k(x.shape[1], attn_impl)
    ring = lambda q, k, v: striped_attention(
        q, k, v, cp_axis, causal=True, block_k=block_k
    )
    h = norm(x, lp["ln1"])
    o, _ = _attn_partial(
        h, lp, n_heads, rope_base=rope_base,
        positions=positions, attention_fn=ring, qk_eps=qk_eps,
    )
    x = x + o
    return _mlp(x, lp, None, ep_axis, moe_cfg, with_aux, norm=norm)


def _block_sp(x_sp, lp, n_heads_local, tp_axis, return_kv=False,
              attn_impl="naive", causal=True, rope_base=None,
              norm=_layernorm, qk_eps=1e-5):
    """Sequence-parallel block (Megatron-SP): ``x_sp`` is (B, T/tp, D),
    sequence-sharded over ``tp``.  All-gather restores the full sequence
    in front of each column-parallel matmul; the row-parallel reduction
    becomes a reduce-scatter back onto the sequence shards — the same
    wire bytes as _block's two allreduces (AR = RS + AG), with layernorm,
    residuals, and inter-block activations at 1/tp the memory.

    ``return_kv=True`` additionally returns the (k, v) head tensors —
    FULL-sequence per local head (B, H_local, T, hd), because attention
    inside the block already runs on the gathered sequence; this is the
    sequence-parallel prefill path of the KV-cache decode."""
    h = norm(x_sp, lp["ln1"])
    h_full = collectives.allgather(h, tp_axis, axis=1)
    partial_o, kv = _attn_partial(
        h_full, lp, n_heads_local, attn_impl, causal, rope_base,
        tp_axis=tp_axis, qk_eps=qk_eps,
    )
    o_sp = collectives.reduce_scatter(
        partial_o, tp_axis, tiled=True, axis=1
    )
    x_sp = x_sp + o_sp
    h = norm(x_sp, lp["ln2"])
    h_full = collectives.allgather(h, tp_axis, axis=1)
    partial_f = _ffn_hidden(h_full, lp) @ lp["w2"]
    f_sp = collectives.reduce_scatter(
        partial_f, tp_axis, tiled=True, axis=1
    )
    out = x_sp + f_sp
    return (out, kv) if return_kv else out


def _enter_block_layout(x, cfg, tp_axis, tp_size, return_kv=False,
                        causal=True):
    """Enter the block stack's activation layout and pick the block fn.

    Under Megatron-SP (``cfg.seq_parallel`` with a real tp axis) the
    sequence dim is sharded over tp — this rank keeps its T/tp slice and
    blocks run :func:`_block_sp`; under context parallelism ``x`` is
    ALREADY this rank's striped shard (the makers shard the tokens) and
    blocks run :func:`_block_cp`; otherwise activations stay replicated
    and blocks run :func:`_block`.  Shared by the training forward and
    the serving prefill so the two paths cannot diverge on the entry
    invariant.  Returns ``(x, block_fn, layout)`` with layout one of
    ``""`` (replicated), ``"sp"``, ``"cp"`` — truthy means x is
    sequence-sharded."""
    from jax import lax

    _check_axis_compat(cfg)
    if _cp_active(cfg, tp_axis):
        if return_kv:
            raise ValueError(
                "context_parallel has no serving path: decode with "
                "dataclasses.replace(cfg, context_parallel=False) — cp "
                "params are replicated over tp and re-shard directly"
            )
        if not causal:
            raise ValueError(
                "context_parallel is causal/decoder-only (the striped "
                "ring's load balance argument is the causal mask)"
            )
        cp_kw = dict(
            n_heads=cfg.n_heads, cp_axis=tp_axis,
            rope_base=cfg.rope_base if cfg.uses_rope() else None,
            attn_impl=cfg.attention, norm=_norm_fn(cfg),
        )
        if cfg.n_experts:
            cp_kw["ep_axis"] = cfg.moe_mesh_axis
            cp_kw["moe_cfg"] = cfg
            cp_kw["with_aux"] = True
        if cfg.qk_norm and cfg.norm_eps != 1e-5:
            cp_kw["qk_eps"] = cfg.norm_eps
        block = partial(_block_cp, **cp_kw)
        return x, block, "cp"
    heads_local = cfg.n_heads // tp_size
    if cfg.vocab_parallel and tp_size > 1 and cfg.vocab % tp_size:
        raise ValueError(
            f"vocab_parallel needs vocab ({cfg.vocab}) divisible by tp "
            f"({tp_size})"
        )
    if cfg.latent is not None and cfg.n_heads % tp_size:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must be divisible by tp ({tp_size}) "
            "so every chip owns whole heads of the latent mixer"
        )
    kv_counts = sorted({cfg.kv_heads(kind) for kind in cfg.pattern()})
    if tp_size > 1 and cfg.latent is None and any(
        n % tp_size for n in kv_counts
    ):
        raise ValueError(
            f"n_kv_heads ({', '.join(map(str, kv_counts))}) must be divisible "
            f"by tp ({tp_size}) so every chip owns whole kv heads"
        )
    sp = cfg.seq_parallel and tp_axis is not None and tp_size > 1
    kw = dict(
        n_heads_local=heads_local, tp_axis=tp_axis,
        attn_impl=cfg.attention, causal=causal,
        rope_base=cfg.rope_base if cfg.uses_rope() else None,
        norm=_norm_fn(cfg),
    )
    if return_kv:
        kw["return_kv"] = True
    if cfg.qk_norm == "head":
        kw["head_norm"] = True
    if cfg.qk_norm and cfg.norm_eps != 1e-5:
        kw["qk_eps"] = cfg.norm_eps
    if cfg.diffusion is not None:
        if x.shape[1] % 2 or (x.shape[1] // 2) % cfg.diffusion.block:
            raise ValueError(
                f"block diffusion runs [noisy ; clean], 2 L rows of whole "
                f"blocks of {cfg.diffusion.block}; got {x.shape[1]} rows"
            )
        kw["block_diffusion"] = (x.shape[1] // 2, cfg.diffusion.block)
    if cfg.latent is not None:
        kw["latent"] = {
            "scale": cfg.attn_scale(), "inv_freq": cfg.rope_inv_freq(),
            "table_scale": cfg.rope_table_scale(), "eps": cfg.norm_eps,
        }
    if cfg.kda is not None:
        kw["kda"] = {
            "lower_bound": cfg.kda.lower_bound, "eps": cfg.norm_eps,
            "beta_scale": cfg.kda.beta_scale, "out_gate": cfg.kda.out_gate,
        }
    if cfg.mamba is not None:
        m = cfg.mamba
        if tp_size > 1 and m.groups % tp_size:
            raise ValueError(
                f"the Mamba-2 mixer's groups ({m.groups}) must be divisible "
                f"by tp ({tp_size}) so every chip owns whole groups of heads"
            )
        kw["mamba"] = {
            "head_dim": m.head_dim, "state": m.state, "chunk": m.chunk,
            "eps": cfg.norm_eps,
        }
    if cfg.ffn == "relu2":
        kw["relu2"] = True
    if cfg.n_experts:
        # expert parallelism rides cfg.moe_mesh_axis ("dp" welded, or a
        # dedicated "ep"): the sharded makers always run over a mesh
        # carrying it, so a live tp_axis implies the axis exists;
        # single-device calls keep every expert local
        kw["ep_axis"] = cfg.moe_mesh_axis if tp_axis is not None else None
        kw["moe_cfg"] = cfg
        kw["with_aux"] = not return_kv  # serving paths skip router aux
    if not sp:
        return x, partial(_block, **kw), ""
    T = x.shape[1]
    if T % tp_size:
        raise ValueError(
            f"seq_parallel needs sequence length ({T}) divisible by "
            f"tp ({tp_size})"
        )
    # enter the sequence-sharded regime: this rank keeps its T/tp slice
    Tl = T // tp_size
    idx = lax.axis_index(tp_axis)
    x = lax.dynamic_slice_in_dim(x, idx * Tl, Tl, axis=1)
    return x, partial(_block_sp, **kw), "sp"


def _layer_blocks(block, cfg):
    """``block`` for each layer of the pattern: the layout's block as it
    is where every layer is alike, and with the layer's own window and
    rotation where ``cfg.layers`` gives them.  Under ``cfg.remat`` each is
    rematerialised on the backward pass but for what its mixer, or its
    attention core's forward rule, names ``KEPT_UNDER_REMAT``: the KDA and
    Mamba-2 mixers' bf16 input projections (671 and 304 MB a layer at the
    train cells' widths: what their chains save as their residual anyway),
    a softmax mixer's q, k, v as its core takes them and the flash core's
    ``o`` and ``lse`` (0.38 GB a layer at MiMo-V2.5's widths: all that
    ``flash_bwd`` reads), so that no block multiplies those out or runs
    ``flash_fwd`` twice; a block that names nothing is replayed whole.
    One static rule, no budget: these fit every layer of every cell
    (``memory_analysis`` of the five ``remat`` cells' full steps, PERF.md
    section 5), which nothing larger (the KDA core's saved set) does."""
    remat = partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names(KEPT_UNDER_REMAT),
    )
    if cfg.layers is None:
        if cfg.remat:
            block = remat(block)
        return [block] * cfg.n_layers
    blocks = [
        partial(
            block, window=kind.window,
            rope_base=(kind.rope_base or cfg.rope_base) if kind.rope else None,
            geometry=kind.heads,
        )
        for kind in cfg.layers
    ]
    return [remat(b) for b in blocks] if cfg.remat else blocks


def _final_hidden(params, tokens, cfg, tp_axis=None, tp_size=1):
    """Embed -> blocks -> final layernorm (under ``cfg.diffusion``
    ``tokens`` are ``[noisy ; clean]`` and ``x`` the noisy half's rows).
    Returns ``(x, layout, aux)``:
    ``layout`` flags how ``x`` is sequence-sharded ("" / "sp" / "cp");
    ``aux`` is None for dense FFNs or the MoE router's terms: the health
    terms summed over layers ({"load_balance", "router_z"}, shared by
    forward() and the fused loss; the three ``balance_*`` where the config
    weighs them) and its counters an MoE layer
    ("expert_tokens" (L, E) over all the router's experts, "dropped"
    (L,), where the chip holds a share of them "held_entries" (L,), under
    group-limited routing "group_tokens" (L, n_group);
    the router probe and the expert bias's rule read them, elsewhere
    they are dead code)."""
    x = _embed_tokens(params, tokens, cfg, tp_axis)
    x, block, sp = _enter_block_layout(x, cfg, tp_axis, tp_size)
    blocks = _layer_blocks(block, cfg)
    norm = _norm_fn(cfg)
    if cfg.diffusion is not None:
        # [noisy ; clean] went through the layers; the final norm (and
        # the head after it) see the noisy half only
        final = lambda x: norm(x[:, : x.shape[1] // 2], params["ln_f"])
    else:
        final = lambda x: norm(x, params["ln_f"])
    if not cfg.n_experts:
        for blk, lp in zip(blocks, params["layers"]):
            x = blk(x, lp)
        return final(x), sp, None
    lb = jnp.zeros((), jnp.float32)
    rz = jnp.zeros((), jnp.float32)
    balance = {}
    counts, dropped, held, groups = [], [], [], []
    for blk, lp in zip(blocks, params["layers"]):
        x, aux = blk(x, lp)
        if aux is None:
            continue  # a dense layer of the pattern
        lb = lb + aux["load_balance"]
        rz = rz + aux["router_z"]
        for name in _BALANCE:
            if name in aux:
                balance[name] = balance.get(name, 0.0) + aux[name]
        counts.append(aux["expert_tokens"])
        dropped.append(aux["dropped"])
        if "held_entries" in aux:
            held.append(aux["held_entries"])
        if "group_tokens" in aux:
            groups.append(aux["group_tokens"])
    aux = {
        "load_balance": lb, "router_z": rz, **balance,
        "expert_tokens": jnp.stack(counts), "dropped": jnp.stack(dropped),
    }
    if held:
        aux["held_entries"] = jnp.stack(held)
    if groups:
        aux["group_tokens"] = jnp.stack(groups)
    return final(x), sp, aux


def forward(params, tokens, cfg: TransformerConfig, tp_axis=None, tp_size=1):
    """Logits for a token batch.  With tp_axis set, runs on weight shards
    inside shard_map; without, a plain single-device forward.  Always
    returns the FULL-vocab logits (vocab-parallel shards are gathered —
    use :func:`loss_fn` for the fused form that never materializes
    them).  Under ``cfg.diffusion`` ``tokens`` are ``[noisy ; clean]``
    (B, 2 L) and the logits the noisy half's (B, L, vocab).

    Exception: under context parallelism the return value is this
    rank's striped (B, T/cp, vocab) logits shard — the makers'
    ``out_specs`` reassemble the sequence with zero inner wire instead
    of replicating full-sequence logits on every ring rank."""
    x, sp, _ = _final_hidden(params, tokens, cfg, tp_axis, tp_size)
    if sp == "cp":
        return _lm_logits(x, params, cfg, tp_axis)
    if sp and _vp_active(cfg, tp_axis):
        # vocab-parallel head under SP: gather the sequence FIRST (every
        # rank needs every row to score its vocab shard — the Megatron
        # layout; gathering hidden is vocab/d_model cheaper than logits)
        x = collectives.allgather_invariant(x, tp_axis, axis=1)
        sp = False
    logits = _lm_logits(x, params, cfg, tp_axis)
    if sp:
        # leave the sharded regime: gather the sequence back (invariant
        # form — the caller may claim tp-replicated outputs)
        logits = collectives.allgather_invariant(
            logits, tp_axis, axis=1
        )
    return logits


def _diffusion_loss(params, tokens, key, cfg, tp_axis, tp_size, with_aux,
                    shard=None):
    """The block-diffusion objective (:class:`BlockDiffusion`): ``tokens``
    (S, L) are noised from ``key`` inside the program
    (:func:`diffusion_noise`), ``[noisy ; clean]`` runs through the layers
    under the block layout, the final norm, the head and the loss see the
    noisy half only (the clean half's logits are never made; device scope
    ``accl.loss::diffusion``), and a masked position's NLL of the id AT
    the position weighs ``1 / t``: ``sum / (S L)``, plus the router's
    penalty where weighed.  ``with_aux`` adds the router's counters and
    ``masked_tokens``; ``shard`` is :func:`diffusion_noise`'s."""
    noisy, masked, t = diffusion_noise(key, tokens, cfg.diffusion, shard)
    x, _, aux = _final_hidden(
        params, jnp.concatenate([noisy, tokens], axis=1), cfg, tp_axis,
        tp_size,
    )
    with device_scope("accl.loss::diffusion"):
        nll = _token_nll(_lm_logits(x, params, cfg, tp_axis), tokens)
        loss = jnp.sum(jnp.where(masked, nll / t, 0.0)) / tokens.size
    if aux is not None and (
        cfg.moe_aux_weight or cfg.moe_router_z_weight
        or any(cfg.moe_balance_weights)
    ):
        loss = loss + _moe_penalty(cfg, aux)
    if not with_aux:
        return loss
    return loss, {
        **(aux or {}), "masked_tokens": jnp.sum(masked, dtype=jnp.int32),
    }


def loss_fn(params, tokens, targets, cfg, tp_axis=None, tp_size=1,
            with_aux=False):
    """Mean next-token NLL (``with_aux``: and the MoE router's terms and
    counters of :func:`_final_hidden`, on the plain path).  Under ``cfg.vocab_parallel`` (with a tp
    axis) the cross-entropy is computed FUSED on the vocab-sharded
    logits — per-rank max/sum-exp/target-logit combined with tp
    collectives (the Megatron vocab-parallel loss) — so the full
    (B, T, vocab) logits never exist; under seq-parallel the hidden is
    gathered out of the SP regime first (the Megatron layout — every
    rank scores every row against its vocab shard).

    Under ``cfg.context_parallel`` ``tokens``/``targets`` are this
    rank's STRIPED sequence shards: the cross-entropy stays local
    ((B, T/cp, vocab) logits only) and the ring-mean of the equal-sized
    shard means is the global mean — full-sequence activations never
    exist on any rank.

    Under ``cfg.diffusion`` (:class:`BlockDiffusion`) ``targets`` is the
    noise KEY: the loss is :func:`_diffusion_loss`."""
    _check_axis_compat(cfg)
    if cfg.diffusion is not None:
        return _diffusion_loss(
            params, tokens, targets, cfg, tp_axis, tp_size, with_aux
        )
    if _cp_active(cfg, tp_axis):
        x, _, aux = _final_hidden(params, tokens, cfg, tp_axis, tp_size)
        z = _lm_logits(x, params, cfg, tp_axis, gather=False)
        nll = _token_nll(z, targets)
        local = nll.mean()
        if aux is not None:
            # MoE x cp: the router health terms were computed over this
            # rank's striped shard; the ring mean below averages them
            # across the sequence ring together with the nll (the same
            # per-rank-tokens approximation the dp average makes)
            local = local + _moe_penalty(cfg, aux)
        return (
            collectives.allreduce(local, tp_axis, ReduceFunction.SUM)
            / tp_size
        )
    if not _vp_active(cfg, tp_axis):
        if cfg.n_experts:
            # one shared trunk pass: hidden AND the router aux terms
            # (moe rejects sp/cp above, so x is the full sequence)
            x, _, aux = _final_hidden(params, tokens, cfg, tp_axis, tp_size)
            logits = _lm_logits(x, params, cfg, tp_axis)
            loss = _token_nll(logits, targets).mean()
            if (cfg.moe_aux_weight or cfg.moe_router_z_weight
                    or any(cfg.moe_balance_weights)):
                loss = loss + _moe_penalty(cfg, aux)
            return (loss, aux) if with_aux else loss
        logits = forward(params, tokens, cfg, tp_axis, tp_size)
        return _token_nll(logits, targets).mean()

    from jax import lax

    x, sp, moe_aux = _final_hidden(params, tokens, cfg, tp_axis, tp_size)
    if sp:
        # exit sequence parallelism BEFORE the vocab-parallel head (the
        # Megatron layout): every rank needs every row's hidden state to
        # score its vocab shard.  Gathering the (B, T, d_model) hidden
        # costs vocab/d_model LESS wire+memory than gathering logits —
        # the saving the fused loss exists for.
        x = collectives.allgather_invariant(x, tp_axis, axis=1)
    z = _lm_logits(x, params, cfg, tp_axis, gather=False)
    # f32 softmax statistics (bf16 logits overflow exp quickly)
    z = z.astype(jnp.float32)
    tgt = targets
    # stable logsumexp across the vocab shards: global max, then psum of
    # the local exp-sums.  The max is a gather of the tp per-shard maxes
    # rather than a pmax: under value_and_grad the pmax primitive has no
    # differentiation rule (even stop_gradient'ed, linearization still
    # traverses it), and the INVARIANT gather form keeps the loss
    # tp-replicated for shard_map's checker
    zmax = lax.stop_gradient(
        collectives.allgather_invariant(
            z.max(axis=-1), tp_axis, axis=0, tiled=False
        ).max(axis=0)
    )
    sumexp = collectives.allreduce(
        jnp.exp(z - zmax[..., None]).sum(axis=-1),
        tp_axis,
        ReduceFunction.SUM,
    )
    # the target's logit: owned by exactly one vocab shard
    local_t, mine = _vp_local_ids(tgt, z.shape[-1], cfg.vocab, tp_axis)
    zt_local = jnp.take_along_axis(
        z, local_t[..., None], axis=-1
    ).squeeze(-1)
    zt = collectives.allreduce(
        jnp.where(mine, zt_local, 0.0), tp_axis, ReduceFunction.SUM
    )
    nll = jnp.log(sumexp) + zmax - zt
    loss = nll.mean()
    if moe_aux is not None:
        loss = loss + _moe_penalty(cfg, moe_aux)
    return loss


# ---------------------------------------------------------------------------
# KV-cache decode (autoregressive generation)
# ---------------------------------------------------------------------------


def _reject_unservable(cfg) -> None:
    """prefill/generate compute the plain block (one kind of layer, a
    cache of every earlier key): a configuration beyond it is refused
    here, by name, and not served wrongly."""
    if not cfg.plain():
        raise ValueError(
            "prefill/generate serve TransformerConfig.plain() only: this "
            "configuration has a layer pattern, a window, a head_dim of "
            "its own, an attention gate, per-head QK-norm, post-norms, a "
            "scaled embedding, a sigmoid router, a shared expert, a "
            "held share of the experts, K/V heads, a rope base, a sink or a "
            "head geometry of a layer kind's own (LayerKind.kv_heads, "
            ".rope_base, .sink, .heads: the cache would be a window's keys "
            "beside every key, on two head counts), a latent mixer (MLA: its "
            "cache is "
            "the latent and the rope key, not k and v), a KDA mixer or a "
            "Mamba-2 mixer (the cache is a recurrent state and a "
            "convolution's last inputs), a block of one sub-layer, a latent "
            "expert bank, relu2, "
            "grouped top-k, "
            "balance losses or block diffusion (generation there denoises "
            "a block of tokens at a time over several passes), and the "
            "decode path has no cache "
            "layout or block for those yet (train and forward do)"
        )


def reject_latent(cfg, where: str) -> None:
    """The paths beside train and forward refuse a latent mixer, a KDA
    mixer, a Mamba-2 mixer, a block of one sub-layer and the
    block-diffusion objective, by name."""
    if cfg.latent is not None:
        raise ValueError(
            "the latent mixer (MLA, TransformerConfig.latent) is supported "
            f"on the decoder's train and forward paths only, not {where}"
        )
    if cfg.kda is not None:
        raise ValueError(
            "the KDA mixer (linear attention, TransformerConfig.kda: its "
            "state is no cache yet, under either gate, at either rank of "
            "the gate projections, with a decay a channel or a head) is "
            f"supported on the decoder's train and forward paths only, not "
            f"{where}"
        )
    if cfg.mamba is not None:
        raise ValueError(
            "the Mamba-2 mixer (a state-space layer, TransformerConfig.mamba) "
            "is supported on the decoder's train and forward paths only, not "
            f"{where}"
        )
    if any("none" in (k.mixer, k.ffn) for k in cfg.layers or ()):
        raise ValueError(
            "a block of one sub-layer (LayerKind.mixer or .ffn 'none') is "
            "supported on the decoder's train and forward paths only, not "
            f"{where}"
        )
    if cfg.diffusion is not None:
        raise ValueError(
            "block diffusion (TransformerConfig.diffusion) is supported on "
            f"the decoder's train and forward paths only, not {where}"
        )
    if any(
        k.sink or (k.kv_heads, k.rope_base, k.heads) != (None,) * 3
        for k in cfg.layers or ()
    ):
        raise ValueError(
            "attention heads of a layer kind's own (LayerKind.kv_heads, "
            ".rope_base), a sink in the softmax (LayerKind.sink) and a head "
            "geometry (LayerKind.heads: partial rotary, a v width of its "
            "own, a value scale) are supported on the decoder's train and "
            f"forward paths only, not {where}"
        )


def _block_decode(x_t, lp, cache_k, cache_v, pos, n_heads_local, tp_axis,
                  rope_tables=None, ep_axis=None, moe_cfg=None,
                  norm=_layernorm, qk_eps=1e-5):
    """One block for a single decode position: write this step's k/v into
    the cache at ``pos`` (dynamic_update_slice keeps shapes static under
    jit/scan), attend over positions <= pos, same tp collectives as the
    training block.  Returns (x_out, cache_k, cache_v).

    The cache is (B, Hkv_local, S, hd) — under GQA it carries only the kv
    heads, the factor-G serving-memory saving that motivates GQA; query
    heads group onto kv head h // G in the einsum."""
    B, _, D = x_t.shape
    h = norm(x_t, lp["ln1"])
    q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]
    q, k = _qk_norm(q, k, lp, tp_axis, qk_eps)
    hd = q.shape[-1] // n_heads_local
    n_kv_local = k.shape[-1] // hd
    rs = lambda t, n: t.reshape(B, 1, n, hd).transpose(0, 2, 1, 3)
    q = rs(q, n_heads_local)  # (B, Hl, 1, hd)
    k, v = rs(k, n_kv_local), rs(v, n_kv_local)  # (B, Hkv_l, 1, hd)
    if rope_tables is not None:
        # rotate this step's q/k at the dynamic cursor; cached keys were
        # rotated at THEIR positions (prefill/prior steps), so scores
        # depend only on relative offsets — rope's defining property
        q = _rope_rotate(q, rope_tables)
        k = _rope_rotate(k, rope_tables)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, 0, pos, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, 0, pos, 0))
    S = cache_k.shape[2]
    qg = q.reshape(B, n_kv_local, n_heads_local // n_kv_local, 1, hd)
    # f32 scores/softmax, value-dtype matmuls (see _attention): a strong
    # NumPy sqrt scalar here once promoted the whole residual stream to
    # f32 and broke the bf16 cache update (dynamic_update_slice dtype
    # mismatch on the next layer)
    scores = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, cache_k,
        preferred_element_type=jnp.float32,
    ) * (1.0 / math.sqrt(hd))
    mask = jnp.arange(S)[None, None, None, None, :] <= pos
    scores = jnp.where(mask, scores, -1e30)
    attn = jnp.einsum(
        "bhgqk,bhkd->bhgqd",
        jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype),
        cache_v,
    )
    attn = attn.reshape(B, n_heads_local, 1, hd)
    attn = attn.transpose(0, 2, 1, 3).reshape(B, 1, -1)
    partial_o = attn @ lp["wo"]
    if tp_axis is not None:
        partial_o = collectives.allreduce(partial_o, tp_axis, ReduceFunction.SUM)
    x = x_t + partial_o
    return (
        _mlp(x, lp, tp_axis, ep_axis, moe_cfg, moe_no_drop=True, norm=norm),
        cache_k,
        cache_v,
    )


def prefill(
    params,
    tokens,
    cfg: TransformerConfig,
    tp_axis=None,
    tp_size=1,
    cache_len: Optional[int] = None,
):
    """Run the prompt through the model once, building the KV cache.
    Returns (last-position logits, caches) where caches is a list of
    (k, v) arrays (B, Hkv_local, cache_len, hd) — kv heads only under
    GQA, the factor-G cache saving.  ``cache_len`` defaults to
    ``cfg.max_seq``; size it to the exact prompt+steps length to avoid
    attending over (and masking) dead cache positions.

    With ``cfg.seq_parallel`` (and a tp axis), the prompt runs under the
    SAME sequence-sharded layout the training forward uses — activations
    between blocks are (B, T/tp, D) per chip — so a seq-parallel-trained
    config keeps its memory/parallelism plan at serving time instead of
    silently reverting to replicated activations.  The cache it builds is
    identical (head-sharded, full sequence): attention inside the SP
    block already runs on the gathered sequence."""
    _reject_unservable(cfg)
    B, T = tokens.shape
    S = cfg.max_seq if cache_len is None else int(cache_len)
    x = _embed_tokens(params, tokens, cfg, tp_axis)
    kv_local = cfg.kv_heads() // tp_size  # GQA: cache holds kv heads only
    hd = cfg.head_size()
    x, block_kv, sp = _enter_block_layout(
        x, cfg, tp_axis, tp_size, return_kv=True
    )
    caches = []
    for lp in params["layers"]:
        x, (k, v) = block_kv(x, lp)
        shape = (B, kv_local, S, hd)
        ck = jnp.zeros(shape, x.dtype).at[:, :, :T].set(k)
        cv = jnp.zeros(shape, x.dtype).at[:, :, :T].set(v)
        caches.append((ck, cv))
    x = _norm_fn(cfg)(x, params["ln_f"])
    last = x[:, -1]
    if sp:
        # the prompt's final position lives on the LAST sequence shard;
        # broadcast its activation to the gang for the shared logits
        last = collectives.bcast(last, tp_axis, root=tp_size - 1)
    return _lm_logits(last, params, cfg, tp_axis), caches


def _select_token(logits, key, temperature: float, top_k: Optional[int]):
    """Next-token selection: greedy at temperature 0, else temperature-
    scaled (optionally top-k-truncated) categorical sampling."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def generate(
    params,
    prompt,
    steps: int,
    cfg: TransformerConfig,
    tp_axis=None,
    tp_size=1,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    rng=None,
):
    """Autoregressive decode: prefill the prompt, then ``steps``
    single-token steps through the KV cache under one ``lax.scan`` (static
    shapes, ONE compiled step body regardless of length).  Returns the
    (B, steps) generated token ids.

    ``temperature=0`` (default) is greedy; ``temperature > 0`` samples
    from the temperature-scaled distribution, truncated to ``top_k``
    logits when given, with one PRNG split per step from ``rng`` — inside
    shard_map the same replicated key yields identical samples on every
    rank, so the tp gang never diverges.

    ``cfg.seq_parallel`` is honored where a sequence dimension exists:
    the PREFILL runs sequence-sharded exactly like the training forward
    (see :func:`prefill`), producing the same head-sharded cache.  The
    per-token decode steps have no sequence dimension to shard, so they
    run the head-parallel math on that cache — the cache layout (and
    therefore the serving plan) is identical to what the SP training
    layout implies, not a silent strategy switch."""
    _reject_unservable(cfg)
    B, T = prompt.shape
    if T + steps > cfg.max_seq and not cfg.uses_rope():
        # rope has no position table, so max_seq is not a serving cliff:
        # the cache below is sized to exactly T + steps either way
        raise ValueError(
            f"prompt {T} + steps {steps} exceeds max_seq {cfg.max_seq}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if top_k is not None and not 0 < top_k <= cfg.vocab:
        raise ValueError(
            f"top_k must be in [1, vocab={cfg.vocab}], got {top_k}"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)  # carried but unused on the greedy path
    heads_local = cfg.n_heads // tp_size
    logits, caches = prefill(
        params, prompt, cfg, tp_axis, tp_size, cache_len=T + steps
    )
    rng, sub = jax.random.split(rng)
    first = _select_token(logits, sub, temperature, top_k).astype(prompt.dtype)

    rope = cfg.rope_base if cfg.uses_rope() else None
    hd = cfg.head_size()

    def step(carry, _):
        caches, tok, pos, key = carry
        x = _embed_rows(params["embed"], tok, cfg, tp_axis)[:, None, :]
        tables = None
        if rope is None:
            pos_emb = jax.lax.dynamic_slice_in_dim(params["pos"], pos, 1, 0)
            x = x + pos_emb[None, 0:1]
        else:
            # one table for the step, shared across all layers
            tables = _rope_tables(jnp.asarray(pos)[None], hd // 2, rope)
        new_caches = []
        for lp, (ck, cv) in zip(params["layers"], caches):
            x, ck, cv = _block_decode(
                x, lp, ck, cv, pos, heads_local, tp_axis,
                rope_tables=tables,
                ep_axis=(
                    cfg.moe_mesh_axis
                    if (tp_axis and cfg.n_experts) else None
                ),
                moe_cfg=cfg if cfg.n_experts else None,
                norm=_norm_fn(cfg), qk_eps=cfg.norm_eps,
            )
            new_caches.append((ck, cv))
        x = _norm_fn(cfg)(x, params["ln_f"])
        logits = _lm_logits(x[:, 0], params, cfg, tp_axis)
        key, sub = jax.random.split(key)
        nxt = _select_token(logits, sub, temperature, top_k).astype(tok.dtype)
        return (new_caches, nxt, pos + 1, key), tok

    (_, _, _, _), toks = jax.lax.scan(
        step, (caches, first, jnp.asarray(T), rng), None, length=steps
    )
    # each iteration emits the token it fed: [g_0 .. g_{steps-1}]
    return toks.T  # (B, steps)


def make_sharded_generate(
    cfg: TransformerConfig,
    mesh: Mesh,
    steps: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
):
    """Jitted dp/tp-sharded generation over the mesh: the KV cache lives
    head-sharded on the tp axis (each chip holds its heads' cache), the
    batch dp-sharded — the serving-side layout of the training
    parallelism plan.  Returns (fn, shard_fn); with ``temperature > 0``
    the returned fn takes (params, prompt, rng) — the key is replicated,
    then folded with the dp index so each batch shard draws its own
    stream while a tp gang stays in lockstep."""
    if cfg.context_parallel:
        raise ValueError(
            "context_parallel has no serving path: decode with "
            "dataclasses.replace(cfg, context_parallel=False) — cp "
            "params are replicated over tp and re-shard directly"
        )
    _reject_unservable(cfg)
    _check_moe_mesh(cfg, mesh)
    specs = param_specs(cfg)
    tp = mesh.shape["tp"]
    axes = _data_axes(cfg, mesh)
    batch = _batch_entry(axes)

    if temperature > 0.0:
        from jax import lax

        def gen(params, prompt, rng):
            # one fold per data axis: every batch shard draws its own
            # stream while a tp gang stays in lockstep
            for a in axes:
                rng = jax.random.fold_in(rng, lax.axis_index(a))
            return generate(
                params, prompt, steps, cfg, "tp", tp,
                temperature=temperature, top_k=top_k, rng=rng,
            )

        in_specs = (specs, P(batch, None), P())
    else:

        def gen(params, prompt):
            return generate(params, prompt, steps, cfg, "tp", tp)

        in_specs = (specs, P(batch, None))

    fn = jax.jit(
        shard_map(
            gen,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P(batch, None),
            check_vma=False,
        )
    )
    return fn, partial(_shard_params, specs=specs, mesh=mesh)


# ---------------------------------------------------------------------------
# sharded programs
# ---------------------------------------------------------------------------


def _reshard(x, mesh, spec):
    """Constrain ``x`` to ``spec`` on ``mesh``, working under BOTH mesh
    axis modes: explicit axes take :func:`jax.sharding.reshard`,
    auto axes take ``with_sharding_constraint``."""
    s = NamedSharding(mesh, spec)
    if jax.sharding.AxisType.Explicit in mesh.axis_types:
        return jax.sharding.reshard(x, s)
    return jax.lax.with_sharding_constraint(x, s)


def normalize_spec(spec):
    """``PartitionSpec`` with trailing ``None`` entries stripped — the
    canonical form the runtime stamps on program OUTPUTS.  Placement
    must use this form: ``P('tp', None)`` and ``P('tp')`` are the same
    layout, but jit keys its cache on the spelling, so unnormalized
    placement makes step 0 run a DIFFERENT compiled program (different
    reduction order) than the steady state — which is both a silent
    double-compile and the checkpoint-resume divergence bug (a restored
    tree re-enters at step-0 spelling while an uninterrupted run is on
    the steady program)."""
    parts = tuple(spec)
    while parts and parts[-1] is None:
        parts = parts[:-1]
    return P(*parts)


def _shard_params(params, specs, mesh):
    # copy before committing: device_put may ALIAS the source buffer (it
    # does on CPU), and the train step donates its params — without the
    # copy, donation would delete the caller's original arrays
    return jax.tree.map(
        lambda p, s: jax.device_put(
            jnp.array(p, copy=True), NamedSharding(mesh, normalize_spec(s))
        ),
        params, specs,
    )


def make_sharded_forward(cfg: TransformerConfig, mesh: Mesh):
    """Jitted tp/dp-sharded forward over the mesh; returns (fn, shard_fn).

    Under ``cfg.context_parallel`` the tokens are striped and
    sequence-sharded over tp on the way in and the logits unstriped on
    the way out, so the caller-facing contract (full-sequence tokens in
    token order -> full logits in token order) is unchanged."""
    _check_moe_mesh(cfg, mesh)
    specs = param_specs(cfg)
    tp = mesh.shape["tp"]
    batch = _batch_entry(_data_axes(cfg, mesh))

    def fwd(params, tokens):
        return forward(params, tokens, cfg, tp_axis="tp", tp_size=tp)

    if cfg.context_parallel:
        from .ring_attention import stripe_sequence, unstripe_sequence

        # each rank emits its striped (B, T/cp, vocab) shard; the
        # out_specs concatenation IS the striped full sequence (stripe =
        # contiguous sharding of the striped order) — no inner gather,
        # no replicated full-logits buffer
        smapped = shard_map(
            fwd,
            mesh=mesh,
            in_specs=(specs, P(batch, "tp")),
            out_specs=P(batch, "tp", None),
            check_vma=False,
        )

        def outer(params, tokens):
            out = smapped(params, stripe_sequence(tokens, tp, axis=1))
            # the API contract returns full logits: reassemble the
            # sequence once at the program's exit edge (under explicit
            # mesh axes the unstripe permutation cannot run on a
            # sequence-sharded operand, so reshard first)
            out = _reshard(out, mesh, P(batch, None, None))
            return unstripe_sequence(out, tp, axis=1)

        fn = jax.jit(outer)
    else:
        fn = jax.jit(
            shard_map(
                fwd,
                mesh=mesh,
                in_specs=(specs, P(batch, None)),
                out_specs=P(batch, None, None),
                check_vma=False,
            )
        )
    return fn, partial(_shard_params, specs=specs, mesh=mesh)


def make_sharded_router_probe(cfg: TransformerConfig, mesh: Mesh):
    """The MoE router's counters from the program's own forward path
    (same blocks, same dispatch as :func:`make_sharded_forward`): a
    jitted ``fn(params, tokens) -> {"expert_tokens": (L, E) routing
    entries sent to each of the router's experts in each MoE layer,
    "dropped": (L,) entries past capacity (or past a held share's row
    buffer), where the chip holds a share of the experts
    "held_entries": (L,) entries whose expert is held here, and under
    group-limited routing "group_tokens": (L, n_group) tokens whose kept
    groups include each group}``, summed over the data axes.  A probe beside the step, so that the train step
    keeps its ``(params, loss)``."""
    if not cfg.n_experts or cfg.context_parallel:
        raise ValueError("the router probe needs an MoE config without cp")
    _check_moe_mesh(cfg, mesh)
    tp = mesh.shape["tp"]
    axes = _data_axes(cfg, mesh)

    def probe(params, tokens):
        aux = _final_hidden(params, tokens, cfg, "tp", tp)[2]
        out = {
            k: aux[k] for k in (
                "expert_tokens", "dropped", "held_entries", "group_tokens"
            ) if k in aux
        }
        for a in axes:
            out = collectives.allreduce(out, a, ReduceFunction.SUM)
        return out

    return jax.jit(
        shard_map(
            probe,
            mesh=mesh,
            in_specs=(param_specs(cfg), P(_batch_entry(axes), None)),
            out_specs=P(),
            check_vma=False,
        )
    )


def _reject_untrainable_attention(cfg) -> None:
    """The train-step builders' check of ``cfg.attention``: every
    lowering it can name is trainable (the flash kernels by their
    custom_vjp backward), so this refuses an unknown NAME, here and not
    deep inside a traced forward."""
    impl = getattr(cfg, "attention", None)
    if impl not in (None, "auto", "naive", "blockwise", "flash"):
        raise ValueError(
            f"unknown attention impl {impl!r}: one of 'auto', 'naive', "
            "'blockwise', 'flash'"
        )


def _move_expert_bias(params, load, rate: float):
    """The expert bias's rule, outside the gradient: after a step each
    MoE layer's ``bias`` moves by ``rate * sign(mean load - load)``,
    towards the experts that got fewer than the mean of the step's
    routing entries.  ``load`` is (MoE layers, router's experts), the
    step's ``expert_tokens``; the bias's own gradient is zero (selection
    reads it through ``stop_gradient``), so SGD left it where it was."""
    layers, i = [], 0
    for lp in params["layers"]:
        if "bias" in lp.get("moe", {}):
            c = load[i].astype(jnp.float32)
            bias = lp["moe"]["bias"] + rate * jnp.sign(c.mean() - c)
            lp = {**lp, "moe": {**lp["moe"], "bias": bias}}
        i += "moe" in lp
        layers.append(lp)
    return {**params, "layers": layers}


def make_sharded_train_step(cfg: TransformerConfig, mesh: Mesh, lr: float = 1e-2):
    """One SGD train step as a single shard_map program over ('dp','tp').

    Where the config has an expert bias (``moe_bias_rate``), the step
    also applies :func:`_move_expert_bias` to it from the step's own
    routing counts, summed over the data axes.

    The differentiated quantity is the *global* mean loss (dp-allreduce of
    the local means), so shard_map's varying-axis tracking transposes the
    forward collectives into exactly the right gradient collectives: sharded
    weights keep local shard grads, replicated weights get the cross-shard
    psum — the dp gradient allreduce of classic data parallelism falls out
    of the same machinery.

    Under ``cfg.diffusion`` (:class:`BlockDiffusion`) the step is
    ``step(params, tokens, key) -> (params, loss, counters)``: the noise
    key (two uint32 words, or a typed key) stands in ``targets``' place,
    a data shard takes the whole batch's draw for its sequences, and
    ``counters`` are the
    step's own ``masked_tokens`` and, where the chip holds a share of the
    experts, ``held_entries`` a layer, summed over the data axes."""
    _reject_untrainable_attention(cfg)
    _check_moe_mesh(cfg, mesh)
    specs = param_specs(cfg)
    tp = mesh.shape["tp"]
    # data axes: 'dp', plus the dedicated expert axis when present (the
    # batch shards over both; dense-param grads psum over both)
    axes = _data_axes(cfg, mesh)
    denom = 1
    for a in axes:
        denom *= mesh.shape[a]
    bias_rate = cfg.moe_bias_rate if cfg.n_experts else 0.0

    def diffusion_step(params, tokens, key):
        index = 0   # this shard's place in the batch over the data axes
        for a in axes:
            index = index * mesh.shape[a] + jax.lax.axis_index(a)

        def global_loss(p):
            local, aux = _diffusion_loss(
                p, tokens, key, cfg, "tp", tp, True, (index, denom)
            )
            counters = {
                k: aux[k] for k in ("masked_tokens", "held_entries")
                if k in aux
            }
            for a in axes:
                counters = collectives.allreduce(
                    counters, a, ReduceFunction.SUM
                )
            return _mean_over_axes(local, axes, denom), counters

        (loss, counters), grads = jax.value_and_grad(
            global_loss, has_aux=True
        )(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss, counters

    def step(params, tokens, targets):
        def global_loss(p):
            if not bias_rate:
                local = loss_fn(p, tokens, targets, cfg, "tp", tp)
                return _mean_over_axes(local, axes, denom)
            # and the step's routing counts, for the bias's rule
            local, aux = loss_fn(
                p, tokens, targets, cfg, "tp", tp, with_aux=True
            )
            load = aux["expert_tokens"]
            for a in axes:
                load = collectives.allreduce(load, a, ReduceFunction.SUM)
            return _mean_over_axes(local, axes, denom), load

        loss, grads = jax.value_and_grad(
            global_loss, has_aux=bool(bias_rate)
        )(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        if bias_rate:
            loss, load = loss
            params = _move_expert_bias(params, load, bias_rate)
        return params, loss

    # context parallelism: tokens/targets are striped (outside shard_map
    # — a global permutation) and sequence-sharded over tp; the loss's
    # ring-mean keeps the differentiated quantity the global mean, so
    # the replicated weights' grads get the tp-psum from shard_map's
    # transpose machinery exactly like dp's
    batch = _batch_entry(axes)
    seq_spec = P(batch, "tp") if cfg.context_parallel else P(batch, None)
    if cfg.diffusion is not None:
        if bias_rate:
            raise ValueError(
                "block diffusion with an expert bias (moe_bias_rate): the "
                "step moves no bias under that objective yet"
            )
        smapped = shard_map(
            diffusion_step,
            mesh=mesh,
            in_specs=(specs, seq_spec, P()),
            out_specs=(specs, P(), P()),
        )
    else:
        smapped = shard_map(
            step,
            mesh=mesh,
            in_specs=(specs, seq_spec, seq_spec),
            out_specs=(specs, P()),
        )
    if cfg.context_parallel:
        from .ring_attention import stripe_sequence

        def outer(params, tokens, targets):
            return smapped(
                params,
                stripe_sequence(tokens, tp, axis=1),
                stripe_sequence(targets, tp, axis=1),
            )

        body = outer
    else:
        body = smapped
    fn = jax.jit(
        body,
        # the old params' HBM is dead the moment the SGD update exists:
        # donating it lets XLA update in place (ref: in-place device BOs)
        donate_argnums=(0,),
    )
    return fn, partial(_shard_params, specs=specs, mesh=mesh)


# ---------------------------------------------------------------------------
# command-ring opt-in: fused optimizer step (FUSED_APPLY slots)
# ---------------------------------------------------------------------------


def fused_optimizer_step(accl, bucket_grads, bucket_params, lr,
                         comm=None, timeout_s=60.0):
    """One data-parallel SGD step through the command ring's
    ``FUSED_APPLY`` slots: every gradient bucket reduces on-ring with
    the optimizer apply running per received chunk DURING the gather —
    no host round trip between reduction and update, so a warm step
    costs exactly one refill interaction for all buckets.

    ``bucket_grads[b]`` is this rank's ``size*n_b`` gradient
    contribution in allreduce chunk layout; ``bucket_params[b]`` its
    own ``n_b``-wide parameter shard.  Returns the applied shards
    (``param - lr * reduced_grad_chunk`` per bucket), host-side copies.

    This is the model zoo's fuse-hint surface: the facade sets
    ``CallOptions.fuse`` and the engine planner routes eligible calls
    to ``FUSED_APPLY`` ring slots, decomposing ineligible ones on host
    with a counted ``fused_decomposed`` fallback — semantics identical
    either way.
    """
    import numpy as np

    world = (comm or accl._world).size
    sends, outs = [], []
    for g, p in zip(bucket_grads, bucket_params):
        g = np.asarray(g, np.float32).ravel()
        p = np.asarray(p, np.float32).ravel()
        if g.size != world * p.size:
            raise ValueError(
                f"bucket gradient has {g.size} elements; FUSED_APPLY "
                f"needs size*n = {world * p.size} (allreduce chunk "
                "layout)"
            )
        sends.append(accl.create_buffer_from(np.concatenate([g, p])))
        outs.append(accl.create_buffer(p.size, np.float32))
    with accl.batch():
        reqs = [
            accl.fused_apply(
                sends[b], outs[b], outs[b].count, lr=lr, comm=comm,
                run_async=True,
            )
            for b in range(len(outs))
        ]
    for req in reqs:
        if not req.wait(timeout_s):
            raise TimeoutError("fused optimizer step timed out")
        req.check()
    applied = []
    for out in outs:
        out.sync_from_device()
        applied.append(out.data[:out.count].copy())
    return applied
