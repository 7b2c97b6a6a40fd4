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
from ..utils.remat import KEPT_UNDER_REMAT
from .layers import (  # noqa: F401 (the shared pieces, importable from here)
    _AUTO_FUSED_MIN_T,
    _NORMS,
    _attention,
    _auto_flash_fits,
    _layernorm,
    _norm_fn,
    _qk_norm,
    _rmsnorm,
    _rope_rotate,
    _rope_tables,
    _tp_specs,
    resolve_attention,
)
from .mixers import MIXERS
from .mixers.attention import HeadGeometry
from .mixers.kda import DeltaAttention
from .mixers.latent import (
    LatentAttention,
    YarnScaling,
    yarn_inv_freq,
    yarn_mscale,
)
from .mixers.mamba2 import Mamba2


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
        """Every layer alike, its mixer one the plain blocks have a form for
        (``MIXERS``: each mixer's ``plain``) and nothing of the later kinds
        (a pattern, a head width of its own, the gate, per-head QK-norm,
        post-norms, a scaled embedding, the sigmoid router, a shared expert,
        a held share, grouped top-k, the balance losses, a latent expert
        bank, relu2): what prefill/generate and the context- and
        sequence-parallel blocks compute."""
        return (
            self.layers is None and not _beyond_plain_mixers(self)
            and self.head_dim is None
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
        # each mixer's description alone, whichever layers have it
        for mixer in MIXERS.values():
            mixer.check(self, None, None)
        if self.rope_yarn is not None and self.pos_embedding != "rope":
            raise ValueError("rope_yarn rescales a rotary embedding")
        if self.diffusion is not None:
            d = self.diffusion
            if (
                self.pos_embedding != "rope"
                # the layout is the attention mixer's, of every key
                or any(
                    self.mixer(k) != "attention" or k.window is not None
                    or k.ffn == "none" for k in self.pattern()
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
                if mixer == "none":
                    if kind.ffn == "none":
                        raise ValueError(
                            f"layer {i}: a block has a mixer, an FFN or both"
                        )
                elif mixer not in MIXERS:
                    raise ValueError(f"layer {i}: unknown mixer {mixer!r}")
                else:
                    MIXERS[mixer].check(self, kind, i)
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

    def default_block(self) -> bool:
        """The block the encoder and the composed pipeline compute."""
        return (
            self.norm == "layernorm" and self.ffn == "gelu"
            and not self.qk_norm and self.tie_head and self.plain()
        )


#: what :meth:`TransformerConfig.plain` refuses beside a mixer, for the
#: refusals' messages
_BEYOND_PLAIN_KINDS = (
    "a layer pattern, a head_dim of its own, an attention gate, per-head "
    "QK-norm, post-norms, a scaled embedding, a sigmoid router, a shared "
    "expert, a held share of the experts, a latent expert bank, relu2, "
    "grouped top-k or balance losses"
)


def _beyond_plain_mixers(cfg) -> list:
    """What the plain blocks (prefill/generate, the context- and
    sequence-parallel blocks, the encoder, the pipelines) have no form for
    among ``cfg``'s layers, in the layers' order: each mixer's by the name
    its module gives (``MIXERS``: ``plain``), a block of one sub-layer by
    its own."""
    named = []
    for kind in dict.fromkeys(cfg.pattern()):
        name = cfg.mixer(kind)
        whats = [] if name == "none" else [MIXERS[name].plain(cfg, kind)]
        if "none" in (name, kind.ffn):
            whats.append(
                "a block of one sub-layer (LayerKind.mixer or .ffn 'none')"
            )
        named += [w for w in whats if w is not None and w not in named]
    return named


def _beyond_plain(cfg) -> str:
    """:func:`_beyond_plain_mixers` and the kinds beside them, as a
    refusal's message lists them."""
    return "; ".join(
        _beyond_plain_mixers(cfg)
        + ["or, of the pattern's, the block's and the router's kinds, "
           + _BEYOND_PLAIN_KINDS]
    )


def _check_axis_compat(cfg) -> None:
    """context_parallel turns the tp axis into the sequence ring —
    it cannot share that axis with the strategies that give tp other
    jobs (head-sharded weights + sequence/vocab sharding)."""
    if (cfg.context_parallel or cfg.seq_parallel) and not cfg.plain():
        raise ValueError(
            "context_parallel and seq_parallel take the plain block only "
            "(TransformerConfig.plain; a recurrent state is not handed "
            "round the ring yet, nor is block diffusion's layout in it): "
            "this configuration has " + _beyond_plain(cfg)
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
    """The specs of one layer of ``kind``: its mixer's leaves (``MIXERS``),
    the block's norms, its FFN's."""
    gated = cfg.ffn == "swiglu"
    cp = cfg.context_parallel
    col, row, _ = _tp_specs(cfg)
    mixer = cfg.mixer(kind)
    layer = {} if mixer == "none" else MIXERS[mixer].specs(cfg, kind)
    # one norm a sub-layer: a block without a mixer has no ``ln1``, one
    # without an FFN no ``ln2``
    if cfg.post_norm != "only":
        if mixer != "none":
            layer["ln1"] = P(None)
        if kind.ffn != "none":
            layer["ln2"] = P(None)
    if cfg.post_norm:
        if mixer != "none":
            layer["ln1_post"] = P(None)
        if kind.ffn != "none":
            layer["ln2_post"] = P(None)
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

    def normal(key, shape):
        return jax.random.normal(key, shape, cfg.dtype) * scale

    for i, kind in enumerate(cfg.pattern()):
        # a layer's four keys: two its mixer's, two its FFN's
        kk = k[2 + 4 * i : 6 + 4 * i]
        mixer = cfg.mixer(kind)
        layer = {} if mixer == "none" else MIXERS[mixer].init(kk[:2], cfg, kind)
        if cfg.post_norm != "only":
            if mixer != "none":
                layer["ln1"] = jnp.ones((cfg.d_model,), cfg.dtype)
            if kind.ffn != "none":
                layer["ln2"] = jnp.ones((cfg.d_model,), cfg.dtype)
        if cfg.post_norm:
            if mixer != "none":
                layer["ln1_post"] = jnp.ones((cfg.d_model,), cfg.dtype)
            if kind.ffn != "none":
                layer["ln2_post"] = jnp.ones((cfg.d_model,), cfg.dtype)
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


def _block(x, lp, mixer, tp_axis, return_kv=False,
           ep_axis=None, moe_cfg=None, with_aux=False,
           reduce_fn=None, fanout_fn=None, norm=_layernorm, relu2=False):
    """One transformer block on tp-sharded weights.  ``lp['wqkv']`` etc. are
    the *local shards*; the tp-allreduce after each row-parallel matmul is
    the reference's fused-allreduce hot path in model form.  ``mixer`` is
    the layer's bound mixer (``MIXERS[name].bind``): ``(h, lp) ->
    (partial_o, kv)``, the row-parallel PARTIAL output.

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
        partial_o, kv = mixer(h, lp)
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


def _block_cp(x, lp, mixer, ep_axis=None, moe_cfg=None, with_aux=False,
              norm=_layernorm):
    """Context-parallel block: ``x`` is (B, T/cp, D), this rank's STRIPED
    sequence shard over the ring's axis; weights are full (replicated over
    the axis).  QKV/MLP matmuls are purely local; ``mixer`` is the
    attention mixer bound to the ring (:func:`_enter_block_layout`):
    striped causal ring attention — K/V blocks (unexpanded kv heads under
    GQA) rotate around the ring folding into the local online-softmax
    state — so nothing in the block ever materializes the full sequence.
    Rope rotates by the shard's GLOBAL token positions; ``cfg.attention``
    maps to the fold's within-hop sub-tiling (:func:`_cp_block_k`).

    With an expert bank on the layer (MoE x cp — long-context MoE), the
    MLP half routes this rank's sequence shard through the expert
    dispatch all-to-all over ``ep_axis`` while the K/V ring turns over
    tp: the two communication patterns ride DIFFERENT mesh axes, which
    is exactly why the composition is legal (tp_axis stays None — under
    cp the experts, like every weight, are replicated over the ring)."""
    h = norm(x, lp["ln1"])
    o, _ = mixer(h, lp)
    x = x + o
    return _mlp(x, lp, None, ep_axis, moe_cfg, with_aux, norm=norm)


def _block_sp(x_sp, lp, mixer, tp_axis, return_kv=False, norm=_layernorm):
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
    partial_o, kv = mixer(h_full, lp)
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


def _bound_blocks(block, cfg, tp_axis, tp_size, **softmax):
    """``block`` for each layer of the pattern, the layer's mixer bound by
    name (``MIXERS``; a block without a mixer binds none): ONE function
    where every layer is alike, one a layer where ``cfg.layers`` gives
    them.  ``softmax``: what the bidirectional and the context-parallel
    paths, which take the plain block alone, bind the attention mixer
    with."""

    def bound(kind):
        name = cfg.mixer(kind)
        if name == "none":
            return partial(block, mixer=None)
        bind = MIXERS[name].bind
        return partial(
            block, mixer=bind(cfg, kind, tp_axis, tp_size, **softmax)
        )

    if cfg.layers is None:
        return [bound(kind) for kind in cfg.pattern()[:1]] * cfg.n_layers
    return [bound(kind) for kind in cfg.layers]


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
    invariant.  Returns ``(x, blocks, layout)``: the layout's block for
    each layer of the pattern with the layer's mixer bound
    (:func:`_bound_blocks`), and layout one of
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
        from .ring_attention import striped_attention

        cp_kw = dict(norm=_norm_fn(cfg))
        if cfg.n_experts:
            cp_kw["ep_axis"] = cfg.moe_mesh_axis
            cp_kw["moe_cfg"] = cfg
            cp_kw["with_aux"] = True
        block_k = _cp_block_k(x.shape[1], cfg.attention)
        # the ring's axis carries no weight shard: the mixer's weights are
        # whole (no tp axis, a tp of 1)
        blocks = _bound_blocks(
            partial(_block_cp, **cp_kw), cfg, None, 1,
            positions=lambda t_local: _cp_positions(t_local, tp_axis),
            attention_fn=lambda q, k, v: striped_attention(
                q, k, v, tp_axis, causal=True, block_k=block_k
            ),
        )
        return x, blocks, "cp"
    if cfg.vocab_parallel and tp_size > 1 and cfg.vocab % tp_size:
        raise ValueError(
            f"vocab_parallel needs vocab ({cfg.vocab}) divisible by tp "
            f"({tp_size})"
        )
    sp = cfg.seq_parallel and tp_axis is not None and tp_size > 1
    kw = dict(tp_axis=tp_axis, norm=_norm_fn(cfg))
    if return_kv:
        kw["return_kv"] = True
    # the bidirectional (encoder) form is the attention mixer's
    softmax = {} if causal else {"causal": False}
    if sp:
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
        blocks = _bound_blocks(
            partial(_block_sp, **kw), cfg, tp_axis, tp_size, **softmax
        )
        return x, blocks, "sp"
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
    blocks = _bound_blocks(
        partial(_block, **kw), cfg, tp_axis, tp_size, **softmax
    )
    return x, blocks, ""


def _layer_blocks(blocks, cfg):
    """The pattern's blocks (:func:`_enter_block_layout`'s) as the train
    and forward paths run them: under ``cfg.remat`` each is
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
    if not cfg.remat:
        return blocks
    policy = jax.checkpoint_policies.save_only_these_names(KEPT_UNDER_REMAT)
    # layers that share a block share its rematerialised form
    remat = {id(b): jax.checkpoint(b, policy=policy) for b in blocks}
    return [remat[id(b)] for b in blocks]


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
    x, blocks, sp = _enter_block_layout(x, cfg, tp_axis, tp_size)
    blocks = _layer_blocks(blocks, cfg)
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
            "prefill/generate serve TransformerConfig.plain() only: the "
            "decode path has no cache layout or block yet (train and "
            "forward do) for what this configuration has, "
            + _beyond_plain(cfg)
        )


def reject_latent(cfg, where: str) -> None:
    """The paths beside train and forward refuse, by name, a layer whose
    mixer they have no form for (``MIXERS``: a latent, a KDA or a Mamba-2
    mixer, an attention mixer under block diffusion or with heads of a
    layer kind's own) and a block of one sub-layer."""
    named = _beyond_plain_mixers(cfg)
    if named:
        raise ValueError(
            f"{named[0]}: supported on the decoder's train and forward "
            f"paths only, not {where}"
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
    x, blocks_kv, sp = _enter_block_layout(
        x, cfg, tp_axis, tp_size, return_kv=True
    )
    caches = []
    for block_kv, lp in zip(blocks_kv, params["layers"]):
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
