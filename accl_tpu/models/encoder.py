"""Bidirectional encoder family (BERT-shaped): the decoder flagship's
sibling on the same parallelism substrate.

Same Megatron-TP blocks (``transformer._block`` with ``causal=False``),
same dp×tp mesh, same fused attention lowerings (the blockwise online-
softmax fold runs full attention by dropping the causal mask) — only the
task head differs: masked-language-model loss over positions selected by
a mask, with the tied unembedding.

The reference has no model layer at all (SURVEY.md: "not a training
framework"); the model families here exist to exercise the collectives
engine the way the reference's host tests exercise the CCLO — the
encoder adds the bidirectional-attention shape (full (T, T) visibility)
to the exercised surface.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from ..constants import ReduceFunction
from ..ops import collectives
from .transformer import (
    TransformerConfig,
    _embed_tokens,
    _enter_block_layout,
    _layernorm,
    _reject_untrainable_attention,
    reject_latent,
    _shard_params,
    param_specs,
)


def encoder_forward(
    params,
    tokens,
    cfg: TransformerConfig,
    tp_axis=None,
    tp_size=1,
):
    """Bidirectional hidden states ``(B, T, d_model)`` for a token batch
    — ``forward``'s encoder twin (no causal mask, no LM head).  Honors
    the full config surface via the shared entry path: remat,
    seq_parallel (sequence-sharded activations between blocks, gathered
    back at exit), and the attention lowering."""
    if cfg.vocab_parallel:
        raise ValueError(
            "vocab_parallel is supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the encoder family"
        )
    if cfg.context_parallel:
        raise ValueError(
            "context_parallel is causal/decoder-only (the striped ring's "
            "load balance argument is the causal mask) — not the encoder "
            "family"
        )
    if cfg.n_experts:
        raise ValueError(
            "n_experts (MoE) is supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the encoder family"
        )
    reject_latent(cfg, "the encoder family")
    if not cfg.default_block():
        raise ValueError(
            "norm/ffn/qk_norm/tie_head other than the default block are "
            "supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the encoder family"
        )
    B, T = tokens.shape
    x = _embed_tokens(params, tokens, cfg)
    x, blocks, sp = _enter_block_layout(
        x, cfg, tp_axis, tp_size, causal=False
    )
    if cfg.remat:
        blocks = [jax.checkpoint(block) for block in blocks]
    for block, lp in zip(blocks, params["layers"]):
        x = block(x, lp)
    x = _layernorm(x, params["ln_f"])
    if sp:
        x = collectives.allgather_invariant(x, tp_axis, axis=1)
    return x


def _mlm_sums(params, tokens, targets, mask, cfg, tp_axis=None, tp_size=1):
    """(masked NLL sum, masked count) — the pre-normalization pieces, so
    a dp-sharded step can psum BOTH and divide globally (a mean of
    per-shard means would weight shards equally regardless of how many
    masked positions each one drew)."""
    h = encoder_forward(params, tokens, cfg, tp_axis, tp_size)
    logits = h @ params["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    return (nll * mask).sum(), mask.sum()


def mlm_loss(params, tokens, targets, mask, cfg, tp_axis=None, tp_size=1):
    """Masked-LM objective: mean NLL of ``targets`` at positions where
    ``mask`` is 1 (the classic denoising head, tied unembedding).
    ``tokens`` carry the corrupted input (e.g. [MASK]-substituted)."""
    total, count = _mlm_sums(
        params, tokens, targets, mask, cfg, tp_axis, tp_size
    )
    return total / jnp.maximum(count, 1.0)


def make_sharded_encoder_step(
    cfg: TransformerConfig, mesh: Mesh, lr: float = 1e-2
):
    """One MLM SGD step over ('dp', 'tp') — the encoder counterpart of
    ``make_sharded_train_step`` (same specs, same donation, same
    varying-axis gradient machinery)."""
    _reject_untrainable_attention(cfg)
    specs = param_specs(cfg)
    tp = mesh.shape["tp"]

    def step(params, tokens, targets, mask):
        def global_loss(p):
            total, count = _mlm_sums(
                p, tokens, targets, mask, cfg, "tp", tp
            )
            gtotal = collectives.allreduce(total, "dp", ReduceFunction.SUM)
            gcount = collectives.allreduce(count, "dp", ReduceFunction.SUM)
            return gtotal / jnp.maximum(gcount, 1.0)

        loss, grads = jax.value_and_grad(global_loss)(params)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    fn = jax.jit(
        shard_map(
            step,
            mesh=mesh,
            in_specs=(
                specs, P("dp", None), P("dp", None), P("dp", None),
            ),
            out_specs=(specs, P()),
        ),
        donate_argnums=(0,),
    )
    return fn, partial(_shard_params, specs=specs, mesh=mesh)


def encode(params, tokens, cfg: TransformerConfig):
    """Single-device convenience: pooled (mean over T) sentence
    embeddings — the encoder's serving surface."""
    h = encoder_forward(params, tokens, cfg)
    return h.mean(axis=1)
