"""Composed 3-axis parallelism: pipeline x data x tensor in ONE program.

The parallelism layers each exist standalone — Megatron-TP blocks
(``transformer.py``), GPipe/1F1B microbatch pipelining (``pipeline.py``),
dp gradient averaging — and the point of the substrate is that they
compose (SURVEY.md §5: the long-context/parallelism machinery is a
composable layer over the collectives engine, not special cases).  This
module is the composition: a mesh ``('pp', 'dp', 'tp')`` where

* each ``pp`` rank owns a contiguous span of transformer blocks, stored
  STACKED (leading layer axis sharded over ``pp``) and walked with one
  ``lax.scan`` — O(1) program size in depth;
* inside a stage, every block runs the Megatron-TP math (column/row
  parallel matmuls, tp-allreduce exits) over the ``tp`` axis;
* the batch is sharded over ``dp`` and split into microbatches that
  stream through the stages (``pipeline_apply``'s uniform schedule, the
  activation handoff one ``ppermute`` hop per boundary);
* embeddings / final layernorm are replicated across ``pp``; their
  gradients (stage-0 consumption + last-stage loss head contributions)
  come out of shard_map's varying-axis tracking, which transposes the
  forward's collectives into exactly the right cotangent psums — the
  same machinery ``make_sharded_train_step`` relies on, extended by one
  mesh axis.

Gradients come from autodiff through the pipeline loop (the GPipe
schedule; the hand-scheduled 1F1B backward lives at the pipeline-layer
API with its stage-local-grads contract).  The whole step — forward
pipeline, loss, backward through transposed ppermute edges, SGD — is
one jitted shard_map program over the 3-D mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .mixers import MIXERS
from .pipeline import pipeline_apply, pipeline_apply_interleaved
from .transformer import (
    TransformerConfig,
    _block,
    _embed_tokens,
    _layernorm,
    _reject_untrainable_attention,
    reject_latent,
    init_params,
)


def stacked_param_specs(cfg: TransformerConfig) -> Dict:
    """Partition specs for the STACKED parameter tree: per-layer leaves
    gain a leading layer axis sharded over ``pp``; within a layer the
    Megatron column/row specs shard over ``tp`` as in
    ``transformer.param_specs``; embeddings/final-ln replicate."""
    layer = {
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "w1": P("pp", None, "tp"),
        "w2": P("pp", "tp", None),
        "ln1": P("pp", None),
        "ln2": P("pp", None),
    }
    out = {
        "embed": P(None, None),
        "ln_f": P(None),
        "layers": layer,
    }
    if not cfg.uses_rope():
        out["pos"] = P(None, None)
    return out


def stack_params(params: Dict) -> Dict:
    """``transformer.init_params``' per-layer list -> stacked arrays with
    a leading layer axis (the pp shard dim)."""
    layers = params["layers"]
    stacked = {
        k: jnp.stack([lp[k] for lp in layers]) for k in layers[0]
    }
    return {**{k: v for k, v in params.items() if k != "layers"},
            "layers": stacked}


def unstack_params(params: Dict) -> Dict:
    """Inverse of :func:`stack_params` (for comparisons/checkpoints)."""
    L = params["layers"]["wq"].shape[0]
    layers = [
        {k: v[i] for k, v in params["layers"].items()} for i in range(L)
    ]
    return {**{k: v for k, v in params.items() if k != "layers"},
            "layers": layers}


# psum whose TRANSPOSE is identity: the correct vjp when the cotangent
# arriving at the psum's output is replicated across the axis (it is —
# everything downstream of the row-parallel allreduce is tp-replicated).
# The manual 1F1B backward runs without the vma machinery that normally
# knows this; the naive transpose under check_vma=False would RE-SUM the
# replicated cotangent and inflate every post-allreduce gradient by tp.
@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _psum_identity_bwd(x, axis_name):
    return lax.psum(x, axis_name)


def _psum_identity_fwd(x, axis_name):
    return lax.psum(x, axis_name), None


def _psum_identity_rev(axis_name, _res, g):
    return (g,)


_psum_identity_bwd.defvjp(_psum_identity_fwd, _psum_identity_rev)


# the dual: identity forward, psum TRANSPOSE — placed where a replicated
# activation FANS OUT into tp-sharded branch compute (the q/k/v and w1
# matmuls).  The true cotangent of the fan-out point is the SUM of every
# rank's branch contribution; vma places this psum automatically, the
# manual backward must place it by hand.  The residual paths
# (replicated compute, counted once) stay outside the wrapper.
@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fanout_psum_bwd(x, axis_name):
    return x


def _fanout_fwd(x, axis_name):
    return x, None


def _fanout_rev(axis_name, _res, g):
    return (lax.psum(g, axis_name),)


_fanout_psum_bwd.defvjp(_fanout_fwd, _fanout_rev)


def interleave_layer_order(n_layers: int, pp: int, v_stages: int):
    """Device-major layer permutation for the interleaved schedule:
    position k of the permuted stack holds old layer ``perm[k]``, laid
    out so the contiguous pp shard of the permuted array gives device
    ``d`` its ``v_stages`` round-robin chunks (global stage
    ``v*pp + d``) in (chunk, layer-within-stage) order."""
    ls = n_layers // (v_stages * pp)
    perm = []
    for d in range(pp):
        for v in range(v_stages):
            j = v * pp + d
            perm.extend(range(j * ls, (j + 1) * ls))
    return perm


def make_pp_train_step(
    cfg: TransformerConfig,
    mesh: Mesh,
    num_microbatches: int,
    lr: float = 1e-2,
    v_stages: int = 1,
    schedule: str = "gpipe",
    adam=None,
    debug_invariants: bool = False,
):
    """One SGD step over the ('pp', 'dp', 'tp') mesh.

    ``adam`` (an :class:`accl_tpu.parallel.AdamConfig`) switches the
    update from SGD to the ZeRO-1 sharded Adam/AdamW: fp32 moments (and
    optional master weights) live 1/dp per chip NESTED inside the
    pp x tp stage sharding, global-norm clipping psums its squared sums
    over every sharding axis (tp, pp) so pipeline training clips exactly
    like the flagship.  The return grows to ``(step, shard,
    init_state)`` with ``step(params, state, tokens, targets) ->
    (params, state, loss)`` — the same contract as
    ``make_zero_train_step``.

    Returns ``(step, shard)``: ``step(params, tokens, targets) ->
    (params, loss)`` with ``params`` in stacked form committed to the
    mesh by ``shard``; ``tokens/targets`` are the GLOBAL batch,
    dp-sharded on the batch dim.  The per-dp-rank batch must divide into
    ``num_microbatches``; ``cfg.n_layers`` must divide by the pp size.

    ``v_stages > 1`` runs the INTERLEAVED virtual-stage schedule: each
    pp rank owns ``v_stages`` round-robin chunks of the layer stack
    (global stage ``v*pp + d`` on device ``d`` —
    :func:`pipeline.pipeline_apply_interleaved`), cutting the pipeline
    bubble to ``(pp-1)/v_stages`` warmup chunk-ticks.  ``shard``
    commits the stacked layers PERMUTED into device-major chunk order
    (:func:`interleave_layer_order`); ``num_microbatches`` must divide
    by pp and ``n_layers`` by ``v_stages * pp``.

    ``schedule="1f1b"`` replaces the autodiff-through-GPipe backward
    with the hand-scheduled one-forward-one-backward interleave
    (:func:`pipeline.pipeline_loss_and_grads_1f1b`): the activation
    stash holds only ``min(pp, M)`` in-flight microbatch INPUTS with
    recompute-at-use, instead of autodiff's O(M·ticks) residuals — the
    memory profile that makes large-M accumulation affordable.  The
    pipeline's 1F1B primitive returns the loss-head parameter grads and
    the stage-0 input grads; this maker closes the loop through the
    embedding vjp and places the replicated-param psums (embedding
    contributions live on pp rank 0, head contributions on the last
    rank) explicitly.  Not combinable with ``v_stages > 1`` yet.

    ``debug_invariants=True`` re-arms, at runtime, the guarantee the
    disabled vma checker would have provided statically (the manual
    1F1B backward must run ``check_vma=False`` — see the smap_kwargs
    note below): the step returns an extra replicated scalar, the max
    |neighbor difference| of the invariant-destined values (loss and
    the replicated-param grads: embed/ln_f/pos) under a one-step
    rotation along every mesh axis.  When every transpose is right the
    scalar sits at the reduction's ROUNDING FLOOR: exactly 0 on
    power-of-two axes in practice, and at worst a few float32 ulp of
    the grads (~1e-9 observed on a dp=3 axis, where XLA's lowering of
    the fused program is not bitwise rank-identical).  A mis-placed
    hand transpose — the exact bug class ``check_vma=False`` stops the
    checker from catching — shows up at the GRADIENT's own magnitude
    (observed ~1e-2, five orders above the floor), so thresholding at
    ~1e-6 separates them cleanly.  The
    checks are uniform post-loop collectives (never inside the per-tick
    switch), token-ordered like every other post-loop psum, so they are
    deadlock-safe by the same rule the schedule itself follows.  Step
    returns become ``(params[, state], loss, invariant_err)``.
    """
    _reject_untrainable_attention(cfg)
    if cfg.seq_parallel:
        raise ValueError(
            "make_pp_train_step does not compose with seq_parallel yet: "
            "the pipeline streams full-sequence microbatch activations "
            "between stages (sequence-shard them with the standalone "
            "Megatron-SP train step, or request the composition)"
        )
    pp = mesh.shape["pp"]
    dp = mesh.shape["dp"]
    tp = mesh.shape["tp"]
    V = int(v_stages)
    if V < 1:
        raise ValueError(f"v_stages ({V}) must be >= 1")
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown composed pipeline schedule {schedule!r}")
    if schedule == "1f1b" and V != 1:
        raise ValueError(
            "schedule='1f1b' does not compose with v_stages > 1 yet"
        )
    if cfg.n_layers % (V * pp):
        raise ValueError(
            f"n_layers ({cfg.n_layers}) must divide by v_stages * pp "
            f"({V} * {pp})"
        )
    ls = cfg.n_layers // (V * pp)  # layers per (virtual) stage
    if cfg.n_heads % tp:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must divide by tp ({tp})"
        )
    if cfg.vocab_parallel:
        raise ValueError(
            "vocab_parallel is supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the composed pipeline"
        )
    if cfg.context_parallel:
        raise ValueError(
            "context_parallel is supported on the decoder flagship only "
            "(forward/loss_fn), not the composed pipeline"
        )
    if cfg.n_experts:
        raise ValueError(
            "n_experts (MoE) is supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the composed pipeline"
        )
    reject_latent(cfg, "the composed pipeline")
    if not cfg.default_block():
        raise ValueError(
            "norm/ffn/qk_norm/tie_head other than the default block are "
            "supported on the decoder flagship only "
            "(forward/loss_fn/generate), not the composed pipeline"
        )
    M = num_microbatches
    specs = stacked_param_specs(cfg)
    # the default block: every layer alike, its mixer the configuration's
    kind = cfg.pattern()[0]
    mixer = MIXERS[cfg.mixer(kind)].bind(cfg, kind, "tp", tp)

    def stage_fn(stage_layers, x):
        """This rank's layer span, walked with one scan; each block is
        the Megatron-TP block over the 'tp' axis.  ``cfg.remat``
        checkpoints each block (recompute on backward) exactly like the
        plain forward does.  Under the manual 1F1B backward the tp
        reduction is the identity-transpose psum (see
        :data:`_psum_identity_bwd`)."""
        def body(h, lp):
            blk = partial(
                _block, mixer=mixer, tp_axis="tp",
                reduce_fn=(
                    _psum_identity_bwd if schedule == "1f1b" else None
                ),
                fanout_fn=(
                    _fanout_psum_bwd if schedule == "1f1b" else None
                ),
            )
            if cfg.remat:
                blk = jax.checkpoint(blk)
            return blk(h, lp), None

        out, _ = lax.scan(body, x, stage_layers)
        return out

    def loss_head(final_act, tgt_mb, p):
        """Last stage's head: final layernorm + tied unembed + CE."""
        h = _layernorm(final_act, p["ln_f"])
        logits = h @ p["embed"].T
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, tgt_mb[..., None], axis=-1
        ).squeeze(-1)
        return nll.mean()

    def _compute_grads(params, tokens, targets):
        """(loss, grads) via the selected schedule — shared by the SGD
        and ZeRO-Adam steps."""
        B, T = tokens.shape  # per-dp-rank batch
        if B % M:
            raise ValueError(
                f"per-dp-rank batch ({B}) must divide into "
                f"num_microbatches ({M})"
            )
        me_pp = lax.axis_index("pp")

        def step_1f1b(p):
            """Hand-scheduled backward: the pipeline primitive owns the
            stage interleave; this closes the program around it —
            embedding vjp in front, loss-head grads behind, explicit
            psums for the replicated params whose contributions live on
            single pp ranks."""
            from ..constants import ReduceFunction
            from ..ops import collectives
            from .pipeline import pipeline_loss_and_grads_1f1b

            def embed_mbs(p_):
                x = _embed_tokens(p_, tokens, cfg)
                return x.reshape(M, B // M, T, cfg.d_model)

            mbs, embed_vjp = jax.vjp(embed_mbs, p)
            tgts = targets.reshape(M, B // M, T)
            head = {"embed": p["embed"], "ln_f": p["ln_f"]}
            loss_pp, layer_grads, head_grads, in_grads = (
                pipeline_loss_and_grads_1f1b(
                    p["layers"], mbs, tgts, "pp", stage_fn,
                    lambda hp, y, t: loss_head(y, t, hp),
                    head_params=head, return_input_grads=True,
                )
            )
            # TOTALLY ORDER the post-loop collectives: the manual path
            # has several independent psum chains (embedding, head,
            # per-leaf dp averages, the loss), and XLA's CPU in-process
            # rendezvous deadlocks when independent collective chains
            # execute concurrently (observed: half the device threads
            # parked in a loop ppermute, half in a grad allreduce, both
            # op_id=1).  A token threaded through optimization_barrier
            # gives every collective a data dependency on its
            # predecessor — a linear schedule, negligible next to the
            # pipeline itself.
            token = loss_pp

            def seq_allreduce(g, *axes):
                # the barrier's output unions the token's vma into g, so
                # invariant-destined values (loss, replicated-param
                # grads) must be sequenced BEFORE the {pp,tp}-varying
                # layer leaves pollute the token
                nonlocal token
                g, _ = lax.optimization_barrier((g, token))
                for ax in axes:
                    g = collectives.allreduce(g, ax, ReduceFunction.SUM)
                token = g.reshape(-1)[0].astype(jnp.float32)
                return g

            # dp average (the gpipe path gets this from the vma
            # transpose of the psum'd loss; here it is explicit)
            loss = seq_allreduce(loss_pp, "dp") / dp
            # in_grads is valid on pp rank 0 (zeros elsewhere): the pp
            # psum hands every rank exactly rank 0's values (and the
            # pp-invariant vma the embedding vjp expects)
            (embed_path,) = embed_vjp(seq_allreduce(in_grads, "pp"))
            d_embed = seq_allreduce(
                embed_path["embed"].astype(jnp.float32)
                + seq_allreduce(head_grads["embed"], "pp"),
                "dp",
            ) / dp
            d_ln_f = seq_allreduce(
                seq_allreduce(head_grads["ln_f"], "pp"), "dp"
            ) / dp
            grads = {
                "embed": d_embed.astype(p["embed"].dtype),
                "ln_f": d_ln_f.astype(p["ln_f"].dtype),
            }
            if "pos" in embed_path:
                grads["pos"] = (
                    seq_allreduce(
                        embed_path["pos"].astype(jnp.float32), "dp"
                    ) / dp
                ).astype(p["pos"].dtype)
            inv_err = None
            if debug_invariants:
                # runtime stand-in for the disabled vma checker: the
                # loss and the replicated-param grads should be
                # identical on every rank.  The check is a NEIGHBOR-
                # COMPARE — rotate by one along each axis with ppermute
                # and diff — which adds no rounding of its own (a mean-
                # compare would); the residual floor is XLA's own fused-
                # program lowering, ulp-level on non-power-of-two axes
                # (see the docstring).  Token-ordered like every other
                # post-loop collective.
                def repl_err(v):
                    nonlocal token
                    v32 = v.astype(jnp.float32)
                    v32, _ = lax.optimization_barrier((v32, token))
                    err = jnp.float32(0)
                    for ax, size in (("pp", pp), ("tp", tp), ("dp", dp)):
                        if size == 1:
                            continue
                        perm = [(i, (i + 1) % size) for i in range(size)]
                        shifted = lax.ppermute(v32, ax, perm)
                        err = jnp.maximum(
                            err, jnp.max(jnp.abs(v32 - shifted))
                        )
                    token = err
                    return err

                inv_err = repl_err(loss)
                for k in ("embed", "ln_f", "pos"):
                    if k in grads:
                        inv_err = jnp.maximum(inv_err, repl_err(grads[k]))
                # the verdict itself must be replicated: a VIOLATED
                # invariant makes |v - m| rank-varying, so max-reduce it
                # mesh-wide before it leaves the shard_map body
                inv_err, _ = lax.optimization_barrier((inv_err, token))
                for ax in ("pp", "tp", "dp"):
                    inv_err = collectives.allreduce(
                        inv_err, ax, ReduceFunction.MAX
                    )
                token = inv_err
            # pp-local stage grads, dp-averaged leaf by leaf (LAST: they
            # are {pp, tp}-varying and the token inherits that)
            grads["layers"] = jax.tree_util.tree_map(
                lambda g, p_: (
                    seq_allreduce(g.astype(jnp.float32), "dp") / dp
                ).astype(p_.dtype),
                layer_grads, p["layers"],
            )
            return loss, grads, inv_err

        def global_loss(p):
            x = _embed_tokens(p, tokens, cfg)
            mbs = x.reshape(M, B // M, T, cfg.d_model)
            tgts = targets.reshape(M, B // M, T)
            if V > 1:
                # this rank's (V*ls, ...) permuted slice -> V chunks of
                # ls layers each; stage_fn scans a chunk's layers
                chunks = jax.tree_util.tree_map(
                    lambda a: a.reshape((V, ls) + a.shape[1:]),
                    p["layers"],
                )
                outs = pipeline_apply_interleaved(
                    chunks, mbs, "pp", stage_fn, V
                )
            else:
                outs = pipeline_apply(p["layers"], mbs, "pp", stage_fn)
            per_mb = jax.vmap(lambda o, t: loss_head(o, t, p))(outs, tgts)
            # last stage's mean, summed over pp (one nonzero term) and
            # averaged over dp — differentiated as the GLOBAL quantity,
            # so the varying-axis transpose places every cotangent psum
            local = jnp.where(me_pp == pp - 1, per_mb.mean(), 0.0)
            return lax.psum(lax.psum(local, "pp"), "dp") / dp

        if schedule == "1f1b":
            return step_1f1b(params)
        loss, grads = jax.value_and_grad(global_loss)(params)
        inv_err = None
        if debug_invariants:
            # same neighbor-compare as the 1f1b path (bitwise-exact for
            # any axis size), minus the token chain the checked-vma
            # autodiff path does not need
            def repl_err(v):
                v32 = v.astype(jnp.float32)
                err = jnp.float32(0)
                for ax, size in (("pp", pp), ("tp", tp), ("dp", dp)):
                    if size == 1:
                        continue
                    perm = [(i, (i + 1) % size) for i in range(size)]
                    shifted = lax.ppermute(v32, ax, perm)
                    err = jnp.maximum(err, jnp.max(jnp.abs(v32 - shifted)))
                return err

            inv_err = repl_err(loss)
            for k in ("embed", "ln_f", "pos"):
                if k in grads:
                    inv_err = jnp.maximum(inv_err, repl_err(grads[k]))
            for ax in ("pp", "tp", "dp"):  # replicate the verdict
                inv_err = lax.pmax(inv_err, ax)
        return loss, grads, inv_err

    def step(params, tokens, targets):
        loss, grads, inv = _compute_grads(params, tokens, targets)
        params = jax.tree.map(lambda p_, g: p_ - lr * g, params, grads)
        if debug_invariants:
            return params, loss, inv
        return params, loss

    def zero_step(params, state, tokens, targets):
        """ZeRO-Adam variant: same gradient computation, then the
        dp-sliced sharded update (moments nested inside the pp x tp
        stage sharding)."""
        from ..parallel.zero import clip_by_global_norm, zero_adam_update

        loss, grads, inv = _compute_grads(params, tokens, targets)
        if adam.clip_grad_norm is not None:
            grads, _ = clip_by_global_norm(
                grads, specs, adam.clip_grad_norm, "tp", "dp",
                pp_axis="pp",
            )
        params, state = zero_adam_update(
            params, grads, state, "dp", adam, specs=specs
        )
        if debug_invariants:
            return params, state, loss, inv
        return params, state, loss

    if adam is not None:
        from ..parallel.zero import zero_state_specs

        sspecs = zero_state_specs(
            specs, master_weights=adam.master_weights
        )
        smap_kwargs = dict(
            mesh=mesh,
            in_specs=(specs, sspecs, P("dp", None), P("dp", None)),
            out_specs=(
                (specs, sspecs, P(), P())
                if debug_invariants
                else (specs, sspecs, P())
            ),
        )
    else:
        smap_kwargs = dict(
            mesh=mesh,
            in_specs=(specs, P("dp", None), P("dp", None)),
            out_specs=(
                (specs, P(), P()) if debug_invariants else (specs, P())
            ),
        )
    if schedule == "1f1b":
        # the vma checker cannot host the manual backward: the per-tick
        # lax.switch takes DIFFERENT branches on different devices, and
        # checked vma auto-inserts transpose collectives inside those
        # branches — communication inside divergent control flow, the
        # exact deadlock the 1F1B design rule exists to prevent
        # (observed: half the devices parked in a loop ppermute, half in
        # an inserted allreduce).  check_vma=False keeps every
        # collective at the hand-placed, uniform positions; the tp-psum
        # transpose the checker would have placed is supplied by
        # _psum_identity_bwd instead, and correctness is pinned by the
        # exact-equivalence test against gpipe.
        smap_kwargs["check_vma"] = False
    if adam is not None:
        fn = jax.jit(
            shard_map(zero_step, **smap_kwargs),
            donate_argnums=(0, 1),
        )
    else:
        fn = jax.jit(
            shard_map(step, **smap_kwargs),
            donate_argnums=(0,),
        )

    def _stacked(params):
        stacked = stack_params(params)
        if V > 1:
            # commit the layers in device-major chunk order so the
            # contiguous pp shard IS each device's round-robin chunks
            perm = np.asarray(interleave_layer_order(cfg.n_layers, pp, V))
            stacked = {
                **stacked,
                "layers": {
                    k: jnp.take(a, perm, axis=0)
                    for k, a in stacked["layers"].items()
                },
            }
        return stacked

    def shard(params):
        # map over SPECS first: PartitionSpec is a tuple subclass, so it
        # must be the is_leaf-guarded tree or jax flattens it.  Specs
        # are normalized at placement (trailing Nones stripped) so the
        # placed tree carries the SAME sharding spelling the step's
        # outputs do — see transformer.normalize_spec (the resume-
        # divergence / double-compile fix).
        from .transformer import normalize_spec

        return jax.tree.map(
            lambda s, p_: jax.device_put(
                jnp.array(p_, copy=True),
                NamedSharding(mesh, normalize_spec(s)),
            ),
            specs, _stacked(params),
            is_leaf=lambda x: isinstance(x, P),
        )

    if adam is None:
        return fn, shard

    from ..parallel.zero import init_zero_state

    def init_state(params):
        # the state layouts (incl. master-weight slices) follow the SAME
        # stacked/permuted form the training step sees
        return init_zero_state(
            _stacked(params), specs, mesh,
            master_weights=adam.master_weights,
        )

    return fn, shard, init_state
