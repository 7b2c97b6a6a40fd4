"""Pipeline parallelism (pp): GPipe-style microbatch streaming over a mesh
axis, the stage handoff a neighbor ``ppermute`` on ICI.

The fifth first-class sharding axis of the flagship family (dp x tp x sp x
ep x pp): each ``pp`` rank owns one contiguous span of layers; M
microbatches stream through the S stages in M + S - 1 steps, stage s
working on microbatch t - s at step t.  The inter-stage edge is the same
neighbor collective-permute the ring collectives are built from — on real
slices the activations ride one ICI hop per stage boundary.

Everything is static-shaped and uniform SPMD: every rank executes every
step, with validity predicated in data (``jnp.where``), never in
communication — the discipline that keeps XLA's collective schedule
deadlock-free (and matches the Pallas kernel tier's design rule).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def _pvary(x, axis_name):
    """Mark ``x`` varying over ``axis_name`` for shard_map's replication
    checker (loop carries initialized from constants are invariant, but
    the loop body makes them varying — the types must match up front).
    No-op data-wise."""
    return lax.pcast(x, (axis_name,), to="varying")


def pipeline_apply(
    stage_params,
    microbatches: jax.Array,
    pp_axis: str,
    stage_fn: Callable,
):
    """Run ``microbatches`` through the S-stage pipeline.

    Inside ``shard_map`` over ``pp_axis``:

    * ``stage_params``: THIS rank's stage parameters (stage ``i`` = rank
      ``i``'s layer span);
    * ``microbatches``: (M, ...) inputs to stage 0, replicated on every
      rank (only stage 0 reads them);
    * ``stage_fn(stage_params, x) -> y``: one stage's computation; input
      and output must share shape/dtype (the homogeneous-stage contract).

    Returns (M, ...) final-stage outputs, valid on the LAST stage (other
    ranks return zeros — the caller broadcasts or reads the last rank,
    like a rooted collective's DummyBuffer convention).
    """
    S = lax.axis_size(pp_axis)
    me = lax.axis_index(pp_axis)
    M = microbatches.shape[0]

    fwd = [(i, i + 1) for i in range(S - 1)]  # stage s -> s+1 edges

    def step(t, state):
        carry, outputs = state
        mb = t - me  # which microbatch this stage works on at step t
        idx = jnp.clip(mb, 0, M - 1)
        valid = (mb >= 0) & (mb < M)
        inp = jnp.where(
            me == 0, lax.dynamic_index_in_dim(microbatches, idx, 0, False),
            carry,
        )
        act = stage_fn(stage_params, inp)
        act = jnp.where(valid, act, jnp.zeros_like(act))
        # the last stage banks its result; everyone else hands off
        bank = jnp.where(valid & (me == S - 1), act, outputs[idx])
        outputs = outputs.at[idx].set(bank)
        # stage handoff: one ICI hop (uniform: every rank permutes every
        # step; invalid lanes carry zeros)
        return lax.ppermute(act, pp_axis, fwd), outputs

    # inits derive from the operand (vma inherited) and are additionally
    # marked pp-varying: the loop body's activations depend on this
    # rank's stage params, and the carry types must match up front
    carry = _pvary(
        jnp.zeros_like(microbatches[0]), pp_axis
    )  # activation entering me
    outputs = _pvary(jnp.zeros_like(microbatches), pp_axis)
    # the schedule is step-index-uniform, so the whole pipeline is ONE
    # compiled loop body (O(1) program size in M and S, differentiable)
    _, outputs = lax.fori_loop(
        0, M + S - 1, step, (carry, outputs), unroll=False
    )
    return outputs


def pipeline_apply_interleaved(
    stage_params,
    microbatches: jax.Array,
    pp_axis: str,
    stage_fn: Callable,
    v_stages: int,
):
    """Interleaved virtual-stage pipeline forward (Megatron-style): each
    of the S devices owns ``v_stages`` NON-contiguous chunks, assigned
    round-robin — global stage ``j`` lives on device ``j % S`` as its
    chunk ``j // S`` — so a microbatch hops device 0, 1, .., S-1, then
    WRAPS to device 0 for chunk 1, and so on through ``V*S`` stages.

    Why: the pipeline bubble is the wave-front fill/drain, one warmup
    tick per stage boundary.  With chunks 1/V the size of a monolithic
    stage, the absolute bubble shrinks to ``(S-1) * t_stage / V`` —
    below GPipe's and 1F1B's ``(S-1) * t_stage`` (1F1B flattens the
    MEMORY profile, not the bubble; interleaving attacks the bubble) —
    at the price of V x the ppermute handoffs per microbatch.

    The schedule is a per-device work QUEUE: device ``d`` at tick ``t``
    executes queue item ``q = t - d`` (idle while out of range), where
    item ``q`` decodes round-robin as round ``r = q // (V*S)``, chunk
    ``v = (q % (V*S)) // S``, lane ``i = q % S``, microbatch
    ``m = r*S + i``.  Every producer runs exactly one tick before its
    consumer on the NEXT ring device, so the handoff is ONE uniform
    neighbor ppermute per tick (the wrap edge S-1 -> 0 carries the
    chunk boundary) — same static-shape, validity-in-data discipline as
    :func:`pipeline_apply`.  Total ticks: ``M*V + S - 1`` of cost
    ``t_stage / V`` each.

    Requires ``M % S == 0`` (microbatches stream in rounds of S — the
    standard interleaved-schedule constraint).  ``stage_params`` leaves
    carry a leading ``(V,)`` chunk dim.  Returns (M, ...) final-stage
    outputs, valid on the LAST device (zeros elsewhere), like
    :func:`pipeline_apply`.
    """
    S = lax.axis_size(pp_axis)
    me = lax.axis_index(pp_axis)
    M = microbatches.shape[0]
    V = int(v_stages)
    if M % S:
        raise ValueError(
            f"interleaved schedule needs microbatches ({M}) divisible "
            f"by pipeline stages ({S})"
        )
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != V:
            # dynamic_index_in_dim CLAMPS an out-of-range chunk index —
            # a mismatch would silently skip/duplicate stages
            raise ValueError(
                f"stage_params leading chunk dim ({leaf.shape[0]}) must "
                f"equal v_stages ({V})"
            )

    ring = [(i, (i + 1) % S) for i in range(S)]  # incl. the wrap edge

    def step(t, state):
        carry, outputs = state
        q = t - me
        valid = (q >= 0) & (q < M * V)
        qc = jnp.clip(q, 0, M * V - 1)
        r = qc // (V * S)
        v = (qc % (V * S)) // S
        m = r * S + (qc % S)
        chunk = jax.tree_util.tree_map(
            lambda p: lax.dynamic_index_in_dim(p, v, 0, False), stage_params
        )
        # stage 0 of chunk 0 on device 0 reads the microbatch; everyone
        # else consumes the ring arrival from the previous tick
        inp = jnp.where(
            (me == 0) & (v == 0),
            lax.dynamic_index_in_dim(microbatches, m, 0, False),
            carry,
        )
        act = stage_fn(chunk, inp)
        act = jnp.where(valid, act, jnp.zeros_like(act))
        # the final stage (last chunk on the last device) banks its
        # result; other lanes write back what the slot already held
        bank = jnp.where(
            valid & (me == S - 1) & (v == V - 1), act, outputs[m]
        )
        outputs = outputs.at[m].set(bank)
        return lax.ppermute(act, pp_axis, ring), outputs

    carry = _pvary(jnp.zeros_like(microbatches[0]), pp_axis)
    outputs = _pvary(jnp.zeros_like(microbatches), pp_axis)
    _, outputs = lax.fori_loop(
        0, M * V + S - 1, step, (carry, outputs), unroll=False
    )
    return outputs


def pipeline_bubble_fraction(
    schedule: str, n_stages: int, n_microbatches: int, v_stages: int = 1
) -> float:
    """Idle fraction of the pipeline's per-device time budget.

    GPipe and 1F1B share the wave-front bubble ``(S-1) / (M + S - 1)``
    (1F1B bounds the activation STASH, not the bubble); the interleaved
    schedule's chunk ticks give ``(S-1) / (M*V + S - 1)`` — the same
    S-1 warmup slots, each 1/V the cost."""
    S, M, V = n_stages, n_microbatches, v_stages
    if schedule in ("gpipe", "1f1b"):
        return (S - 1) / (M + S - 1)
    if schedule == "interleaved":
        return (S - 1) / (M * V + S - 1)
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


def pipeline_loss(
    stage_params,
    microbatches: jax.Array,
    targets: jax.Array,
    pp_axis: str,
    stage_fn: Callable,
    loss_fn: Callable,
):
    """Pipeline forward + per-microbatch loss.

    ``loss_fn(final_activations, targets_mb) -> scalar``; the mean loss is
    computed on the last stage and broadcast to all pp ranks (a masked
    psum), so every rank returns the same differentiable scalar —
    ``jax.grad`` through it yields each stage's parameter gradients with
    the activation/gradient handoffs transposed onto the reverse edges
    automatically.
    """
    S = lax.axis_size(pp_axis)
    me = lax.axis_index(pp_axis)
    M = microbatches.shape[0]
    outs = pipeline_apply(stage_params, microbatches, pp_axis, stage_fn)
    per_mb = jax.vmap(loss_fn)(outs, targets)  # (M,)
    local = jnp.where(me == S - 1, per_mb.mean(), 0.0)
    return lax.psum(local, pp_axis)


def pipeline_loss_and_grads_1f1b(
    stage_params,
    microbatches: jax.Array,
    targets: jax.Array,
    pp_axis: str,
    stage_fn: Callable,
    loss_fn: Callable,
    head_params=None,
    return_input_grads: bool = False,
):
    """One-forward-one-backward (PipeDream-flush) schedule: same bubble
    fraction as GPipe for equal-cost phases ((S-1)/(M+S-1)) but the
    activation stash holds only ``min(S, M)`` in-flight microbatches
    instead of all ``M`` — the memory profile that makes large-M
    gradient accumulation affordable on HBM.

    Returns ``(loss, stage_grads)``: the same scalar ``pipeline_loss``
    yields (every rank), and THIS rank's stage-parameter gradients,
    computed by a hand-written backward interleaved with the forward.

    Schedule (tick ``t``, stage ``s``, 0-based): forward of microbatch
    ``f`` at ``t = s + f`` during warmup (``f < S - s``) and
    ``t = s + 2f`` in steady state; backward of microbatch ``b`` at
    ``t = 2S - 1 - s + 2b``.  Forward and backward ticks of one stage
    never coincide (parity), so each tick runs exactly one of
    {forward, backward, idle} under a per-device ``lax.switch`` —
    divergent control flow is fine because ALL communication (the fwd
    activation edge, the reverse gradient edge, and their validity
    flags) happens unconditionally every tick, keeping the XLA
    collective schedule uniform and deadlock-free.

    The backward recomputes the stage forward from the stashed INPUT
    (``jax.vjp`` at use time) — activation rematerialization, the same
    FLOPs-for-HBM trade ``jax.checkpoint`` makes, which is what bounds
    the stash at one microbatch input per in-flight stage.

    Two extensions let a REAL model (the composed flagship) use this
    schedule, where the pipeline is only the middle of the program:

    * ``head_params``: when given, ``loss_fn`` is called as
      ``loss_fn(head_params, y, tgt)`` and the return grows a third
      element — the loss head's parameter gradients (final layernorm,
      unembed), accumulated on the last stage and zeros elsewhere (the
      caller psums over pp);
    * ``return_input_grads=True`` appends the (M, ...) gradients of the
      stage-0 INPUTS (valid on stage 0, zeros elsewhere) — what the
      caller backpropagates through its embedding.
    """
    S = lax.axis_size(pp_axis)
    me = lax.axis_index(pp_axis)
    M = microbatches.shape[0]
    mb_shape = microbatches.shape[1:]
    K = min(S, M)  # ring-stash slots: the max in-flight forwards anywhere

    fwd_edges = [(i, i + 1) for i in range(S - 1)]
    bwd_edges = [(i + 1, i) for i in range(S - 1)]
    warm = jnp.minimum(M, S - me)

    def fwd_index(t):
        off = t - me
        is_warm = (off >= 0) & (off < warm)
        f_steady = off // 2
        is_steady = (
            (off >= 0) & (off % 2 == 0)
            & (f_steady >= S - me) & (f_steady < M)
        )
        f = jnp.where(is_warm, off, f_steady)
        return jnp.clip(f, 0, M - 1), is_warm | is_steady

    def bwd_index(t):
        q = t - (2 * S - 1 - me)
        b = q // 2
        return jnp.clip(b, 0, M - 1), (q >= 0) & (q % 2 == 0) & (b < M)

    # Loop-state zeros must carry the vma the BODY will give them, or
    # checked-vma shard_maps reject the carry/branch types — and checked
    # vma is what keeps the transpose of the stage's tp psums an
    # identity (under check_vma=False it re-sums the replicated
    # cotangent, inflating every post-allreduce gradient by tp).  ``z``
    # is a zero scalar varying exactly like the data (dp etc. on a
    # composed mesh; nothing on the toy 1-axis mesh); adding/multiplying
    # it in unions that vma into each constant zero, and _pvary adds the
    # pp axis the body's stage compute contributes.
    z = microbatches.reshape(-1)[0] * 0
    zero_mb = _pvary(
        jnp.zeros(mb_shape, microbatches.dtype) + z, pp_axis
    )
    zero_grads = jax.tree_util.tree_map(
        lambda a: a * 0 * z.astype(a.dtype), stage_params
    )
    with_head = head_params is not None

    def tick(t, state):
        fwd_carry = state["fc"]
        bwd_carry = state["bc"]
        stash = state["stash"]
        grads = state["grads"]
        loss_acc = state["loss"]
        f, do_f = fwd_index(t)
        b, do_b = bwd_index(t)

        x_f = jnp.where(
            me == 0,
            lax.dynamic_index_in_dim(microbatches, f, 0, False),
            fwd_carry,
        )
        x_b = lax.dynamic_index_in_dim(stash, b % K, 0, False)
        tgt_b = lax.dynamic_index_in_dim(targets, b, 0, False)

        def idle_branch(_):
            return {**state, "fc": zero_mb, "bc": zero_mb}

        def fwd_branch(_):
            act = stage_fn(stage_params, x_f)
            new_stash = lax.dynamic_update_index_in_dim(stash, x_f, f % K, 0)
            return {**state, "fc": act, "bc": zero_mb, "stash": new_stash}

        def bwd_branch(_):
            y, vjp = jax.vjp(stage_fn, stage_params, x_b)
            # last stage seeds the cotangent from the loss (the 1/M is
            # pipeline_loss's per-microbatch mean); upstream stages use
            # the gradient handed back on the reverse edge
            out = dict(state)
            if with_head:
                lval, (dh, g_last) = jax.value_and_grad(
                    lambda hp, yy: loss_fn(hp, yy, tgt_b), argnums=(0, 1)
                )(head_params, y)
                # the head's grads exist only where the head ran: the
                # last stage (caller psums over pp)
                out["head"] = jax.tree_util.tree_map(
                    lambda h, d: h + jnp.where(me == S - 1, d / M, 0.0),
                    state["head"], dh,
                )
            else:
                lval, g_last = jax.value_and_grad(
                    lambda yy: loss_fn(yy, tgt_b)
                )(y)
            g_y = jnp.where(me == S - 1, g_last / M, bwd_carry)
            dp, dx = vjp(g_y)
            out["grads"] = jax.tree_util.tree_map(jnp.add, grads, dp)
            out["loss"] = loss_acc + jnp.where(me == S - 1, lval, 0.0)
            if return_input_grads:
                # stage 0's dx is d(loss)/d(embedded microbatch b): bank
                # it for the caller's embedding backward
                out["ibank"] = jnp.where(
                    me == 0,
                    lax.dynamic_update_index_in_dim(
                        state["ibank"], dx, b, 0
                    ),
                    state["ibank"],
                )
            out["fc"] = zero_mb
            out["bc"] = dx
            return out

        branch = jnp.where(do_f, 1, jnp.where(do_b, 2, 0))
        state = lax.switch(
            branch, [idle_branch, fwd_branch, bwd_branch], None
        )

        # uniform communication: both edges + validity flags every tick;
        # a carry only adopts a VALID arrival (stage s+1 may not consume
        # an activation until several ticks after s produced it, and the
        # in-between permutes carry invalid zeros)
        got_act = lax.ppermute(state["fc"], pp_axis, fwd_edges)
        act_ok = lax.ppermute(do_f.astype(jnp.int32), pp_axis, fwd_edges)
        got_dx = lax.ppermute(state["bc"], pp_axis, bwd_edges)
        dx_ok = lax.ppermute(do_b.astype(jnp.int32), pp_axis, bwd_edges)
        state["fc"] = jnp.where(act_ok > 0, got_act, fwd_carry)
        state["bc"] = jnp.where(dx_ok > 0, got_dx, bwd_carry)
        return state

    state = {
        "fc": zero_mb,  # activation arriving from the previous stage
        "bc": zero_mb,  # gradient arriving from the next stage
        "stash": _pvary(
            jnp.zeros((K,) + mb_shape, microbatches.dtype) + z, pp_axis
        ),
        "grads": zero_grads,
        "loss": _pvary(
            jnp.zeros((), jnp.float32) + z.astype(jnp.float32), pp_axis
        ),
    }
    if with_head:
        state["head"] = jax.tree_util.tree_map(
            lambda h: _pvary(
                jnp.zeros(h.shape, jnp.float32)
                + z.astype(jnp.float32),
                pp_axis,
            ),
            head_params,
        )
    if return_input_grads:
        state["ibank"] = _pvary(
            jnp.zeros((M,) + mb_shape, microbatches.dtype) + z, pp_axis
        )
    state = lax.fori_loop(
        0, 2 * (M + S - 1), tick, state, unroll=False
    )
    loss = lax.psum(
        jnp.where(me == S - 1, state["loss"] / M, 0.0), pp_axis
    )
    out = (loss, state["grads"])
    if with_head:
        out = out + (state["head"],)
    if return_input_grads:
        out = out + (state["ibank"],)
    return out


def pipeline_loss_and_grads(
    stage_params,
    microbatches: jax.Array,
    targets: jax.Array,
    pp_axis: str,
    stage_fn: Callable,
    loss_fn: Callable,
    schedule: str = "gpipe",
    v_stages: int = 1,
):
    """Config-selectable pipeline backward: ``schedule="gpipe"`` is
    ``jax.grad`` through :func:`pipeline_loss` (autodiff stores one
    residual set per loop step, O(M) activations); ``"1f1b"`` is the
    hand-scheduled interleave (O(min(S, M)) stash + recompute);
    ``"interleaved"`` streams ``v_stages`` round-robin chunks per device
    (:func:`pipeline_apply_interleaved` — the bubble drops to
    ``(S-1)/V`` warmup chunk-ticks; see
    :func:`pipeline_bubble_fraction`) with autodiff backward.  All
    return the identical ``(loss, stage_grads)``."""
    if schedule != "interleaved" and v_stages != 1:
        raise ValueError(
            f"v_stages ({v_stages}) only applies to the interleaved "
            f"schedule, not {schedule!r}"
        )
    if schedule == "1f1b":
        return pipeline_loss_and_grads_1f1b(
            stage_params, microbatches, targets, pp_axis, stage_fn, loss_fn
        )
    if schedule not in ("gpipe", "interleaved"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    S = lax.axis_size(pp_axis)
    me = lax.axis_index(pp_axis)

    # differentiate the LOCAL (pre-psum) loss: inside shard_map the
    # psum's transpose re-sums the replicated cotangent, inflating every
    # gradient by S.  The last stage's masked scalar still backpropagates
    # to every stage through the transposed ppermute edges.
    def local_loss(p):
        if schedule == "interleaved":
            outs = pipeline_apply_interleaved(
                p, microbatches, pp_axis, stage_fn, v_stages
            )
        else:
            outs = pipeline_apply(p, microbatches, pp_axis, stage_fn)
        per_mb = jax.vmap(loss_fn)(outs, targets)
        return jnp.where(me == S - 1, per_mb.mean(), 0.0)

    local, grads = jax.value_and_grad(local_loss)(stage_params)
    return lax.psum(local, pp_axis), grads
