"""ZeRO-style optimizer-state sharding over the data-parallel axis.

Classic data parallelism allreduces gradients and keeps a full optimizer
state on every rank.  The TPU-native sharded form re-homes that exchange
onto the collectives this framework owns (SURVEY.md §2.2's fused
ring reduce-scatter + allgather, the allreduce decomposition the
reference firmware executes at c:1888-2071):

* gradients are reduced across ``dp`` once (the transpose-inserted
  allreduce of the mean loss — shard_map's varying-axis tracking places
  every tp/dp psum, so mixed replicated/tp-sharded params stay exact);
* each dp rank takes only ITS 1/dp slice of the reduced gradient into
  the update, and the fp32 Adam moments live sharded the same way —
  optimizer state costs 1/dp per chip instead of a full copy (ZeRO-1);
* the rank updates its parameter slice and **all-gathers** the result
  (the second leg of the reference's fused ring allreduce, standing
  alone).

HBM for optimizer state and update compute both drop by the dp factor;
the wire pays one extra param allgather versus classic DP.  Composes
with tensor parallelism: everything here acts on the tp-local shard.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..ops.collectives import allgather_invariant


class AdamConfig(NamedTuple):
    lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    # AdamW: decoupled weight decay applied to the parameter slice (not
    # the gradient), skipped for 1-D leaves (layernorm scales / biases)
    # per standard practice
    weight_decay: float = 0.0
    # LR schedule: linear warmup over ``warmup_steps``, then (when
    # ``decay_steps`` is set) cosine decay from the peak to
    # ``min_lr_ratio * lr`` by step ``decay_steps``; constant otherwise
    warmup_steps: int = 0
    decay_steps: Optional[int] = None
    min_lr_ratio: float = 0.0
    # global-L2-norm gradient clipping (None = off): the norm is the
    # GLOBAL one — model-parallel shards psum their squared sums over
    # tp, so every rank scales by the same factor and sharded/unsharded
    # training see the identical clipped update
    clip_grad_norm: Optional[float] = None
    # mixed precision: keep an fp32 MASTER copy of each rank's 1/dp
    # parameter slice in the optimizer state (alongside the fp32
    # moments) and update THAT; the working params are its cast.  With
    # bf16 params this is the standard TPU recipe — bf16's ~3 decimal
    # digits silently swallow updates below the param's ulp, while the
    # master track accumulates them exactly.  Costs 4 extra bytes per
    # param per dp group (sharded 1/dp like the moments).
    master_weights: bool = False


def schedule_lr(cfg: AdamConfig, step):
    """Learning rate at ``step`` (1-based, traced ok): warmup-cosine.

    The serving trainer composes this inside the jitted step, so the
    schedule costs nothing and checkpoints implicitly (step lives in the
    optimizer state)."""
    t = jnp.asarray(step, jnp.float32)
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.decay_steps is not None and cfg.decay_steps <= cfg.warmup_steps:
        raise ValueError(
            f"decay_steps ({cfg.decay_steps}) must exceed warmup_steps "
            f"({cfg.warmup_steps})"
        )
    if cfg.warmup_steps:
        lr = lr * jnp.minimum(1.0, t / float(cfg.warmup_steps))
    if cfg.decay_steps:
        span = cfg.decay_steps - cfg.warmup_steps
        prog = jnp.clip((t - cfg.warmup_steps) / span, 0.0, 1.0)
        cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        floor = cfg.min_lr_ratio
        lr = lr * (floor + (1.0 - floor) * cos)
    return lr


def _padded(n: int, dp: int) -> int:
    return -(-n // dp) * dp


def _pad_flat(x, padded: int, dtype):
    """Row-major flatten + zero-pad to ``padded`` — the shared layout
    rule for every flat dp-sliced array (moments, master weights)."""
    flat = x.reshape(-1).astype(dtype)
    if padded != flat.shape[0]:
        flat = jnp.concatenate(
            [flat, jnp.zeros((padded - flat.shape[0],), dtype)]
        )
    return flat


def _dp_slice(x, dp: int, idx):
    """This rank's 1/dp slice of ``x`` flattened-and-padded in fp32 —
    THE slice program: master-weight init and the Adam update both call
    exactly this, so their layouts cannot desynchronize."""
    padded = _padded(int(np.prod(x.shape)), dp)
    return lax.dynamic_slice_in_dim(
        _pad_flat(x, padded, jnp.float32), idx * (padded // dp),
        padded // dp,
    )


def reshard_plan(n: int, old_dp: int, new_dp: int) -> list:
    """Incremental ZeRO shard-ownership migration plan for an elastic
    membership cutover (``join_rank``/``evict_rank`` changed the dp
    world).  Pure integer math over the ``_dp_slice`` layout rule — no
    jax, no mesh — so every member derives the identical plan from the
    agreed (old_dp, new_dp) pair with zero wire bytes, the
    ``Communicator.grow`` slot-ordering discipline.

    Returns one entry per NEW dp rank: ``{"rank", "begin", "end",
    "fetch": [{"src", "begin", "end"}, ...]}`` where ``fetch`` lists
    the logical index ranges (within [0, n)) the rank must pull from
    each OLD owner whose slice overlaps its new one; a range whose old
    owner IS the rank itself is omitted — already local, nothing moves.
    That makes the migration incremental by construction: each fetch
    range is an independent bucket the facade schedules behind its own
    drain point, not a global stop-the-world re-slice."""
    n = int(n)
    old_dp, new_dp = int(old_dp), int(new_dp)
    if n < 0 or old_dp < 1 or new_dp < 1:
        raise ValueError("reshard_plan needs n >= 0 and dp sizes >= 1")
    old_shard = _padded(n, old_dp) // old_dp
    new_shard = _padded(n, new_dp) // new_dp
    plan = []
    for j in range(new_dp):
        begin = min(j * new_shard, n)
        end = min(begin + new_shard, n)
        fetch = []
        i = begin
        while i < end:
            src = min(i // old_shard, old_dp - 1) if old_shard else 0
            seg_end = min(end, (src + 1) * old_shard) if old_shard else end
            if src != j:
                fetch.append({"src": src, "begin": i, "end": seg_end})
            i = seg_end
        plan.append({"rank": j, "begin": begin, "end": end,
                     "fetch": fetch})
    return plan


def _spec_axes(spec) -> tuple:
    """Mesh axes a PartitionSpec shards over, flattened in order."""
    axes = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)


def _state_spec(pspec, dp_axis: str):
    """Sharding for one leaf's flat moment array: the dp slice axis
    nested inside whatever model-parallel axes shard the param itself —
    each (model-shard, dp-rank) pair owns a distinct 1/dp slice of ITS
    parameter shard's moments.

    A param ALREADY sharded over dp (expert-parallel MoE banks: each dp
    rank owns its experts outright) has no further dp split to take —
    its moments simply live with the expert shard."""
    axes = _spec_axes(pspec)
    if dp_axis in axes:
        return P(tuple(axes))
    return P(tuple(axes) + (dp_axis,)) if axes else P(dp_axis)


def init_zero_state(params, specs, mesh: Mesh, dp_axis: str = "dp",
                    master_weights: bool = False):
    """Sharded (m, v) fp32 moments + step counter: per leaf, a flat array
    whose sharding nests the param's own model-parallel axes around the
    dp slice axis, so every rank materializes exactly its 1/dp of its
    parameter shard's moments.  ``master_weights`` adds ``w``: the fp32
    master copy of each rank's parameter slice, laid out identically —
    built by the SAME pad/slice program the update uses, so the two can
    never disagree on layout."""
    dp = mesh.shape[dp_axis]

    def zeros_for(p, pspec):
        axes = _spec_axes(pspec)
        div = 1
        for ax in axes:
            div *= mesh.shape[ax]
        local_n = int(np.prod(p.shape)) // div
        # dp-sharded params (expert banks) take no further dp split:
        # the rank's moments cover its whole expert shard
        glen = (
            local_n * div if dp_axis in axes else _padded(local_n, dp) * div
        )
        sharding = NamedSharding(mesh, _state_spec(pspec, dp_axis))
        # allocate DIRECTLY sharded: materializing the full array on one
        # device first would transiently hold dp x the steady-state
        # footprint — the exact memory this module exists to avoid
        return jnp.zeros((glen,), jnp.float32, device=sharding)

    state = {
        "m": jax.tree.map(zeros_for, params, specs),
        "v": jax.tree.map(zeros_for, params, specs),
        # committed replicated (not left uncommitted): checkpoint restore
        # reproduces the sharding it sees, and an uncommitted scalar would
        # come back single-device, clashing with the mesh-wide params
        "step": jax.device_put(
            jnp.zeros((), jnp.int32), NamedSharding(mesh, P())
        ),
    }
    if master_weights:
        is_leaf = lambda x: isinstance(x, P)
        wspecs = jax.tree.map(
            lambda sp: _state_spec(sp, dp_axis), specs, is_leaf=is_leaf
        )

        def slices(p_tree):
            dp_ = lax.axis_size(dp_axis)
            idx = lax.axis_index(dp_axis)
            is_p = lambda x: isinstance(x, P)
            pl, treedef = jax.tree.flatten(p_tree)
            sl = jax.tree.leaves(specs, is_leaf=is_p)
            out = [
                # dp-sharded leaves (expert banks): the rank's whole
                # shard IS its slice — flatten, no dp sub-slice
                p.reshape(-1).astype(jnp.float32)
                if dp_axis in _spec_axes(sp_)
                else _dp_slice(p, dp_, idx)
                for p, sp_ in zip(pl, sl)
            ]
            return jax.tree.unflatten(treedef, out)

        sharded = jax.tree.map(
            lambda p, sp: jax.device_put(
                jnp.asarray(p), NamedSharding(mesh, sp)
            ),
            params, specs,
        )
        state["w"] = jax.jit(
            shard_map(
                slices, mesh=mesh, in_specs=(specs,), out_specs=wspecs
            )
        )(sharded)
    return state


def zero_state_specs(specs, dp_axis: str = "dp",
                     master_weights: bool = False):
    """PartitionSpec pytree matching :func:`init_zero_state` (for use as
    shard_map in/out specs).  ``specs`` is the PARAM spec tree
    (PartitionSpec is a tuple subclass, so it is treated as a leaf)."""
    is_leaf = lambda x: isinstance(x, P)
    leafmap = lambda t: jax.tree.map(
        lambda s: _state_spec(s, dp_axis), t, is_leaf=is_leaf
    )
    out = {
        "m": leafmap(specs),
        "v": leafmap(specs),
        "step": P(),
    }
    if master_weights:
        out["w"] = leafmap(specs)
    return out


def clip_by_global_norm(grads, specs, max_norm: float, tp_axis=None,
                        dp_axis=None, ep_axis=None, pp_axis=None):
    """Scale ``grads`` so their GLOBAL L2 norm is at most ``max_norm`` —
    inside shard_map.  Leaves whose spec shards over ``tp_axis`` (or
    ``dp_axis``/``ep_axis`` — expert-parallel MoE banks; ``pp_axis`` —
    pipeline layer stacks) hold disjoint slices: their local squared
    sums psum across those axes so each element counts exactly once;
    replicated leaves already carry the full gradient on every rank.
    Dp-REPLICATED grads are dp-reduced by the time this runs (the loss
    mean's transpose placed that psum), so they need no dp exchange.
    Returns ``(clipped_grads, global_norm)``."""
    is_leaf = lambda x: isinstance(x, P)
    gleaves = jax.tree.leaves(grads)
    sleaves = jax.tree.leaves(specs, is_leaf=is_leaf)
    # bucket leaves by which mesh axes shard them: each bucket's local
    # squared sum psums over exactly its axes
    buckets: dict = {}
    for g, s in zip(gleaves, sleaves):
        axes = tuple(
            a for a in (tp_axis, dp_axis, ep_axis, pp_axis)
            if a is not None and a in _spec_axes(s)
        )
        ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
        buckets[axes] = buckets.get(axes, 0.0) + ss
    total = jnp.zeros((), jnp.float32)
    for axes, ss in buckets.items():
        for a in axes:
            ss = lax.psum(ss, a)
        total = total + ss
    norm = jnp.sqrt(total)
    # scale = 1 when norm <= max_norm, else max_norm / norm
    scale = (max_norm / jnp.maximum(norm, max_norm)).astype(jnp.float32)
    clipped = jax.tree.map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads
    )
    return clipped, norm


def zero_adam_update(params, grads, state, dp_axis: str, cfg: AdamConfig,
                     specs=None):
    """One sharded Adam step — runs INSIDE shard_map.

    ``params``/``grads`` are the rank's (tp-)local values, replicated
    across ``dp``; ``state`` leaves are the rank's 1/dp moment slices.
    ``specs`` (the param PartitionSpec tree) marks leaves ALREADY
    sharded over dp (expert-parallel MoE banks): those take the
    rank-local update on the whole shard — no dp slice, no allgather
    (each rank owns its experts outright, and their gradients arrive
    fully summed through the dispatch all-to-all's transpose).
    Returns (new_params, new_state).
    """
    dp = lax.axis_size(dp_axis)
    idx = lax.axis_index(dp_axis)
    step = state["step"] + 1
    bc1 = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    lr_t = schedule_lr(cfg, step)

    master = state.get("w")

    def leaf(p, g, m, v, w, dp_local):
        n = int(np.prod(p.shape))
        if dp_local:
            # expert-bank leaf: the whole local shard updates in place
            gs = g.reshape(-1).astype(jnp.float32)
            m = cfg.b1 * m + (1.0 - cfg.b1) * gs
            v = cfg.b2 * v + (1.0 - cfg.b2) * gs * gs
            shard = (
                p.reshape(-1).astype(jnp.float32) if w is None else w
            )
            upd = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
            if cfg.weight_decay and p.ndim > 1:
                upd = upd + cfg.weight_decay * shard
            new_w = shard - lr_t * upd
            return new_w.astype(p.dtype).reshape(p.shape), m, v, new_w
        # this rank's slice of the (already dp-reduced) mean gradient
        gs = _dp_slice(g, dp, idx)
        m = cfg.b1 * m + (1.0 - cfg.b1) * gs
        v = cfg.b2 * v + (1.0 - cfg.b2) * gs * gs
        mhat = m / bc1
        vhat = v / bc2
        # this rank's parameter slice (of the PADDED flat, so the last
        # rank's slice never clamps into its neighbor's), updated
        # locally.  With master weights the fp32 slice in the state IS
        # the source of truth (the bf16 param is its lossy cast — slicing
        # p instead would re-quantize every step and lose the small
        # updates the master track exists to keep).
        shard = _dp_slice(p, dp, idx) if w is None else w
        upd = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay and p.ndim > 1:
            # AdamW decoupled decay on the param slice itself; 1-D
            # leaves (ln scales, biases) are conventionally exempt
            upd = upd + cfg.weight_decay * shard
        new_w = shard - lr_t * upd
        new_shard = new_w.astype(p.dtype)
        # rebuild the full parameter from the slices.  The plain
        # lax.all_gather can't be used: its output is conservatively
        # dp-varying, which shard_map's replication checker rejects for a
        # P(None)-spec'd output; allgather_invariant is the
        # Varying->Invariant form at allgather wire volume.
        new_flat = allgather_invariant(new_shard, dp_axis)
        return new_flat[:n].reshape(p.shape), m, v, new_w

    is_p = lambda x: isinstance(x, P)
    pl, st = jax.tree.flatten(params)
    gl = jax.tree.leaves(grads)
    ml = jax.tree.leaves(state["m"])
    vl = jax.tree.leaves(state["v"])
    wl = jax.tree.leaves(master) if master is not None else [None] * len(pl)
    if specs is None:
        dl = [False] * len(pl)
    else:
        dl = [
            dp_axis in _spec_axes(sp_)
            for sp_ in jax.tree.leaves(specs, is_leaf=is_p)
        ]
    flat_out = [
        leaf(p, g, m, v, w, d)
        for p, g, m, v, w, d in zip(pl, gl, ml, vl, wl, dl)
    ]
    new_params = jax.tree.unflatten(st, [t[0] for t in flat_out])
    new_state = {
        "m": jax.tree.unflatten(st, [t[1] for t in flat_out]),
        "v": jax.tree.unflatten(st, [t[2] for t in flat_out]),
        "step": step,
    }
    if master is not None:
        new_state["w"] = jax.tree.unflatten(st, [t[3] for t in flat_out])
    return new_params, new_state


def make_zero_train_step(
    model_cfg,
    mesh: Mesh,
    adam: AdamConfig = AdamConfig(),
    accum_steps: int = 1,
):
    """dp x tp train step with ZeRO-sharded Adam: returns
    ``(step, shard_params, init_state)``; ``step(params, state, tokens,
    targets) -> (params, state, loss)``.  Donates params AND state (both
    update in place on device).

    ``accum_steps > 1`` runs gradient accumulation: each rank's local
    batch is split into that many microbatches, scanned with one
    forward/backward each, and the AVERAGED gradient feeds a single
    optimizer step — the effective batch grows by the factor while
    activation memory stays at one microbatch (HBM, not FLOPs, is the
    TPU ceiling).  ``adam.clip_grad_norm`` applies global-L2-norm
    clipping to the (accumulated) gradient before the update."""
    from ..constants import ReduceFunction
    from ..models.transformer import (
        _batch_entry,
        _check_moe_mesh,
        _data_axes,
        _mean_over_axes,
        _reject_untrainable_attention,
        _shard_params,
        loss_fn,
        param_specs,
    )
    from ..ops import collectives

    _reject_untrainable_attention(model_cfg)
    _check_moe_mesh(model_cfg, mesh)
    schedule_lr(adam, 1)  # fail fast on decay/warmup misconfiguration

    specs = param_specs(model_cfg)
    sspecs = zero_state_specs(specs, master_weights=adam.master_weights)
    tp = mesh.shape["tp"]
    # data axes: 'dp' plus the dedicated expert axis when the mesh has
    # one (batch shards over both; dense grads psum over both).  The
    # ZeRO moment slices stay dp-sharded (replicated over ep): ep's job
    # is expert placement, dp's is the optimizer-state split.
    data_axes = _data_axes(model_cfg, mesh)
    denom = 1
    for a in data_axes:
        denom *= mesh.shape[a]
    ep_ax = (
        model_cfg.moe_mesh_axis
        if model_cfg.n_experts and model_cfg.moe_mesh_axis != "dp"
        else None
    )

    if accum_steps < 1:
        raise ValueError(f"accum_steps ({accum_steps}) must be >= 1")

    def step(params, state, tokens, targets):
        # varying-axis tracking places every gradient psum (tp AND dp)
        # exactly where replication demands — manual placement under
        # check_vma=False gets mixed replicated/sharded params wrong
        if accum_steps == 1:

            def global_loss(p):
                local = loss_fn(p, tokens, targets, model_cfg, "tp", tp)
                return _mean_over_axes(local, data_axes, denom)

            loss, grads = jax.value_and_grad(global_loss)(params)
        else:
            b = tokens.shape[0]
            if b % accum_steps:
                raise ValueError(
                    f"per-rank batch ({b}) must divide by accum_steps "
                    f"({accum_steps})"
                )
            mb = b // accum_steps
            # differentiate at dp-VARYING params: a dp-varying microbatch
            # loss would otherwise force the vma transpose to psum every
            # microbatch's gradient back to the params' dp-invariance —
            # pvary'd primals keep each microbatch's gradient dp-LOCAL,
            # so the whole step pays ONE gradient psum after the scan
            # (accum_steps x less cross-dp wire, identical math)
            _pvary = partial(lax.pcast, to="varying")
            is_p_ = lambda x: isinstance(x, P)
            pl_, pd_ = jax.tree.flatten(params)
            sl_ = jax.tree.leaves(specs, is_leaf=is_p_)
            # data-axis-SHARDED leaves (expert banks) are already varying
            # on their axis — only the replicated axes need the cast
            def _missing(sp_):
                return tuple(
                    a for a in data_axes if a not in _spec_axes(sp_)
                )

            params_v = jax.tree.unflatten(pd_, [
                _pvary(x, _missing(sp_)) if _missing(sp_) else x
                for x, sp_ in zip(pl_, sl_)
            ])

            def micro(tok, tgt):
                return jax.value_and_grad(
                    lambda p: loss_fn(p, tok, tgt, model_cfg, "tp", tp)
                )(params_v)

            def body(carry, tt):
                acc_l, acc_g = carry
                l, g = micro(tt[0], tt[1])
                acc_g = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc_g, g
                )
                return (acc_l + l, acc_g), None

            toks = tokens.reshape(accum_steps, mb, -1)
            tgts = targets.reshape(accum_steps, mb, -1)
            # seed the carry with microbatch 0 (a fresh-zeros carry has
            # unvarying axis types, which scan would reject against the
            # dp/tp-varying gradients), then fold the rest
            l0, g0 = micro(toks[0], tgts[0])
            g0 = jax.tree.map(lambda x: x.astype(jnp.float32), g0)
            (lsum, gsum), _ = lax.scan(body, (l0, g0), (toks[1:], tgts[1:]))
            # the step's ONE cross-dp exchange.  Dp-SHARDED leaves
            # (expert banks) skip the psum: their gradients arrive
            # fully summed through the dispatch all-to-all's transpose
            # even for a dp-local loss
            loss = _mean_over_axes(lsum, data_axes, denom * accum_steps)
            is_p = lambda x: isinstance(x, P)
            gl, gd = jax.tree.flatten(gsum)
            sl = jax.tree.leaves(specs, is_leaf=is_p)
            grads = jax.tree.unflatten(gd, [
                _mean_over_axes(g, _missing(sp_), denom * accum_steps)
                for g, sp_ in zip(gl, sl)
            ])
        if adam.clip_grad_norm is not None:
            grads, _ = clip_by_global_norm(
                grads, specs, adam.clip_grad_norm, "tp", "dp", ep_ax
            )
        new_params, new_state = zero_adam_update(
            params, grads, state, "dp", adam, specs=specs
        )
        return new_params, new_state, loss

    # context parallelism: tokens/targets stripe (a global permutation,
    # outside shard_map) and sequence-shard over tp — the same entry
    # contract as the SGD maker's cp path; loss_fn's cp branch consumes
    # the rank's striped shard
    batch = _batch_entry(data_axes)
    seq_spec = (
        P(batch, "tp") if model_cfg.context_parallel else P(batch, None)
    )
    smapped = shard_map(
        step,
        mesh=mesh,
        in_specs=(specs, sspecs, seq_spec, seq_spec),
        out_specs=(specs, sspecs, P()),
    )
    if model_cfg.context_parallel:
        from ..models.ring_attention import stripe_sequence

        def outer(params, state, tokens, targets):
            return smapped(
                params,
                state,
                stripe_sequence(tokens, tp, axis=1),
                stripe_sequence(targets, tp, axis=1),
            )

        body = outer
    else:
        body = smapped
    fn = jax.jit(body, donate_argnums=(0, 1))
    return (
        fn,
        partial(_shard_params, specs=specs, mesh=mesh),
        partial(
            init_zero_state, specs=specs, mesh=mesh,
            master_weights=adam.master_weights,
        ),
    )
