"""End-to-end distributed training with checkpoint/resume.

The flagship loop: the dp x tp transformer training step (every
cross-device edge an accl_tpu collective) driven over a mesh, with
orbax-backed checkpointing — save on an interval, resume after a restart.
The reference has no checkpoint/resume at all (SURVEY.md §5: "none —
library, not trainer"); this closes that aux-subsystem gap for the
framework's trainer surface.

Runnable anywhere:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m accl_tpu.examples.train --steps 20 --ckpt-dir /tmp/ckpt

Re-running the same command resumes from the last saved step.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np


def _ocp():
    import orbax.checkpoint as ocp

    return ocp


def train(
    steps: int = 20,
    ckpt_dir: Optional[str] = None,
    save_every: int = 10,
    dp: Optional[int] = None,
    tp: int = 2,
    seed: int = 0,
    log_every: int = 5,
    platform: Optional[str] = None,
    optimizer: str = "sgd",
    parallelism: str = "dp_tp",
    data: Optional[str] = None,
    accum_steps: int = 1,
    clip_grad_norm: Optional[float] = None,
    master_weights: bool = False,
    dtype: str = "float32",
    n_experts: int = 0,
    ep: int = 1,
    v_stages: int = 1,
    pp_schedule: str = "gpipe",
):
    """Train the flagship transformer.

    ``data`` points at an ``ACCLTOK1`` token file (see
    ``accl_tpu.data.write_token_file``): batches then come from the
    native prefetching loader — deterministic per (file, seed, step), so
    checkpoint resume consumes the exact stream an uninterrupted run
    would (the loader seeks to the resumed step).  Without ``data``,
    synthetic random tokens keyed by (seed, step) keep the same
    resume-exactness property.

    ``optimizer="zero_adam"`` switches the step to the ZeRO-sharded Adam
    (fp32 moments living 1/dp per chip, ``parallel/zero.py``); its
    optimizer state checkpoints and resumes alongside the params.
    ``accum_steps``/``clip_grad_norm``/``master_weights`` (zero_adam
    only) enable gradient accumulation, global-L2-norm clipping, and the
    fp32 master-weight track; ``dtype="bfloat16"`` trains bf16 params
    (pair with master_weights — bf16's ulp otherwise swallows small
    updates).

    ``parallelism="context"`` trains with context parallelism: the tp
    axis becomes the sequence ring (striped ring attention inside the
    blocks, activations sequence-sharded end-to-end).

    ``n_experts`` switches every block's FFN to the expert-parallel MoE
    (router aux in the loss).  Experts ride dp by default; ``ep > 1``
    un-welds them onto a DEDICATED expert axis of a (dp, ep, tp) mesh
    (the batch shards over dp x ep).  MoE composes with
    parallelism="context" (long-context MoE: expert a2a + K/V ring on
    different axes) but not with "pipeline".

    ``parallelism="pipeline"`` trains over the composed pp x dp x tp mesh
    (``models/composed.py``: pipeline stages of tp-sharded blocks,
    microbatched dp-sharded batch — pp=2, microbatches=2); params
    checkpoint in stacked form.  Composes with ``optimizer="zero_adam"``
    (ZeRO-1 moments nested inside the stage sharding, clipping and
    master weights included).  ``v_stages > 1`` switches to
    the interleaved virtual-stage schedule (that many round-robin layer
    chunks per pp rank, 1/v_stages the pipeline bubble; the model grows
    to 2 * v_stages layers so every chunk holds a layer, and checkpoints
    are layout-compatible only with the same --v-stages).

    Returns ``(steps_completed, final_loss)``; ``final_loss`` is ``None``
    when a restored checkpoint already covers the requested ``steps``
    (nothing ran)."""
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ..models import (
        TransformerConfig,
        init_params,
        make_sharded_train_step,
    )
    from ..parallel import AdamConfig, make_zero_train_step

    devs = jax.devices()
    use_pp = parallelism == "pipeline"
    if parallelism not in ("dp_tp", "context", "pipeline"):
        raise ValueError(f"unknown parallelism {parallelism!r}")
    if use_pp and accum_steps != 1:
        raise ValueError(
            "parallelism='pipeline' accumulates through its "
            "microbatches; accum_steps is a dp_tp/context knob"
        )
    if (
        accum_steps != 1 or clip_grad_norm is not None or master_weights
    ) and optimizer != "zero_adam":
        raise ValueError(
            "accum_steps/clip_grad_norm/master_weights require "
            "optimizer='zero_adam'"
        )
    if dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown dtype {dtype!r}")
    pp = 2 if use_pp else 1
    if use_pp and len(devs) < 2:
        raise ValueError(
            "parallelism='pipeline' needs >= 2 devices (pp=2); this host "
            f"exposes {len(devs)}"
        )
    if ep > 1 and not n_experts:
        raise ValueError("--ep > 1 requires --n-experts")
    if ep > 1 and use_pp:
        raise ValueError("--ep does not combine with parallelism='pipeline'")
    if v_stages > 1 and not use_pp:
        raise ValueError("--v-stages requires parallelism='pipeline'")
    if pp_schedule != "gpipe" and not use_pp:
        raise ValueError("--pp-schedule requires parallelism='pipeline'")
    if ep > len(devs):
        raise ValueError(
            f"--ep {ep} needs at least that many devices; this host "
            f"exposes {len(devs)} (the dp x ep x tp mesh cannot fold)"
        )
    tp = min(tp, max(len(devs) // (pp * ep), 1))  # 1-device hosts: tp=1
    if dp is None:
        dp = max(len(devs) // (pp * ep * tp), 1)
    if dp * ep * tp * pp > len(devs):
        raise ValueError(
            f"pp ({pp}) x dp ({dp}) x ep ({ep}) x tp ({tp}) = "
            f"{pp * dp * ep * tp} exceeds the {len(devs)} devices this "
            "host exposes — lower --dp or --ep (tp self-clamps)"
        )
    if use_pp:
        mesh = Mesh(
            np.array(devs[: pp * dp * tp]).reshape(pp, dp, tp),
            ("pp", "dp", "tp"),
        )
    elif ep > 1:
        # dedicated expert axis: experts shard over ep, batch over dp x ep
        mesh = Mesh(
            np.array(devs[: dp * ep * tp]).reshape(dp, ep, tp),
            ("dp", "ep", "tp"),
        )
    else:
        mesh = Mesh(np.array(devs[: dp * tp]).reshape(dp, tp), ("dp", "tp"))

    heads = max(4, tp)
    heads += (-heads) % tp  # tp must divide heads (and so d_model/d_ff)
    cfg = TransformerConfig(
        vocab=128, d_model=16 * heads, n_heads=heads,
        # interleaved pipeline: every virtual stage needs a layer
        n_layers=2 * v_stages if use_pp else 2,
        d_ff=32 * heads, max_seq=32,
        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
        context_parallel=parallelism == "context",
        n_experts=n_experts,
        moe_mesh_axis="ep" if ep > 1 else "dp",
    )
    use_zero = optimizer == "zero_adam"
    # per-dp-rank batch: 2 samples per MICRObatch, so accumulation grows
    # the effective batch (its purpose) instead of shrinking microbatches
    per_rank_b = 2 * accum_steps
    params0 = init_params(jax.random.PRNGKey(seed), cfg)
    if use_pp:
        from ..models import make_pp_train_step

        if use_zero:
            step_fn, shard, init_state = make_pp_train_step(
                cfg, mesh, num_microbatches=2, v_stages=v_stages,
                schedule=pp_schedule,
                adam=AdamConfig(
                    lr=0.01, clip_grad_norm=clip_grad_norm,
                    master_weights=master_weights,
                ),
            )
            params = shard(params0)
            opt_state = init_state(params0)
        else:
            step_fn, shard = make_pp_train_step(
                cfg, mesh, num_microbatches=2, lr=0.1, v_stages=v_stages,
                schedule=pp_schedule,
            )
            params = shard(params0)
            opt_state = None
    elif use_zero:
        step_fn, shard, init_state = make_zero_train_step(
            cfg, mesh,
            AdamConfig(
                lr=0.01, clip_grad_norm=clip_grad_norm,
                master_weights=master_weights,
            ),
            accum_steps=accum_steps,
        )
        params = shard(params0)
        opt_state = init_state(params0)
    else:
        step_fn, shard = make_sharded_train_step(cfg, mesh, lr=0.1)
        params = shard(params0)
        opt_state = None
    def ckpt_tree():
        # ONE definition of the checkpoint layout: the restore reference
        # and every save must agree or orbax restore breaks
        return (
            {"params": params, "opt_state": opt_state}
            if use_zero else params
        )

    start_step = 0

    ckptr = None
    if ckpt_dir:
        ocp = _ocp()

        ckpt_dir = os.path.abspath(ckpt_dir)
        ckptr = ocp.CheckpointManager(
            ckpt_dir,
            options=ocp.CheckpointManagerOptions(max_to_keep=2),
        )
        latest = ckptr.latest_step()
        if latest is not None:
            # restore with the sharded structure as the reference tree so
            # arrays come back on-mesh
            try:
                restored = ckptr.restore(
                    latest, args=ocp.args.StandardRestore(ckpt_tree())
                )
            except Exception as e:
                # only tree-structure mismatches suggest the optimizer
                # flag; anything else (corrupt file, sharding change,
                # orbax skew) must surface as itself
                msg = str(e).lower()
                if "structure" in msg or "tree" in msg:
                    raise ValueError(
                        f"failed to restore {ckpt_dir} at step {latest} "
                        f"with optimizer={optimizer!r}, "
                        f"parallelism={parallelism!r}, "
                        f"master_weights={master_weights}, "
                        f"n_experts={n_experts}; was the checkpoint "
                        "saved with a different --optimizer, "
                        "--parallelism, --master-weights, or "
                        "--n-experts? (pipeline mode stores layers "
                        "STACKED, dp_tp stores them as a list; master "
                        "weights add a 'w' subtree to the optimizer "
                        "state; MoE replaces w1/w2 with a 'moe' "
                        "subtree)"
                    ) from e
                raise
            if use_zero:
                params, opt_state = restored["params"], restored["opt_state"]
            else:
                params = restored
            start_step = latest + 1
            print(f"resumed from step {latest} in {ckpt_dir}")

    if start_step >= steps:
        print(
            f"nothing to do: checkpoint already at step {start_step - 1}, "
            f"requested --steps {steps}"
        )
        if ckptr is not None:
            ckptr.close()
        return start_step, None

    loss = None
    loader = None
    if data is not None:
        from ..data import TokenLoader

        # single-controller: one loader feeds the whole dp-sharded batch
        # (multi-process deployments shard via shard/num_shards instead)
        loader = TokenLoader(
            data, batch=per_rank_b * dp * ep, seq=cfg.max_seq, seed=seed,
            start_step=start_step,
        )
    try:
      for it in range(start_step, steps):
        if loader is not None:
            t_np, g_np, got_step = loader.next()
            if got_step != it:
                # not an assert: stripped under `python -O`, which would
                # turn a resume/seek mismatch into silent wrong-data
                # training
                raise RuntimeError(
                    f"loader/step misalignment: loader at {got_step}, "
                    f"trainer at {it}"
                )
            # validate the WHOLE window: targets carry one position the
            # tokens array doesn't (the shifted-off last column)
            if max(int(t_np.max()), int(g_np.max())) >= cfg.vocab:
                raise ValueError(
                    f"token file carries ids >= vocab ({cfg.vocab})"
                )
            tokens = jnp.asarray(t_np)
            targets = jnp.asarray(g_np)
        else:
            # per-step data stream keyed by (seed, step): a resumed run
            # consumes the exact token stream an uninterrupted run would,
            # so losses stay bit-comparable across restarts
            rng = np.random.default_rng([seed, it])
            # per-dp-rank batch of 2 per microbatch — which also divides
            # the pipeline mode's num_microbatches=2 exactly
            tokens = jnp.asarray(
                rng.integers(
                    0, cfg.vocab, (per_rank_b * dp * ep, cfg.max_seq)
                ),
                jnp.int32,
            )
            targets = jnp.roll(tokens, -1, axis=1)
        if use_zero:
            params, opt_state, loss = step_fn(
                params, opt_state, tokens, targets
            )
        else:
            params, loss = step_fn(params, tokens, targets)
        loss = float(loss)
        if log_every and (it + 1) % log_every == 0:
            print(f"step {it + 1}/{steps} loss {loss:.4f}", flush=True)
        if ckptr is not None and (it + 1) % save_every == 0:
            ckptr.save(it, args=_ocp().args.StandardSave(ckpt_tree()))
    finally:
      if loader is not None:
        loader.close()  # even when a step raises: stop the prefetch thread
    if ckptr is not None:
        ckptr.save(steps - 1, args=_ocp().args.StandardSave(ckpt_tree()))
        ckptr.wait_until_finished()
        ckptr.close()
    return steps, loss  # loss is the last completed step's global loss


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", default=None)
    ap.add_argument(
        "--optimizer", default="sgd", choices=["sgd", "zero_adam"]
    )
    ap.add_argument(
        "--parallelism", default="dp_tp",
        choices=["dp_tp", "context", "pipeline"],
    )
    ap.add_argument(
        "--n-experts", type=int, default=0,
        help="MoE: expert count (sharded over dp, or over --ep); "
        "0 = dense FFN",
    )
    ap.add_argument(
        "--ep", type=int, default=1,
        help="dedicated expert-parallel mesh axis size (>1 un-welds "
        "experts from dp onto a (dp, ep, tp) mesh; requires --n-experts)",
    )
    ap.add_argument(
        "--v-stages", type=int, default=1,
        help="interleaved virtual stages per pipeline rank "
        "(parallelism=pipeline; bubble drops by this factor)",
    )
    ap.add_argument(
        "--pp-schedule", default="gpipe", choices=["gpipe", "1f1b"],
        help="composed pipeline backward: autodiff-through-GPipe or the "
        "hand-scheduled 1F1B (min(pp,M)-input stash + recompute)",
    )
    ap.add_argument(
        "--data", default=None,
        help="ACCLTOK1 token file (native prefetching loader); "
        "default: synthetic tokens",
    )
    ap.add_argument(
        "--accum-steps", type=int, default=1,
        help="gradient accumulation microbatches per step (zero_adam)",
    )
    ap.add_argument(
        "--clip-grad-norm", type=float, default=None,
        help="global-L2-norm gradient clipping (zero_adam)",
    )
    ap.add_argument(
        "--master-weights", action="store_true",
        help="fp32 master-weight track in the optimizer state (zero_adam)",
    )
    ap.add_argument(
        "--dtype", default="float32", choices=["float32", "bfloat16"],
        help="parameter/activation dtype",
    )
    args = ap.parse_args(argv)
    from ..utils import use_compile_cache

    use_compile_cache()
    train(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        save_every=args.save_every, tp=args.tp, seed=args.seed,
        platform=args.platform, optimizer=args.optimizer,
        parallelism=args.parallelism, data=args.data,
        accum_steps=args.accum_steps, clip_grad_norm=args.clip_grad_norm,
        master_weights=args.master_weights, dtype=args.dtype,
        n_experts=args.n_experts, ep=args.ep, v_stages=args.v_stages,
        pp_schedule=args.pp_schedule,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
