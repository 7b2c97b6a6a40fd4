"""Benchmark on real hardware: one process, prints ONE JSON line, and
exits nonzero when the device is not a TPU (``ACCL_BENCH_SMALL=1`` is the
CPU harness test), when its ``device_kind`` has no row in the peak table,
or when any leg or capture gate failed.

Headline metric (BASELINE.md): allreduce bus bandwidth with >= 2 chips
(2*(P-1)/P * bytes / t vs the reference's 100 GbE wire rate of
12.5 GB/s); on a single chip, the collective engine's datapath
throughput — a large fused ``combine`` (the reduce_ops role) — against
the reference CCLO's internal envelope of 16 GB/s (64 B/cycle @ 250 MHz,
ccl_offload_control.h:34).

Beyond the headline, the JSON carries an ``extras`` map with the
per-kernel single-chip numbers (XLA vs Pallas combine, the Pallas
compression lanes, flagship train-step MFU), a ``device`` stamp
(platform, device_kind, count) and an ``errors`` map: kernel compile/run
failures are REPORTED, never swallowed (ref bench.cpp:25-61 records
every op it sweeps) — and a run with any is a failed run.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# All timed windows run on the ns-resolution monotonic clock through ONE
# helper (utils.timing.Timer wraps time.perf_counter_ns) — the timing
# discipline audit of the telemetry PR: no time.time()-resolution
# windows anywhere in the harness (importing the package pulls no jax).
from accl_tpu.utils.timing import Timer

# ACCL_BENCH_SMALL=1 shrinks workloads ~1000x so the full bench harness can
# be smoke-tested on CPU/CI; numbers are then meaningless but every code
# path (incl. error reporting) runs.
_SMALL = bool(int(os.environ.get("ACCL_BENCH_SMALL", "0")))


def _size(n: int) -> int:
    return max(n // 1024, 1024) if _SMALL else n

def _peak_flops(device_kind: str):
    """bf16 dense peak FLOP/s of one chip, from the one peak table
    (``accl_tpu.utils.platform.DEVICE_PEAKS``, keyed by the exact
    ``device_kind``): an unknown kind raises.  The ``ACCL_BENCH_SMALL``
    harness test has no peak and no MFU."""
    if _SMALL:
        return None
    from accl_tpu.utils import device_peaks

    return device_peaks(device_kind)["bf16_flops"]


def _slope_time(timed, k1: int, k2: int) -> float:
    """Seconds per iteration from the (k2-k1) slope: warm both loop
    lengths (compile), take min-of-3 for each, difference cancels the
    host<->device dispatch overhead."""
    for k in (k1, k2):
        timed(k)
    t1 = min(timed(k1) for _ in range(3))
    t2 = min(timed(k2) for _ in range(3))
    return max((t2 - t1) / (k2 - k1), 1e-9)


def _staged_copies(base):
    """Generator of DISTINCT-content copies of ``base`` (1/128 scale
    steps, exact in f32/bf16).  Every copy is committed (blocked) before
    it is handed out, so staging cost can never land inside a timed
    window, and no two timed dispatches share an operand, so a timing
    never rests on what some layer does with a repeated (executable,
    operands) pair.  A locally attached chip re-executes a repeat like
    any other dispatch; the distinct operands cost nothing and keep the
    loops independent of that.  ONE definition for every bench."""
    import itertools

    for i in itertools.count(1):
        x = base * (1.0 + i / 128.0)
        x.block_until_ready()
        yield x


def _combine_slope_bench(combine_fn) -> float:
    """Slope-timed combine datapath GB/s: a device-side fori_loop amortizes
    dispatch; the K2-K1 slope cancels the host<->device roundtrip so only
    on-chip time per combine remains."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    n = _size(64 * 1024 * 1024)  # 256 MB per operand, fp32
    a = jnp.ones((n,), jnp.float32)
    b = jnp.full((n,), 1.0, jnp.float32)

    @partial(jax.jit, static_argnums=2)
    def loop(a, b, k):
        return lax.fori_loop(0, k, lambda i, acc: combine_fn(acc, b), a)

    staged = _staged_copies(a)

    def timed(k):
        a_k = next(staged)  # distinct content per dispatch
        with Timer() as t:
            out = loop(a_k, b, k)
            float(out[0])  # forced readback: completion barrier
        return t.elapsed_ns() / 1e9

    per_iter = _slope_time(timed, *((2, 6) if _SMALL else (10, 110)))
    moved = 3 * n * 4  # two reads + one write per combine
    return moved / per_iter / 1e9


def _bench_combine_xla() -> float:
    return _combine_slope_bench(lambda acc, b: acc + b)


def _bench_combine_pallas() -> float:
    """Same slope harness, the combine being the Pallas reduce_ops kernel
    in its in-place (accumulate) form — the result aliases the operand's
    HBM pages, the same a <- a+b the XLA loop performs, minus the third
    stream.  Hand-written dataplane vs XLA's fusion on the identical op."""
    from accl_tpu.ops.pallas import combine as pallas_combine

    return _combine_slope_bench(
        lambda acc, b: pallas_combine(acc, b, accumulate=True)
    )


def _bench_cast_pallas(stochastic: bool = False) -> float:
    """Compression-lane bandwidth: the Pallas cast kernel (f32<->bf16, the
    hp_compression role).  Each loop iteration is a down-cast + up-cast
    round trip (12 bytes moved per element); slope timing as above."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    from accl_tpu.ops.pallas import cast

    n = _size(32 * 1024 * 1024)  # 128 MB fp32
    x = jnp.ones((n,), jnp.float32)

    def body(i, acc):
        y = cast(acc, jnp.bfloat16, stochastic=stochastic, seed=7)
        return cast(y, jnp.float32)

    @partial(jax.jit, static_argnums=1)
    def loop(x, k):
        return lax.fori_loop(0, k, body, x)

    staged = _staged_copies(x)

    def timed(k):
        x_k = next(staged)  # distinct content per dispatch
        with Timer() as t:
            out = loop(x_k, k)
            float(out[0])
        return t.elapsed_ns() / 1e9

    per_iter = _slope_time(timed, *((2, 6) if _SMALL else (4, 24)))
    moved = n * (4 + 2) + n * (2 + 4)  # down + up round trip
    return moved / per_iter / 1e9


def _bench_quant_int8_pallas() -> float:
    """int8 wire-quantization lane (quantize + dequantize round trip)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from functools import partial

    from accl_tpu.ops.pallas import dequantize_int8, quantize_int8

    n = _size(32 * 1024 * 1024)
    x = jnp.linspace(-3.0, 3.0, n, dtype=jnp.float32)

    def body(i, acc):
        v, s, cnt = quantize_int8(acc)
        return dequantize_int8(v, s, cnt, acc.shape, acc.dtype)

    @partial(jax.jit, static_argnums=1)
    def loop(x, k):
        return lax.fori_loop(0, k, body, x)

    staged = _staged_copies(x)

    def timed(k):
        x_k = next(staged)  # distinct content per dispatch
        with Timer() as t:
            out = loop(x_k, k)
            float(out[0])
        return t.elapsed_ns() / 1e9

    per_iter = _slope_time(timed, *((2, 6) if _SMALL else (4, 24)))
    moved = n * (4 + 1) + n * (1 + 4)  # quantize + dequantize
    return moved / per_iter / 1e9


def _bench_attention() -> dict:
    """Forward attention latency, naive vs blockwise vs flash at a
    serving-ish shape — the per-op record behind the train_mfu delta
    (and the direct number for the flash kernel's Mosaic lowering)."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.models.transformer import _attention

    if _SMALL:
        B, H, T, D, iters = 1, 2, 256, 64, 3
    else:
        B, H, T, D, iters = 4, 16, 2048, 128, 20
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, H, T, D), jnp.bfloat16)
    # one DISTINCT operand per timed iteration (see _staged_copies); the
    # multiplier step is 1/128 = 2^-7, exactly representable in bf16's 8
    # mantissa bits, so every operand differs in CONTENT as well as
    # buffer identity (1 + 0.001*i would round back to a handful of
    # values)
    qs = [q * (1.0 + (i + 1) / 128.0) for i in range(iters)]
    for x in qs:
        x.block_until_ready()
    flops = 4.0 * B * H * T * T * D  # qk^T + pv, causal halves both

    out = {}
    for impl in ("naive", "blockwise", "flash"):
        fn = jax.jit(lambda a, b, c, i=impl: _attention(a, b, c, impl=i))
        fn(q, q, q).block_until_ready()  # compile
        with Timer() as t:
            for it in range(iters):
                r = fn(qs[it], q, q)
            r.block_until_ready()
        dt = t.elapsed_ns() / iters / 1e9
        out[f"attn_{impl}_us"] = round(dt * 1e6, 1)
        out[f"attn_{impl}_tflops"] = round(flops / 2 / dt / 1e12, 2)
        # fwd+bwd (the training cost): flash exercises its custom_vjp
        # backward kernel, blockwise its rematerialized scan transpose
        gfn = jax.jit(jax.grad(
            lambda a, b, c, i=impl: _attention(a, b, c, impl=i)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ))
        jax.block_until_ready(gfn(q, q, q))  # compile
        with Timer() as t:
            for it in range(iters):
                r = gfn(qs[it], q, q)
            jax.block_until_ready(r)
        dt = t.elapsed_ns() / iters / 1e9
        out[f"attn_{impl}_grad_us"] = round(dt * 1e6, 1)
    return out


def _bench_train_mfu(
    small: bool = False, attention: str = "auto", seq: int = 1024,
    fused: bool = False,
) -> dict:
    if fused:
        # the fused variant: the train step's grad-exchange + optimizer
        # phase through the facade, fused slots vs host round-trip
        return _bench_train_fused(small=small)
    """Flagship train-step MFU on the local devices: one dp x tp=1 sharded
    SGD step on the bf16 transformer; FLOPs from XLA's own cost analysis
    of the compiled step.  ``attention`` picks the lowering — "auto" (the
    flagship default: naive below T=1024; from T >= 1024 the Pallas
    flash kernel on-chip while K/V fit the VMEM gate, measured crossover
    since the block-512 kernel landed) vs an explicit
    "blockwise"/"naive", the with/without record.  ``seq=4096`` is the
    long-context record: naive would OOM on score residuals there, so
    the fused lowerings are the only entrants."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from accl_tpu.models import (
        TransformerConfig,
        init_params,
        make_sharded_train_step,
    )

    ndev = len(jax.devices())
    if small:  # CPU smoke-test path
        cfg = TransformerConfig(
            vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq=64, dtype=jnp.float32, attention=attention,
        )
        batch, seq = 2 * ndev, 64
    else:
        # big-matmul regime: d_model 4096 keeps the MXU fed (61% MFU on
        # v5e vs 30% at d_model 1024).  cfg.remat stays off; with an
        # explicit attention="blockwise" the per-q-block checkpoint makes
        # cost-analysis FLOPs include its backward recompute (~1% at
        # T=1024) — compare against the recompute-free forms when
        # reading the number
        cfg = TransformerConfig(
            vocab=32768, d_model=4096, n_heads=32, n_layers=6, d_ff=16384,
            max_seq=seq, dtype=jnp.bfloat16, attention=attention,
        )
        # keep tokens/step comparable across seq lengths (8K per device)
        batch = max(8 * 1024 // seq, 1) * ndev
    mesh = Mesh(np.array(jax.devices()).reshape(ndev, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(cfg, mesh, lr=0.01)
    params = shard(init_params(jax.random.PRNGKey(0), cfg))
    tokens = jnp.zeros((batch, seq), jnp.int32)
    targets = jnp.ones((batch, seq), jnp.int32)

    lowered = step.lower(params, tokens, targets)
    compiled = lowered.compile()
    # per-DEVICE FLOPs per step: compiled.cost_analysis() reports the
    # post-SPMD per-device module, so MFU divides by ONE chip's peak (the
    # analytic fallback computes global FLOPs and is divided by ndev to
    # stay consistent)
    flops_per_dev = (
        float((compiled.cost_analysis() or {}).get("flops", 0.0)) or None
    )
    if flops_per_dev is None:
        # analytic fallback: 6 * params * tokens (fwd+bwd dense), global
        n_params = sum(
            int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)
        )
        flops_per_dev = 6.0 * n_params * batch * seq / ndev

    params, loss = step(params, tokens, targets)  # warm (reuses compile)
    float(loss)
    iters = 3 if small else 10
    with Timer() as t:
        for _ in range(iters):
            params, loss = step(params, tokens, targets)
        float(loss)
    dt = t.elapsed_ns() / iters / 1e9

    achieved_per_dev = flops_per_dev / dt
    suffix = "" if attention == "auto" else f"_{attention}"
    if seq != 1024 and not small:
        suffix = f"_t{seq}{suffix}"
    out = {f"train_tflops{suffix}": round(achieved_per_dev * ndev / 1e12, 2)}
    peak = _peak_flops(jax.devices()[0].device_kind)
    if peak is not None:
        out[f"train_mfu{suffix}"] = round(achieved_per_dev / peak, 4)
    return out


def _bench_train_fused(small: bool = False) -> dict:
    """The fused-compute-slot train-step evidence (the ``accl_hls``
    analog's headline): the SAME L-bucket data-parallel optimizer step
    measured two ways on a 4-rank gang — UNFUSED (a batched window of
    per-bucket facade reduce-scatters, then the classic host round
    trip per bucket: read back the reduced chunk, apply ``param - lr *
    grad`` on host, push the shard back for the next forward) vs FUSED
    (one window of L ``fused_apply`` slots per step — gradient
    reduction and the apply epilogue sequenced on device, updated
    shards landing in device buffers, no host between compute and
    collective).  The forward/backward compute is identical in both
    variants and excluded on purpose: this leg isolates the phase the
    fused slots change.  Counter-asserted in the artifact: warm fused
    ``device_interactions``/step == refill count/step
    (``check_cmdring`` gates equality), and the fused fallback
    counters (``unsupported_op``/``compressed``/``fused_decomposed``)
    read ZERO across the fused warm workload.  A second warm window
    mixes all three fused opcodes (FUSED_MATMUL_RS / FUSED_APPLY /
    FUSED_ATTN_HOP) for the per-opcode residency evidence."""
    import threading

    import jax

    from accl_tpu.core import xla_group

    world = 4
    if len(jax.devices()) < world:
        raise RuntimeError(
            f"fused train-step leg needs a >= {world}-device mesh "
            "(off-chip: XLA_FLAGS=--xla_force_host_platform_device_"
            "count=8)"
        )
    n = _size(2 * 1024) if small else 16 * 1024  # per-rank shard
    buckets = 8                                  # gradient buckets/step
    steps = 3 if small else 8
    lr = 0.125  # power of two: exact through the Q16.16 fparam word

    def run_ranks(fn):
        errs = []

        def tgt(r):
            try:
                fn(r)
            except Exception as e:  # surface, don't deadlock
                errs.append(e)

        ts = [
            threading.Thread(target=tgt, args=(r,)) for r in range(world)
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errs:
            raise errs[0]

    g = xla_group(world)
    try:
        a0 = g[0]
        ring = a0.engine.gang.cmdring
        rng = np.random.default_rng(0)
        grads = [
            [
                rng.standard_normal(world * n).astype(np.float32)
                for _ in range(buckets)
            ]
            for _ in range(world)
        ]
        params = [
            [
                rng.standard_normal(n).astype(np.float32)
                for _ in range(buckets)
            ]
            for _ in range(world)
        ]

        # -- unfused: RS window + per-bucket host apply round trips --------
        send = [
            [a.create_buffer_from(gr) for gr in grads[r]]
            for r, a in enumerate(g)
        ]
        red = [
            [a.create_buffer(n, np.float32) for _ in range(buckets)]
            for a in g
        ]
        pdev = [
            [a.create_buffer_from(p) for p in params[r]]
            for r, a in enumerate(g)
        ]

        def unfused_step(r):
            a = g[r]
            with a.batch():  # best-case unfused: the RS half batches too
                reqs = [
                    a.reduce_scatter(
                        send[r][b], red[r][b], n, run_async=True
                    )
                    for b in range(buckets)
                ]
            for req in reqs:
                assert req.wait(120)
                req.check()
            for b in range(buckets):
                red[r][b].sync_from_device()  # the round trip fused kills
                pdev[r][b].data[:] = (
                    pdev[r][b].data - lr * red[r][b].data
                )
                pdev[r][b].sync_to_device()   # shard back for the fwd

        run_ranks(unfused_step)  # warm compile
        ic0 = a0.capabilities()["device_interactions"]
        with Timer() as t:
            for _ in range(steps):
                run_ranks(unfused_step)
        unfused_us = t.elapsed_ns() / steps / 1e3
        unfused_inter = (
            a0.capabilities()["device_interactions"] - ic0
        ) / steps

        # -- fused: ONE window of L fused_apply slots per step -------------
        fsend = [
            [
                a.create_buffer_from(
                    np.concatenate([grads[r][b], params[r][b]])
                )
                for b in range(buckets)
            ]
            for r, a in enumerate(g)
        ]
        fout = [
            [a.create_buffer(n, np.float32) for _ in range(buckets)]
            for a in g
        ]

        def fused_step(r):
            a = g[r]
            with a.batch():
                reqs = [
                    a.fused_apply(
                        fsend[r][b], fout[r][b], n, lr=lr,
                        run_async=True,
                    )
                    for b in range(buckets)
                ]
            for req in reqs:
                assert req.wait(120)
                req.check()

        run_ranks(fused_step)  # warm compile (arms the ring)
        st0 = ring.stats()
        ic0 = a0.capabilities()["device_interactions"]
        with Timer() as t:
            for _ in range(steps):
                run_ranks(fused_step)
        fused_us = t.elapsed_ns() / steps / 1e3
        st1 = ring.stats()
        fused_inter = (
            a0.capabilities()["device_interactions"] - ic0
        ) / steps
        fused_refills = (st1["refills"] - st0["refills"]) / steps

        # -- per-opcode residency: all three fused slots in ONE window -----
        mm_send = [
            a.create_buffer_from(
                rng.standard_normal(world * n).astype(np.float32)
            )
            for a in g
        ]
        mm_out = [a.create_buffer(n, np.float32) for a in g]
        kv = [
            rng.standard_normal(n).astype(np.float32) for _ in range(world)
        ]
        q = [
            rng.standard_normal(n).astype(np.float32) for _ in range(world)
        ]
        hop_send = [
            a.create_buffer_from(np.concatenate([kv[r], q[r]]))
            for r, a in enumerate(g)
        ]
        hop_out = [a.create_buffer(n, np.float32) for a in g]

        def fused_window(r):
            a = g[r]
            with a.batch():
                reqs = [
                    a.fused_matmul_reduce_scatter(
                        mm_send[r], mm_out[r], n, scale=0.5,
                        run_async=True,
                    ),
                    a.fused_apply(
                        fsend[r][0], fout[r][0], n, lr=lr,
                        run_async=True,
                    ),
                    a.fused_attn_hop(
                        hop_send[r], hop_out[r], hop=1, count=n,
                        scale=2.0, run_async=True,
                    ),
                ]
            for req in reqs:
                assert req.wait(120)
                req.check()

        run_ranks(fused_window)  # cold
        s0 = ring.stats()
        run_ranks(fused_window)  # warm: every fused opcode rides
        s1 = ring.stats()
        ops0, ops1 = s0.get("ops") or {}, s1.get("ops") or {}
        fused_op_slots = {
            op: ops1.get(op, 0) - ops0.get(op, 0)
            for op in ("FUSED_MATMUL_RS", "FUSED_APPLY", "FUSED_ATTN_HOP")
        }
        fb0 = st0.get("fallbacks") or {}
        fb1 = s1.get("fallbacks") or {}
        fused_fallbacks = {
            reason: fb1.get(reason, 0) - fb0.get(reason, 0)
            for reason in ("unsupported_op", "compressed",
                           "fused_decomposed")
        }

        # flops of the measured phase (reduce + apply per shard element,
        # per bucket): world adds + 2 apply ops per element, per rank —
        # reported so a chip capture can carry MFU next to the walls
        flops = buckets * (world * (world + 1) * n + world * 2 * n)
        out = {
            "gang_cmdring_fused_step_us": round(fused_us, 1),
            "gang_cmdring_unfused_step_us": round(unfused_us, 1),
            "gang_cmdring_fused_interactions_per_step": round(
                fused_inter, 4
            ),
            "gang_cmdring_fused_refills_per_step": round(
                fused_refills, 4
            ),
            "gang_cmdring_unfused_interactions_per_step": round(
                unfused_inter, 4
            ),
            "gang_cmdring_fused_op_slots": fused_op_slots,
            "gang_cmdring_fused_fallbacks": fused_fallbacks,
            "train_fused_world": world,
            "train_fused_shard_elems": n,
            "train_fused_buckets": buckets,
            "train_fused_steps": steps,
            "train_fused_tflops": round(
                flops / (fused_us / 1e6) / 1e12, 6
            ),
        }
        peak = _peak_flops(jax.devices()[0].device_kind)
        if peak is not None:
            out["gang_cmdring_fused_mfu"] = round(
                flops / (fused_us / 1e6) / peak, 6
            )
        return out
    finally:
        for a in g:
            a.deinit()


# measured HBM need of the T=4096 blockwise train step's compile (the
# per-q-block backward residuals dominate; 17.91 GiB on v5e, diagnosed
# 2026-08-01).  The residual footprint
# scales ~quadratically in seq at fixed tokens/step.
_BLOCKWISE_T4096_NEED_BYTES = int(17.91 * (1 << 30))


def _blockwise_t4096_oom_skip():
    """Pre-flight for the known HBM-OOM configuration: a structured
    ``skipped`` record (reason + the numbers behind it) when this host's
    chips cannot compile the T=4096 blockwise step, else None (run it).
    Unknown HBM sizes run the bench — a wrong guess there degrades to
    the classified-OOM error path, never a silent skip."""
    import jax

    limit = None
    try:
        stats = jax.local_devices()[0].memory_stats()
        limit = (stats or {}).get("bytes_limit")
    except Exception:
        limit = None
    if limit is None:
        # memory_stats absent on some runtimes: fall back to the known
        # 16 GiB-class device kinds the OOM was diagnosed on
        kind = jax.devices()[0].device_kind.lower()
        if any(k in kind for k in ("v5 lite", "v5e", "v6 lite", "v6e")):
            limit = 16 * (1 << 30)
    if limit is not None and _BLOCKWISE_T4096_NEED_BYTES > limit:
        return {
            "reason": (
                "blockwise attention at T=4096 needs "
                f"~{_BLOCKWISE_T4096_NEED_BYTES / (1 << 30):.2f} GiB of "
                f"HBM at compile; this chip exposes "
                f"{limit / (1 << 30):.2f} GiB (the BENCH_r05 classified "
                "OOM, now detected up front)"
            ),
            "needed_bytes": _BLOCKWISE_T4096_NEED_BYTES,
            "hbm_bytes_limit": int(limit),
        }
    return None


def _bench_decode_throughput() -> dict:
    """Serving-side number: greedy KV-cache decode tokens/sec on the
    flagship model, summed over ALL local devices (dp-sharded, global
    batch 8 * n_devices) — a per-host figure, not per-chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from accl_tpu.models import (
        TransformerConfig, init_params, make_sharded_generate,
    )

    if _SMALL:
        cfg = TransformerConfig(
            vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
            max_seq=64, dtype=jnp.float32,
        )
        batch, prompt_len, steps = 2, 8, 8
    else:
        cfg = TransformerConfig(
            vocab=32768, d_model=2048, n_heads=16, n_layers=8, d_ff=8192,
            max_seq=1024, dtype=jnp.bfloat16,
        )
        batch, prompt_len, steps = 8, 128, 128
    ndev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(ndev, 1), ("dp", "tp"))
    fn, shard = make_sharded_generate(cfg, mesh, steps)
    params = shard(init_params(jax.random.PRNGKey(0), cfg))
    prompt = jnp.zeros((batch * ndev, prompt_len), jnp.int32)
    fn(params, prompt).block_until_ready()  # warm/compile
    iters = 2 if _SMALL else 5
    # one DISTINCT prompt per timed dispatch (anti execution-cache, see
    # _bench_attention: byte-identical repeats can be cache-served)
    prompts = [
        jnp.full(
            (batch * ndev, prompt_len), (i + 1) % cfg.vocab, jnp.int32
        )
        for i in range(iters)
    ]
    for p in prompts:
        p.block_until_ready()
    with Timer() as t:
        for it in range(iters):
            out = fn(params, prompts[it])
        out.block_until_ready()
    dt = t.elapsed_ns() / iters / 1e9
    return {"decode_tokens_per_s": round(batch * ndev * steps / dt, 1)}


def _bench_facade_overhead() -> dict:
    """Per-call latency (us) of a small collective through the full MPI
    facade (buffer -> CallOptions -> gang -> jitted program -> result
    adoption).  The reference's equivalent is the hostctrl kernel-start +
    firmware round trip per call; here it bounds the Python control
    plane's cost — the data path itself is device-resident.

    Three numbers land in extras so the artifact itself separates
    architecture cost from dispatch cost:

    * ``facade_call_overhead_us`` — the end-to-end per-call figure;
    * ``facade_dispatch_floor_us`` — the per-call cost of the SAME loop
      shape (N async dispatches of a trivial jitted program + one
      drain) with no facade at all: pure jit dispatch + transport;
    * ``facade_arch_overhead_us`` — the difference: what the facade's
      Python control plane (buffer resolution, CallOptions, seqn
      bookkeeping, program-cache lookup) itself costs per call.
    """
    import jax
    import jax.numpy as jnp

    from accl_tpu.core import xla_group

    iters = 50 if _SMALL else 300

    # dispatch floor FIRST, same discipline as the facade loop below:
    # async enqueues, one completion barrier at the end
    x = jnp.ones((1024,), jnp.float32)
    trivial = jax.jit(lambda v: v + 1.0)
    trivial(x).block_until_ready()  # compile
    with Timer() as t:
        out = x
        for _ in range(iters):
            out = trivial(out)
        out.block_until_ready()
    floor_us = t.elapsed_ns() / iters / 1e3

    def prepare(a):
        """Stage the warm-path loop on one rank handle; returns a
        re-runnable round closure (plus the batched bench's state)."""
        s = a.create_buffer_from(np.ones(1024, np.float32))
        d = a.create_buffer(1024, np.float32)
        # warm TWICE: call 1 builds the CollectivePlan + compiles the
        # slow-path program; call 2 is the first plan-cache hit, which
        # prepares (and jit-caches) the plan's program handle — the
        # steady state every later call runs in
        a.allreduce(s, d, 1024)
        a.allreduce(s, d, 1024)

        # one DISTINCT send buffer per call (the _staged_copies
        # discipline: no two timed dispatches share an operand; the
        # floor loop feeds its output back, so it never repeats one
        # either).  Every staging
        # put is BARRIERED before the timed window — create_buffer_from
        # commits asynchronously.
        sends = [
            a.create_buffer_from(
                np.full(1024, 1.0 + (i + 1) / 128.0, np.float32)
            )
            for i in range(iters)
        ]
        for sb in sends:
            sb.device_array().block_until_ready()

        def drain():  # complete all queued device work (calls are async)
            arr = d.device_array() if hasattr(d, "device_array") else None
            if arr is not None:
                arr.block_until_ready()

        def run_round():
            """One timed window: (us/call, interactions/call, plan-hit
            rate).  Interactions come straight off the engine counter —
            the single-interaction contract says 1.0 on this path; the
            plan-hit rate says 1.0 means nothing re-derived."""
            drain()  # earlier work must not bill its completion to us
            ic0 = a.engine.device_interactions()
            pc0 = a.capabilities()["plan_cache"]
            with Timer() as t:
                for it in range(iters):
                    a.allreduce(sends[it], d, 1024)
                drain()  # sustained end-to-end: host + device
            pc1 = a.capabilities()["plan_cache"]
            return (
                t.elapsed_ns() / iters / 1e3,
                (a.engine.device_interactions() - ic0) / iters,
                (pc1["hits"] - pc0["hits"]) / iters,
            )

        return run_round, sends, d, drain

    # two groups, telemetry ON (the default, always-on contract) and
    # OFF (the ACCL_TELEMETRY=0 kill switch), both prepared/warmed up
    # front and then measured in ALTERNATING rounds with rotating order
    # — the sweep_group_paired noise discipline; two sequentially-
    # captured windows differ by far more than the 5% being certified
    # (first-window cache/alloc churn measured as a fake 2x "overhead")
    g = xla_group(1)
    g_off = []
    try:
        prev = os.environ.get("ACCL_TELEMETRY")
        os.environ["ACCL_TELEMETRY"] = "0"
        try:
            g_off = xla_group(1)
        finally:
            if prev is None:
                os.environ.pop("ACCL_TELEMETRY", None)
            else:
                os.environ["ACCL_TELEMETRY"] = prev
        a = g[0]
        run_on, sends, d, drain = prepare(a)
        run_off, _, _, _ = prepare(g_off[0])
        on_vals, off_vals = [], []
        rounds = 4
        for k in range(rounds):
            order = (
                (run_on, on_vals), (run_off, off_vals)
            ) if k % 2 == 0 else (
                (run_off, off_vals), (run_on, on_vals)
            )
            for fn, acc in order:
                acc.append(fn())
        best = min(on_vals)
        call_us, per_call, plan_hit_rate = best
        off_us = min(off_vals)[0]

        # contract-plane budget (parse_results.check_verify): the same
        # interleaved A/B discipline, verifier armed vs disarmed on the
        # SAME prepared warm path — ACCL_VERIFY must cost <=5% when on
        # and ~0% when off (the off cost is one None check per call,
        # already inside the telemetry-on baseline above)
        ver_vals, base_vals = [], []
        for k in range(rounds):
            if k % 2 == 0:
                a.set_contract_verify(True)
                ver_vals.append(run_on())
                a.set_contract_verify(False)
                base_vals.append(run_on())
            else:
                base_vals.append(run_on())
                a.set_contract_verify(True)
                ver_vals.append(run_on())
                a.set_contract_verify(False)
        verify_snap = None
        a.set_contract_verify(True)
        run_on()  # one armed round so the snapshot carries live counters
        verify_snap = a.telemetry_snapshot()["contract"]
        a.set_contract_verify(False)
        ver_us = min(ver_vals)[0]
        base_us = min(base_vals)[0]
        verify = {
            "overhead_pct": round(
                max(0.0, (ver_us - base_us) / max(base_us, 1e-9) * 100.0),
                2,
            ),
            "interval": verify_snap.get("interval"),
            "calls_verified": verify_snap.get("calls_verified"),
            "windows_exchanged": verify_snap.get("windows_exchanged"),
        }

        # batched dispatch: N queued collectives flush through the
        # command queue as ONE fused program — the amortized per-call
        # cost is the facade's floor when a training step batches its
        # step collectives
        B = 8
        nbatches = max(1, iters // B)

        def batched_round(base):
            with a.batch():
                reqs = [
                    a.allreduce(
                        sends[(base + i) % iters], d, 1024, run_async=True
                    )
                    for i in range(B)
                ]
            for r in reqs:
                r.wait()

        batched_round(0)  # warm: compiles the fused batch program
        drain()
        with Timer() as t:
            for k in range(nbatches):
                batched_round(k * B)
            drain()
        batched_us = t.elapsed_ns() / (nbatches * B) / 1e3

        # telemetry evidence for the capture artifact: the snapshot must
        # carry every merged section (parse_results.check_telemetry) and
        # the per-op histograms ride along as the warm path measured them
        snap = a.telemetry_snapshot()
        telemetry = {
            "snapshot_keys": sorted(snap.keys()),
            "schema_version": snap.get("schema_version"),
            "records": len(snap["flight_recorder"]),
            "histograms": {
                k: {"count": h["count"], "mean_us": h["mean_us"]}
                for k, h in (snap["metrics"].get("histograms") or {}).items()
            },
        }

        # causal trace plane evidence (parse_results.check_telemetry):
        # flow events need >= 2 ranks (a world-1 span has no far end to
        # link), so a 2-rank InProc side group produces a merged,
        # VALIDATED flow set — the capture proves cross-rank linkage,
        # not just that ids were derived
        import threading as _threading

        from accl_tpu import telemetry as _telemetry
        from accl_tpu.core import emulated_group

        fg = emulated_group(2)
        try:
            fsend = [
                x.create_buffer_from(np.ones(64, np.float32)) for x in fg
            ]
            frecv = [x.create_buffer(64, np.float32) for x in fg]
            for _ in range(4):
                ths = [
                    _threading.Thread(
                        target=lambda x, i: x.allreduce(
                            fsend[i], frecv[i], 64
                        ),
                        args=(x, i), name="accl-bench-flow",
                    )
                    for i, x in enumerate(fg)
                ]
                for t2 in ths:
                    t2.start()
                for t2 in ths:
                    t2.join(60)
            merged = _telemetry.merge_traces([
                {"traceEvents": x.telemetry_trace_events()} for x in fg
            ])
            flow_problems = _telemetry.validate_flows(
                merged["traceEvents"]
            )
            flow_events = sum(
                1 for e in merged["traceEvents"]
                if e.get("cat") == "accl.flow"
            )
        finally:
            for x in fg:
                x.deinit()
        telemetry["flow_events"] = 0 if flow_problems else flow_events
        telemetry["flow_problems"] = len(flow_problems)
    finally:
        for x in g:
            x.deinit()
        for x in g_off:
            x.deinit()

    # the always-on budget (parse_results.check_telemetry): telemetry-on
    # within 5% of -off on the identical interleaved loop
    telemetry["overhead_pct"] = round(
        max(0.0, (call_us - off_us) / max(off_us, 1e-9) * 100.0), 2
    )

    return {
        "facade_call_overhead_us": round(call_us, 1),
        "facade_call_overhead_telemetry_off_us": round(off_us, 1),
        "facade_dispatch_floor_us": round(floor_us, 1),
        "facade_arch_overhead_us": round(call_us - floor_us, 1),
        "facade_device_interactions_per_call": round(per_call, 2),
        "facade_plan_cache_hit_rate": round(plan_hit_rate, 4),
        "facade_batched_call_overhead_us": round(batched_us, 1),
        "facade_verify_overhead_pct": verify["overhead_pct"],
        "telemetry": telemetry,
        "verify": verify,
    }


def _bench_monitor_overhead() -> dict:
    """Interleaved monitor-on/off A/B on the facade warm path with the
    scrape service LIVE and actually polled during the on rounds —
    the monitor plane's <=5% budget (parse_results.check_monitor),
    certified under real serving load, not an idle socket.

    "On" = scrape server bound on an ephemeral port + a poller thread
    GETting /metrics every 100 ms while the timed loop runs (still 10x
    hotter than an aggressive 1 s production scrape; each scrape
    renders a full snapshot on the request thread, so the GIL cost is
    real and measured); "off" = service stopped.  Rounds alternate with
    rotating order (the sweep_group_paired noise discipline the
    telemetry/verify A/Bs use) and are sized to span several scrape
    periods.  The straggler tracker and anomaly watchdog are armed in
    BOTH arms — they ride the telemetry observer unconditionally, so
    their cost is part of the telemetry A/B's always-on budget; this
    bench isolates the SERVICE."""
    import threading
    import urllib.request

    from accl_tpu.core import xla_group

    iters = 50 if _SMALL else 1500
    g = xla_group(1)
    try:
        a = g[0]
        d = a.create_buffer(1024, np.float32)
        sends = [
            a.create_buffer_from(
                np.full(1024, 1.0 + (i + 1) / 64.0, np.float32)
            )
            for i in range(16)
        ]
        for sb in sends:
            sb.device_array().block_until_ready()
        a.allreduce(sends[0], d, 1024)
        a.allreduce(sends[0], d, 1024)  # warm: plan + prepared program

        def drain():
            arr = d.device_array() if hasattr(d, "device_array") else None
            if arr is not None:
                arr.block_until_ready()

        def run_round():
            drain()
            with Timer() as t:
                for it in range(iters):
                    a.allreduce(sends[it % len(sends)], d, 1024)
                drain()
            return t.elapsed_ns() / iters / 1e3

        scrape_stats = {"n": 0, "errors": 0}

        def scrape_once(port):
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=2
                ) as r:
                    r.read()
                scrape_stats["n"] += 1
            except Exception:
                scrape_stats["errors"] += 1

        def scraper(port, stop):
            while not stop.wait(0.1):
                scrape_once(port)

        def on_round():
            port = a.start_monitor(0)
            stop = threading.Event()
            t = threading.Thread(
                target=scraper, args=(port, stop),
                name="accl-bench-scraper", daemon=True,
            )
            t.start()
            try:
                return run_round()
            finally:
                stop.set()
                t.join(timeout=5.0)
                # at least one scrape is guaranteed live per armed
                # round, however short ACCL_BENCH_SMALL makes the loop
                scrape_once(port)
                a.stop_monitor()

        on_vals, off_vals = [], []
        for k in range(4):
            order = (
                ((on_round, on_vals), (run_round, off_vals))
                if k % 2 == 0
                else ((run_round, off_vals), (on_round, on_vals))
            )
            for fn, acc in order:
                acc.append(fn())

        # route validation: every endpoint live and well-formed (the
        # check_monitor gate refuses a capture without this evidence)
        # ring-span evidence (the causal trace plane): one batched
        # window rides the command ring, so the /trace export carries
        # ring-resident spans next to the call spans
        try:
            with a.batch():
                ring_reqs = [
                    a.allreduce(sends[i], d, 1024, run_async=True)
                    for i in range(2)
                ]
            for rq in ring_reqs:
                rq.wait()
        except Exception:
            pass  # evidence-only: the gate below reports honestly
        ring_spans = sum(
            1 for e in a.telemetry_trace_events()
            if e.get("cat") == "cmdring"
        )

        port = a.start_monitor(0)
        routes_ok = True
        try:
            for route, kind in (
                ("/metrics", "prom"), ("/snapshot", "json"),
                ("/trace", "json"), ("/cmdring", "json"),
            ):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{route}", timeout=5
                ) as r:
                    body = r.read().decode()
                if kind == "json":
                    json.loads(body)
                elif "accl_" not in body:
                    routes_ok = False
        except Exception:
            routes_ok = False
        finally:
            a.stop_monitor()
        snap = a.telemetry_snapshot()
        on_us, off_us = min(on_vals), min(off_vals)
        monitor = {
            "overhead_pct": round(
                max(0.0, (on_us - off_us) / max(off_us, 1e-9) * 100.0), 2
            ),
            "scrapes": scrape_stats["n"],
            "scrape_errors": scrape_stats["errors"],
            "routes_ok": routes_ok,
            "schema_version": snap.get("schema_version"),
            "stragglers_enabled": bool(
                (snap.get("stragglers") or {}).get("enabled")
            ),
            "ring_spans": ring_spans,
        }
        return {
            "facade_monitor_overhead_pct": monitor["overhead_pct"],
            "monitor": monitor,
        }
    finally:
        for x in g:
            x.deinit()


def _bench_arbiter() -> dict:
    """QoS arbiter evidence for the capture gate
    (parse_results.check_arbiter), three legs:

    * **overhead A/B** — interleaved warm facade rounds with the
      arbiter disarmed vs armed (one registered tenant, zero
      contention): the <=5% budget for carrying the plane on the warm
      path.  Same rotating-order discipline as the telemetry/monitor
      A/Bs.
    * **adversarial cross-tenant load** — a GUARANTEED small-message
      tenant and a BEST_EFFORT flooder on one emulator fabric under a
      seeded fault plan (every flooder frame wire-delayed); the
      guaranteed p99 comes from the LIVE ``/tenants`` route — the
      histograms the monitor plane serves — and must hold the bound
      while the flooder's admissions visibly queue.  A third
      UNARBITRATED baseline run of the same workload (no quotas, no
      windowing — the flooder free-runs) must violate: a blown
      guaranteed p99, or the flood traffic itself erroring out of the
      shared fabric (rx exhaustion / timeouts) — either way the SLO
      the arbiter exists to protect is broken without it.
    * **ring-share** — a budget-clamped warm batch on the gang command
      ring: the flooder's refill windows bounded at its configured
      slot budget (max_window <= budget, budgeted_windows counted).
    """
    import threading
    import urllib.request

    from accl_tpu.core import emulated_group, xla_group
    from accl_tpu.faults import FaultPlan, FaultRule

    # -- leg 1: disabled-vs-armed warm-path overhead (gang facade) ----------
    iters = 50 if _SMALL else 3000
    g = xla_group(1)
    try:
        a = g[0]
        d = a.create_buffer(1024, np.float32)
        send = a.create_buffer_from(np.ones(1024, np.float32))
        # LONG stabilization: the XLA CPU warm path drifts ~15% over
        # its first thousands of calls, which would masquerade as
        # arbiter overhead in short rounds
        for _ in range(iters):
            a.allreduce(send, d, 1024)

        def drain():
            arr = d.device_array() if hasattr(d, "device_array") else None
            if arr is not None:
                arr.block_until_ready()

        def run_round():
            drain()
            with Timer() as t:
                for _ in range(iters):
                    a.allreduce(send, d, 1024)
                drain()
            return t.elapsed_ns() / iters / 1e3

        def on_round():
            a.set_arbiter(True)
            try:
                return run_round()
            finally:
                a.set_arbiter(False)

        a.set_tenant_class("guaranteed", name="bench")
        on_vals, off_vals = [], []
        for k in range(8):
            order = (
                ((on_round, on_vals), (run_round, off_vals))
                if k % 2 == 0
                else ((run_round, off_vals), (on_round, on_vals))
            )
            for fn, acc in order:
                acc.append(fn())
        # PAIRED-DIFFERENCE median: the warm path drifts ~15% over a
        # run, so unpaired min/median estimators report phantom
        # overhead (~2-3x); adjacent on/off rounds share drift state
        # and their difference cancels it
        import statistics

        on_us = statistics.median(on_vals)
        off_us = statistics.median(off_vals)
        deltas = [
            (on_vals[k] - off_vals[k]) / max(off_vals[k], 1e-9) * 100.0
            for k in range(len(on_vals))
        ]
        out = {
            "arbiter_off_round_us": round(off_us, 3),
            "arbiter_on_round_us": round(on_us, 3),
            "arbiter_overhead_pct": round(
                max(0.0, statistics.median(deltas)), 2
            ),
        }
    finally:
        for x in g:
            x.deinit()

    # -- leg 2: adversarial cross-tenant load (emulator, seeded plan) --------
    # one offered load, two regimes: a bulk tenant pushing 24 x 8 KiB
    # eager transfers as fast as the fabric admits, every bulk frame
    # wire-delayed 5 ms by the seeded plan.  Arbitrated, window_share=1
    # serializes the burst AT ADMISSION (fabric concurrency 1/rank) and
    # the guaranteed tenant's p99 holds; unarbitrated, the burst hits
    # the fabric concurrently and breaks it — a blown p99 or the bulk
    # traffic erroring out of the shared rx pool, either being the SLO
    # violation the arbiter exists to prevent.
    BOUND_US = 16384.0
    FLOOD_COUNT = 2048  # 8 KiB eager payloads
    SERVE_CALLS = 16 if _SMALL else 32

    def adversarial(arbitrated: bool) -> dict:
        grp = emulated_group(2)
        errors = {"flood": 0, "serve": 0}
        try:
            subs = [None, None]

            def prep(x, r):
                from accl_tpu.constants import MAX_INFLIGHT_WINDOW

                subs[r] = x.create_communicator([0, 1])
                # short engine deadline: a wedged unarbitrated call
                # must fail in seconds, not stall the leg for 30 s each
                x.set_timeout(5.0)
                # the plane stays armed in BOTH regimes (the live
                # /tenants histograms are the measurement instrument);
                # the baseline's quotas are set provably NON-BINDING —
                # window share at the maximum, equal to the flood's
                # issue-ahead depth, so admission never queues and DRR
                # never engages: an unarbitrated run with live meters
                x.set_arbiter(True)
                x.set_tenant_class("guaranteed", name="serve")
                x.set_tenant_class(
                    "best_effort", comm=subs[r], name="bulk"
                )
                x.set_tenant_quota(
                    comm=subs[r],
                    window_share=1 if arbitrated
                    else MAX_INFLIGHT_WINDOW,
                )

            ths = [
                threading.Thread(
                    target=prep, args=(x, r), name=f"accl-bench-prep-{r}"
                )
                for r, x in enumerate(grp)
            ]
            for t in ths:
                t.start()
            for t in ths:
                t.join(60)
            # the seeded adversarial load shape: every flooder-comm
            # frame wire-delayed (64 KiB rendezvous payloads serialize
            # the delayed handshake per call)
            grp[0].engine.fabric.install_fault_plan(FaultPlan(
                rules=[FaultRule(
                    action="delay", comm=subs[0].id, delay_s=0.005,
                )],
                seed=4321,
            ))
            fsend = [
                x.create_buffer_from(
                    np.ones(FLOOD_COUNT, np.float32)
                )
                for x in grp
            ]
            frecv = [
                x.create_buffer(FLOOD_COUNT, np.float32) for x in grp
            ]
            gsend = [
                x.create_buffer_from(np.ones(64, np.float32))
                for x in grp
            ]
            grecv = [x.create_buffer(64, np.float32) for x in grp]

            stop = threading.Event()
            # symmetric stop with a reconcile phase: the first rank to
            # observe the stop latches a tentative final round, but
            # issue-ahead lets the unarbitrated regime run ~16 rounds
            # past its peer — so after exiting, each rank publishes how
            # many rounds it ISSUED and both top up to the maximum
            # (bounded wait), leaving no unmatched collective stranded
            latch = {"stop_at": None, "issued": {}}
            llock = threading.Lock()
            FLOOD_ROUND = 4

            def flood(x, r):
                # SUSTAINED offered load for the whole serve window:
                # arbitrated, the arbiter paces issuance at admission
                # (window_share=1 -> fabric concurrency 1/rank);
                # unarbitrated, up to MAX_INFLIGHT_WINDOW concurrent
                # transfers free-run into the 16-slot shared rx pool
                # (issue-ahead depth == the non-binding share, so the
                # baseline's admission gate provably never queues) —
                # the production hazard this plane removes
                from accl_tpu.constants import MAX_INFLIGHT_WINDOW

                reqs = []
                depth = 2 if arbitrated else MAX_INFLIGHT_WINDOW
                rnd = 0

                def one_round():
                    for _ in range(FLOOD_ROUND):
                        try:
                            reqs.append(x.allreduce(
                                fsend[r], frecv[r], FLOOD_COUNT,
                                comm=subs[r], run_async=True,
                            ))
                        except Exception:
                            errors["flood"] += 1
                        if len(reqs) >= depth:
                            q = reqs.pop(0)
                            if not q.wait(90) or q.get_retcode() != 0:
                                errors["flood"] += 1

                while True:
                    with llock:
                        if stop.is_set() and latch["stop_at"] is None:
                            latch["stop_at"] = rnd
                        if (
                            latch["stop_at"] is not None
                            and rnd >= latch["stop_at"]
                        ):
                            break
                    one_round()
                    rnd += 1
                # reconcile: both ranks converge on the max issued
                # round count, so every collective has its counterpart
                with llock:
                    latch["issued"][r] = rnd
                deadline = time.monotonic() + 60.0
                target = rnd
                while time.monotonic() < deadline:
                    with llock:
                        if len(latch["issued"]) == 2:
                            target = max(latch["issued"].values())
                            break
                    time.sleep(0.005)
                while rnd < target:
                    one_round()
                    rnd += 1
                for q in reqs:
                    if not q.wait(90) or q.get_retcode() != 0:
                        errors["flood"] += 1

            def serve(x, r):
                time.sleep(0.1)  # let the flood reach steady state
                for _ in range(SERVE_CALLS):
                    try:
                        x.allreduce(gsend[r], grecv[r], 64)
                    except Exception:
                        errors["serve"] += 1
                stop.set()

            def drive(x, r):
                f = threading.Thread(
                    target=flood, args=(x, r),
                    name=f"accl-bench-flood-{r}",
                )
                f.start()
                serve(x, r)
                f.join(180)

            ths = [
                threading.Thread(
                    target=drive, args=(x, r),
                    name=f"accl-bench-drive-{r}",
                )
                for r, x in enumerate(grp)
            ]
            for t in ths:
                t.start()
            for t in ths:
                t.join(240)
            # p99 from the LIVE monitor surface (the /tenants route)
            port = grp[0].start_monitor(0)
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/tenants", timeout=10
                ) as r:
                    doc = json.loads(r.read().decode())
            finally:
                grp[0].stop_monitor()
            serve_t = doc["tenants"].get(str(grp[0].comm.id)) or {}
            bulk_t = doc["tenants"].get(str(subs[0].id)) or {}
            lat = serve_t.get("latency") or {}
            return {
                "p99_us": lat.get("p99_us"),
                "mean_us": lat.get("mean_us"),
                "flooder_queued_peak": bulk_t.get("queued_peak", 0),
                "flooder_wait_ns": bulk_t.get(
                    "grant_wait_ns_total", 0
                ),
                "serve_errors": errors["serve"],
                "flood_errors": errors["flood"],
            }
        finally:
            for x in grp:
                try:
                    x.deinit()
                except Exception:
                    pass  # a wedged baseline must still report

    fair = adversarial(arbitrated=True)
    base = adversarial(arbitrated=False)
    out.update({
        "arbiter_p99_bound_us": BOUND_US,
        "arbiter_guaranteed_p99_us": fair["p99_us"],
        "arbiter_guaranteed_mean_us": fair["mean_us"],
        "arbiter_flooder_queued_peak": fair["flooder_queued_peak"],
        "arbiter_flooder_wait_ns": fair["flooder_wait_ns"],
        # the GUARANTEED tenant must be clean under arbitration; the
        # BEST_EFFORT flooder's chaos-plan losses are its class working
        # as designed (recorded for honesty, not gated)
        "arbiter_fair_errors": fair["serve_errors"],
        "arbiter_fair_flood_errors": fair["flood_errors"],
        "arbiter_baseline_p99_us": base["p99_us"],
        "arbiter_baseline_mean_us": base["mean_us"],
        "arbiter_baseline_errors": base["serve_errors"],
        "arbiter_baseline_flood_errors": base["flood_errors"],
    })

    # -- leg 3: ring-share evidence (gang command ring, budget-clamped) ------
    g = xla_group(2)
    try:
        done = threading.Barrier(2, timeout=120)

        def ring_leg(x, r):
            x.set_arbiter(True)
            x.set_tenant_class("best_effort", name="bulk")
            x.set_tenant_quota(ring_slots=2)
            done.wait()
            s = x.create_buffer_from(np.ones(32, np.float32))
            dd = x.create_buffer(32, np.float32)
            for _ in range(2):
                with x.batch():
                    for _ in range(6):
                        x.allreduce(s, dd, 32, run_async=True)

        ths = [
            threading.Thread(
                target=ring_leg, args=(x, r), name=f"accl-bench-ring-{r}"
            )
            for r, x in enumerate(g)
        ]
        for t in ths:
            t.start()
        for t in ths:
            t.join(180)
        st = g[0].engine.gang.cmdring.stats()
        out.update({
            "arbiter_ring_budget": 2,
            "arbiter_ring_max_window": st.get("max_window"),
            "arbiter_ring_budgeted_windows": st.get("budgeted_windows"),
            "arbiter_ring_slots": (
                (st.get("comm_slots") or {}).get(str(g[0].comm.id), 0)
            ),
        })
    finally:
        for x in g:
            x.deinit()
    return out


def _bench_gang_device_time() -> dict:
    """Separate the gang call's DEVICE time from its host dispatch
    floor by payload-slope timing (the engine's ``get_duration`` is host
    wall-clock around the XLA program, so every per-call number carries
    the dispatch floor, and the artifact must say how much that is).

    Method: per-call wall time of the SAME facade allreduce at payload
    ``n`` and ``2n``.  For a bandwidth-bound collective the on-device
    time is linear in bytes while the dispatch cost is size-independent,
    so ``2 * (wall(2n) - wall(n))`` estimates the device time at ``2n``
    and the remainder is the dispatch floor.  The estimate is clamped to
    ``[0, wall]`` — the artifact invariant (device <= wall) holds by
    construction, noise only degrades precision.

    Overlap plane (this PR): the dispatch floor that matters to a
    workload is the SUSTAINED one — a back-to-back window of ``run_async``
    calls riding the engine's in-flight window, where each call's floor
    amortizes behind its predecessors' device time.  The pipelined loop
    measures that: ``gang_allreduce_dispatch_floor_us`` is now
    ``pipelined_wall - device`` (the amortized floor), the serialized
    per-call wall stays as ``gang_allreduce_wall_us``, and
    ``gang_inflight_overlap_pct`` = how much of the serial wall the
    window hides.  Gated by ``parse_results.check_overlap``."""
    from accl_tpu.core import xla_group

    n = _size(4 * 1024 * 1024)
    # 25 (not 50) calls per payload: each needs its OWN send buffer
    # (anti execution-cache), and 25 distinct 2n buffers is ~800 MB of
    # HBM — the statistics stay sound, the bench cannot RESOURCE_EXHAUST
    iters = 10 if _SMALL else 25
    g = xla_group(1)
    try:
        a = g[0]

        def timed(count, pipelined=False):
            # one DISTINCT send buffer per call (anti execution-cache,
            # see _bench_facade_overhead), staged from ONE host array
            # and BARRIERED before the timed window — create_buffer_from
            # commits asynchronously, and unfinished puts would bill the
            # host link's copy time to the payload slope below
            host = np.ones(count, np.float32)
            sends = []
            for i in range(iters):
                host[0] = 1.0 + (i + 1) / 128.0  # distinct content
                sends.append(a.create_buffer_from(host.copy()))
            host[0] = 0.5  # distinct from every timed send's content
            warm = a.create_buffer_from(host)  # NOT reused by the loop
            d = a.create_buffer(count, np.float32)
            for sb in sends + [warm]:
                sb.device_array().block_until_ready()
            a.allreduce(warm, d, count)  # warm: compiles the program

            def drain():
                arr = (
                    d.device_array()
                    if hasattr(d, "device_array") else None
                )
                if arr is not None:
                    arr.block_until_ready()

            drain()
            if pipelined:
                # the back-to-back window: launches run ahead of
                # completion up to the in-flight depth; the wait+drain
                # at the end closes the last calls' tails
                with Timer() as t:
                    reqs = [
                        a.allreduce(sends[it], d, count, run_async=True)
                        for it in range(iters)
                    ]
                    for r in reqs:
                        r.wait(120)
                    drain()
                for r in reqs:
                    r.check()
            else:
                with Timer() as t:
                    for it in range(iters):
                        a.allreduce(sends[it], d, count)
                    drain()
            return t.elapsed_ns() / iters / 1e3

        w1 = timed(n)
        w2 = timed(2 * n)
        dev = min(max(2.0 * (w2 - w1), 0.0), w2)
        p2 = timed(2 * n, pipelined=True)
        floor = min(max(p2 - dev, 0.0), p2)
        overlap_pct = max(0.0, (1.0 - p2 / w2) * 100.0) if w2 > 0 else 0.0
        inflight = (a.engine.telemetry_report().get("inflight") or {})
        return {
            "gang_allreduce_wall_us": round(w2, 1),
            "gang_allreduce_device_us": round(dev, 1),
            "gang_allreduce_pipelined_wall_us": round(p2, 1),
            "gang_allreduce_dispatch_floor_us": round(floor, 1),
            "gang_inflight_overlap_pct": round(overlap_pct, 1),
            "gang_inflight_window_depth": inflight.get("depth"),
            "gang_inflight_max_depth_seen": inflight.get("max_depth_seen"),
        }
    finally:
        for x in g:
            x.deinit()


def _bench_cmdring() -> dict:
    """The command-ring (device-resident sequencer) dispatch floor: the
    SAME warm facade allreduce measured two ways at the same payload —
    a serialized sync loop (every call pays the host-dispatch floor)
    and batched windows riding the command ring (one refill interaction
    per window of N, sequenced on device).  Device time is estimated by
    payload slope exactly like ``_bench_gang_device_time``; the two
    floors are then wall − device at the SAME 2n point, so
    ``check_cmdring`` can demand ring < host on one capture.  A smaller
    payload than the gang bench keeps the floor (not bandwidth)
    dominant — the regime the ring exists for.  Also emits
    ``gang_cmdring_refills_per_call``: the host-interaction
    amortization evidence (1/window when every call rode the ring)."""
    from accl_tpu.core import xla_group

    n = _size(64 * 1024)  # 256 KB fp32: floor-dominant, ring-eligible
    wdepth = 8            # collectives per batched window
    windows = 3 if _SMALL else 12
    g = xla_group(1)
    try:
        a = g[0]

        def fresh_sends(count, k):
            host = np.ones(count, np.float32)
            sends = []
            for i in range(k):
                host[0] = 1.0 + (i + 1) / 128.0  # distinct content
                sends.append(a.create_buffer_from(host.copy()))
            for sb in sends:
                sb.device_array().block_until_ready()
            return sends

        def drain(d):
            arr = d.device_array() if hasattr(d, "device_array") else None
            if arr is not None:
                arr.block_until_ready()

        def timed_serial(count):
            iters = wdepth * (2 if _SMALL else 3)
            sends = fresh_sends(count, iters)
            d = a.create_buffer(count, np.float32)
            a.allreduce(sends[0], d, count)  # warm compile
            drain(d)
            with Timer() as t:
                for sb in sends:
                    a.allreduce(sb, d, count)
                drain(d)
            return t.elapsed_ns() / iters / 1e3

        def timed_ring(count):
            """The pipelined stream: K refill windows dispatched
            PIPELINED (``_dispatch_pending`` launches each window
            without draining — the host keeps refilling while the
            device executes) with one drain at the end.  Also returns
            the per-window-DRAINED latency leg (reported, not gated)
            and the dispatches a window cost."""
            sends = fresh_sends(count, wdepth)
            d = a.create_buffer(count, np.float32)
            # warm window: compiles the sequencer program
            with a.batch():
                reqs = [
                    a.allreduce(sb, d, count, run_async=True)
                    for sb in sends
                ]
            for r in reqs:
                r.wait(120)
                r.check()
            drain(d)
            # latency leg: each window drained before the next
            with Timer() as tl:
                for _ in range(2):
                    with a.batch():
                        reqs = [
                            a.allreduce(sb, d, count, run_async=True)
                            for sb in sends
                        ]
                    for r in reqs:
                        r.wait(120)
                        r.check()
            latency = tl.elapsed_ns() / (2 * wdepth) / 1e3

            def burst():
                reqs = []
                a.begin_batch()
                try:
                    for _ in range(windows):
                        reqs.extend(
                            a.allreduce(sb, d, count, run_async=True)
                            for sb in sends
                        )
                        a._dispatch_pending()  # post, do NOT drain
                finally:
                    a.end_batch()  # ONE drain for the whole stream
                for r in reqs:
                    r.wait(120)
                    r.check()

            burst()  # warm
            ring0 = a.engine.telemetry_report().get("cmdring") or {}
            with Timer() as t:
                burst()
                drain(d)
            ring1 = a.engine.telemetry_report().get("cmdring") or {}
            calls = windows * wdepth
            refills = ring1.get("refills", 0) - ring0.get("refills", 0)
            slots = ring1.get("slots", 0) - ring0.get("slots", 0)
            disp = ring1.get("dispatches", 0) - ring0.get("dispatches", 0)
            redisp_per_window = max(0, disp - 1) / windows
            return (
                t.elapsed_ns() / calls / 1e3,
                refills / calls,
                slots,
                latency,
                redisp_per_window,
                disp,
            )

        def mixed_warm():
            """The fallback-counters-zero leg: a warm mixed window over
            the grown opcode space (reduce-scatter / allgather /
            alltoall / barrier / compressed allreduce beside the plain
            one) — the per-opcode residency evidence and the
            unsupported_op/compressed counters the gate demands stay
            zero."""
            nm = _size(4 * 1024)
            world = 1  # this bench group's gang
            send = a.create_buffer_from(np.ones(nm, np.float32))
            send_w = a.create_buffer_from(
                np.ones(world * nm, np.float32)
            )
            ar = a.create_buffer(nm, np.float32)
            car = a.create_buffer(nm, np.float32)
            car8 = a.create_buffer(nm, np.float32)
            cari = a.create_buffer(nm, np.float32)
            rs = a.create_buffer(nm, np.float32)
            ag = a.create_buffer(world * nm, np.float32)
            a2a = a.create_buffer(world * nm, np.float32)

            def window():
                with a.batch():
                    reqs = [
                        a.allreduce(send, ar, nm, run_async=True),
                        a.reduce_scatter(send_w, rs, nm, run_async=True),
                        a.allgather(send, ag, nm, run_async=True),
                        a.barrier(run_async=True),
                        a.alltoall(send_w, a2a, nm, run_async=True),
                        # the full compressed-lane family in ONE mixed
                        # window: f16 cast, fp8 stochastic cast, int8
                        # scaled — all must ride the ring (the
                        # quantized-wire fallback-counters-zero gate)
                        a.allreduce(
                            send, car, nm, compress_dtype=np.float16,
                            run_async=True,
                        ),
                        a.allreduce(
                            send, car8, nm,
                            compress_dtype="float8_e4m3fn",
                            run_async=True,
                        ),
                        a.allreduce(
                            send, cari, nm, compress_dtype="int8",
                            run_async=True,
                        ),
                    ]
                for r in reqs:
                    r.wait(120)
                    r.check()

            window()  # cold
            s0 = a.engine.telemetry_report().get("cmdring") or {}
            window()  # warm: must ride whole
            s1 = a.engine.telemetry_report().get("cmdring") or {}
            ops0, ops1 = s0.get("ops") or {}, s1.get("ops") or {}
            fb0, fb1 = s0.get("fallbacks") or {}, s1.get("fallbacks") or {}
            return (
                {
                    op: ops1.get(op, 0) - ops0.get(op, 0)
                    for op in (
                        "ALLREDUCE", "REDUCE_SCATTER", "ALLGATHER",
                        "ALLTOALL", "BARRIER",
                    )
                },
                {
                    reason: fb1.get(reason, 0) - fb0.get(reason, 0)
                    for reason in ("unsupported_op", "compressed")
                },
            )

        w1 = timed_serial(n)
        w2 = timed_serial(2 * n)
        dev = min(max(2.0 * (w2 - w1), 0.0), w2)
        (r2, refills_per_call, slots, latency, redisp_per_window,
         sus_dispatches) = timed_ring(2 * n)
        op_slots, mixed_fallbacks = mixed_warm()
        floor_host = min(max(w2 - dev, 0.0), w2)
        floor_ring = min(max(latency - dev, 0.0), latency)
        floor_sustained = min(max(r2 - dev, 0.0), r2)
        ring_stats = a.engine.telemetry_report().get("cmdring") or {}
        return {
            "gang_cmdring_serial_wall_us": round(w2, 1),
            "gang_cmdring_wall_us": round(latency, 1),
            "gang_cmdring_device_us": round(dev, 1),
            "gang_cmdring_host_floor_us": round(floor_host, 1),
            # THE ring floor (gate: < host floor): the inline window
            # form — one async zero-copy program per drained window,
            # the dispatch cost a warm window actually pays
            "gang_cmdring_dispatch_floor_us": round(floor_ring, 1),
            # the persistence legs (gate: redispatch-zero):
            # the pipelined mailbox stream trades per-call wall for
            # ZERO program launches after the first — the trade that
            # pays where launches are expensive
            "gang_cmdring_sustained_wall_us": round(r2, 1),
            "gang_cmdring_sustained_floor_us": round(floor_sustained, 1),
            "gang_cmdring_latency_wall_us": round(latency, 1),
            "gang_cmdring_refills_per_call": round(refills_per_call, 4),
            "gang_cmdring_window": wdepth,
            "gang_cmdring_ring_slots": slots,
            # persistence evidence
            "gang_cmdring_redispatches_per_window": round(
                redisp_per_window, 4
            ),
            "gang_cmdring_sustained_dispatches": sus_dispatches,
            "gang_cmdring_sustained_windows": windows,
            # opcode-space evidence (the mixed-op warm leg)
            "gang_cmdring_op_slots": op_slots,
            "gang_cmdring_mixed_fallbacks": mixed_fallbacks,
            "gang_cmdring_mode": ring_stats.get("mode"),
            "gang_cmdring_fallbacks": ring_stats.get("fallbacks"),
        }
    finally:
        for x in g:
            x.deinit()


def _bench_compression() -> dict:
    """Quantized-wire evidence, two legs (parse_results.check_compression):

    **Effective-bandwidth sweep** — the SAME warm allreduce at one
    large (bandwidth-side) payload, per wire verdict (off / f16 / fp8
    / int8), on the emulator tier — the tier whose fabric moves REAL
    frame bytes — with the emulated link PACED at a modeled rate
    (``Fabric.set_wire_rate``; ``ACCL_COMPRESSION_WIRE_GBPS``, default
    0.5 Gb/s — a DCN-class commodity link, the regime wire compression
    exists for).  Unpaced, the in-process wire is memcpy at ~10 GB/s
    and a sweep reads pure codec cost — no wire at all.  The artifact
    records the modeled rate; effective bandwidth is payload bits /
    wall (algbw), and wire bytes per contribution come from the shared
    codec's sizing rule (scale sidecars included).

    **Convergence leg** — a deterministic 2-rank data-parallel SGD run
    (linear regression, gradients allreduced through the facade) at
    the aggressive fp8-e4m3 wire: final loss with error feedback ON
    must land within the documented bound of the f32-wire run (and the
    raw-compressed run shows what EF buys).  Unpaced — this leg is
    about numerics, not bytes."""
    import threading

    from accl_tpu import wire as wirecodec
    from accl_tpu.constants import DataType
    from accl_tpu.core import emulated_group

    gbps = float(os.environ.get("ACCL_COMPRESSION_WIRE_GBPS", "0.5"))
    # 4 MiB fp32: the large-bucket regime.  SMALL mode trims to 1 MiB
    # (not _size's 1024 elements — a floor-dominated payload measures
    # dispatch, and this sweep exists to measure the wire)
    n = (1 << 18) if _SMALL else (1 << 20)
    reps = 2 if _SMALL else 3
    world = 4
    lanes = [
        ("off", None, None),
        ("float16", np.float16, DataType.FLOAT16),
        ("float8_e4m3", "float8_e4m3fn", DataType.FLOAT8_E4M3),
        ("int8", "int8", DataType.INT8),
    ]
    sweep = {}
    g = emulated_group(world)
    try:
        g[0].engine.fabric.set_wire_rate(gbps)
        rng = np.random.default_rng(0)
        data = [
            rng.standard_normal(n).astype(np.float32)
            for _ in range(world)
        ]
        for lane, wire, dt in lanes:
            sends = [
                a.create_buffer_from(d.copy())
                for a, d in zip(g, data)
            ]
            recvs = [a.create_buffer(n, np.float32) for a in g]

            def work(i, k, wire=wire):
                for _ in range(k):
                    g[i].allreduce(
                        sends[i], recvs[i], n, compress_dtype=wire
                    )

            def run(k):
                ts = [
                    threading.Thread(target=work, args=(i, k))
                    for i in range(world)
                ]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()

            run(1)  # warm
            with Timer() as t:
                run(reps)
            wall_us = t.elapsed_ns() / reps / 1e3
            wire_b = (
                wirecodec.wire_nbytes(n, dt) if dt is not None else n * 4
            )
            sweep[lane] = {
                "wall_us": round(wall_us, 1),
                "effective_gbps": round(
                    n * 4 * 8 / (wall_us * 1e3), 4
                ),
                "wire_bytes_per_contrib": wire_b,
            }
    finally:
        for a in g:
            a.deinit()

    conv = _compression_convergence()
    off_bw = sweep["off"]["effective_gbps"]
    return {
        "compression_sweep": sweep,
        "compression_payload_bytes": n * 4,
        "compression_wire_gbps_model": gbps,
        "compression_world": world,
        # headline gains the gate reads (fraction over the f32 wire)
        "compression_effective_gain_fp8": round(
            sweep["float8_e4m3"]["effective_gbps"] / off_bw - 1.0, 4
        ),
        "compression_effective_gain_int8": round(
            sweep["int8"]["effective_gbps"] / off_bw - 1.0, 4
        ),
        "compression_convergence": conv,
    }


def _bench_topology() -> dict:
    """Hierarchical-collective evidence (parse_results.check_topology):
    flat vs hierarchical allreduce on a 2x4 multi-slice layout over the
    emulator fabric's two-class paced link model
    (``Fabric.set_wire_rates``; ``ACCL_TOPOLOGY_ICI_GBPS`` /
    ``ACCL_TOPOLOGY_DCN_GBPS``, default 8 / 0.05 Gb/s — a fast
    intra-slice interconnect over a slow cross-slice link, the regime
    the decomposition exists for.  The DCN default sits low enough
    that the modeled wire dominates the emulator's GIL-bound per-chunk
    Python overhead — at DCN-realistic rates that constant overhead
    drowns the very wall-clock difference the capture exists to
    show).  Three claims, one capture:

    * **wall clock** — with the cross-slice class paced slow, the
      slice-local reduce-scatter / cross-slice rail allreduce /
      slice-local allgather decomposition must beat the flat ring;
    * **cross-link bytes** — the fabric's per-link-class counters must
      show the DCN traffic cut by ~the slice factor (flat crosses
      ``2*L*(W-1)/W * payload``, hierarchical ``2*(L-1) * payload``);
    * **bit identity** — integer-valued payloads make differing
      reduction orders exact, so hierarchical-vs-flat is a hard
      equality, not a tolerance."""
    import threading

    from accl_tpu.core import emulated_group
    from accl_tpu.topology import Topology

    ici = float(os.environ.get("ACCL_TOPOLOGY_ICI_GBPS", "8.0"))
    dcn = float(os.environ.get("ACCL_TOPOLOGY_DCN_GBPS", "0.05"))
    world, slices = 8, 2
    topo = Topology.from_slice_size(world, world // slices)
    # 1 MiB fp32 even in SMALL mode: the gate's large-bucket floor —
    # below it the sweep measures dispatch, not the wire
    n = 1 << 18
    reps = 2 if _SMALL else 3
    rng = np.random.default_rng(7)
    data = [
        rng.integers(-64, 64, n).astype(np.float32) for _ in range(world)
    ]
    g = emulated_group(world, topology=topo)
    try:
        fabric = g[0].engine.fabric
        fabric.set_wire_rates(ici_gbps=ici, dcn_gbps=dcn)
        sends = [a.create_buffer_from(d.copy()) for a, d in zip(g, data)]
        recvs = [a.create_buffer(n, np.float32) for a in g]

        def work(i, k):
            for _ in range(k):
                g[i].allreduce(sends[i], recvs[i], n)

        def run(k):
            ts = [
                threading.Thread(target=work, args=(i, k))
                for i in range(world)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        def leg(hier: bool):
            for a in g:
                a.set_tuning("hierarchical", 1 if hier else 0)
            run(1)  # warm: plans + (hier) subcommunicator derivation
            fabric.reset_wire_class_stats()
            with Timer() as t:
                run(reps)
            stats = fabric.wire_class_stats()
            return (
                {
                    "wall_us": round(t.elapsed_ns() / reps / 1e3, 1),
                    "dcn_bytes_per_run": int(
                        (stats["bytes"].get("dcn") or 0) / reps
                    ),
                    "ici_bytes_per_run": int(
                        (stats["bytes"].get("ici") or 0) / reps
                    ),
                },
                [np.asarray(r.device_view()[:n]).copy() for r in recvs],
            )

        flat, flat_out = leg(False)
        hier, hier_out = leg(True)
        for a in g:
            a.set_tuning("hierarchical", 0)
        bit_identical = all(
            np.array_equal(f, h) for f, h in zip(flat_out, hier_out)
        )
    finally:
        for a in g:
            a.deinit()
    return {
        "topology_signature": topo.signature(),
        "topology_world": world,
        "topology_num_slices": topo.num_slices,
        "topology_payload_bytes": n * 4,
        "topology_wire_gbps_model": {"ici": ici, "dcn": dcn},
        "topology_flat": flat,
        "topology_hier": hier,
        "topology_speedup": round(
            flat["wall_us"] / max(hier["wall_us"], 1e-9), 4
        ),
        "topology_dcn_reduction": round(
            flat["dcn_bytes_per_run"]
            / max(hier["dcn_bytes_per_run"], 1), 4
        ),
        "topology_bit_identical": bit_identical,
    }


def _compression_convergence(steps: int = 40, dim: int = 512,
                             batch: int = 64) -> dict:
    """The convergence leg: 2-rank DP-SGD linear regression with
    facade-allreduced gradients, run three ways — f32 wire, fp8-e4m3
    raw, fp8-e4m3 with error feedback — same seeds, same data.  Both
    ranks apply the identical summed gradient, so the run is SPMD by
    construction and the final mse is the convergence verdict."""
    import threading

    from accl_tpu.core import emulated_group

    rng = np.random.default_rng(42)
    w_true = rng.standard_normal(dim).astype(np.float32)
    X = [
        rng.standard_normal((batch, dim)).astype(np.float32)
        for _ in range(2)
    ]
    y = [x @ w_true for x in X]

    def train(wire, ef: bool) -> float:
        g = emulated_group(2)
        losses = [None, None]
        try:
            if ef:
                for a in g:
                    a.set_error_feedback(True)

            def run_rank(r):
                a = g[r]
                w = np.zeros(dim, np.float32)
                gbuf = a.create_buffer(dim, np.float32)
                obuf = a.create_buffer(dim, np.float32)
                for _ in range(steps):
                    err = X[r] @ w - y[r]
                    grad = (X[r].T @ err / batch).astype(np.float32)
                    gbuf.data[:] = grad
                    gbuf.sync_to_device()
                    a.allreduce(gbuf, obuf, dim, compress_dtype=wire)
                    obuf.sync_from_device()
                    w -= 0.05 * obuf.data / 2.0
                losses[r] = float(np.mean((X[r] @ w - y[r]) ** 2))

            ts = [
                threading.Thread(target=run_rank, args=(r,))
                for r in range(2)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
        finally:
            for a in g:
                a.deinit()
        return max(losses)

    loss_f32 = train(None, False)
    loss_raw = train("float8_e4m3fn", False)
    loss_ef = train("float8_e4m3fn", True)
    base = max(loss_f32, 1e-12)
    return {
        "wire": "float8_e4m3",
        "steps": steps,
        "loss_f32": round(loss_f32, 8),
        "loss_raw_compressed": round(loss_raw, 8),
        "loss_error_feedback": round(loss_ef, 8),
        # the gated number: EF-compressed final loss relative to the
        # uncompressed run (documented bound: <= 10%)
        "delta_pct": round((loss_ef - loss_f32) / base * 100.0, 3),
        "raw_delta_pct": round((loss_raw - loss_f32) / base * 100.0, 3),
    }


def _bench_ring_allreduce(ndev: int, algo: str = "xla") -> float:
    """Bus bandwidth of a K-iteration device-side allreduce loop over the
    mesh; slope timing so dispatch cancels out.  ``algo`` picks the XLA
    psum or the explicit ring pipeline."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from accl_tpu.ops import make_mesh
    from accl_tpu.ops.driver import AXIS
    from accl_tpu.ops import ring as ring_ops

    mesh = make_mesh(ndev)
    n = _size(16 * 1024 * 1024)  # 64 MB per rank fp32
    stacked = jnp.ones((ndev, n), jnp.float32)

    @partial(jax.jit, static_argnums=1)
    def loop(x, k):
        def body(x):
            def it(i, acc):
                if algo == "ring":
                    red = ring_ops.ring_allreduce(acc, AXIS, num_segments=4)
                else:
                    red = lax.psum(acc, AXIS)
                return red / ndev  # keep magnitude bounded

            return lax.fori_loop(0, k, it, x[0])[None]

        return shard_map(
            body, mesh=mesh, in_specs=(P(AXIS),), out_specs=P(AXIS),
            check_vma=False,
        )(x)

    staged = _staged_copies(stacked)

    def timed(k):
        x_k = next(staged)  # distinct content per dispatch
        with Timer() as t:
            out = loop(x_k, k)
            float(out[0, 0])
        return t.elapsed_ns() / 1e9

    per_iter = _slope_time(timed, *((2, 6) if _SMALL else (5, 25)))
    bytes_per_rank = n * 4
    return 2 * (ndev - 1) / ndev * bytes_per_rank / per_iter / 1e9


def _try(extras: dict, errors: dict, key: str, fn):
    """Run one bench leg; record its number or its failure.  A failure
    lands in ``errors`` and the run goes on to the next leg, so one
    capture names every leg that failed — and :func:`main` exits
    nonzero when ``errors`` is not empty."""
    try:
        val = fn()
    except Exception as e:  # noqa: BLE001 - recorded, and fails the run
        msg = f"{type(e).__name__}: {e}"
        if "Ran out of memory" in msg or "Exceeded hbm capacity" in msg:
            # classify compile-time HBM overflows so the artifact states
            # the finding, not just the exception (e.g. the T=4096
            # blockwise train step needs 17.9G of the v5e's 15.75G —
            # diagnosed 2026-08-01; flash fits because its custom_vjp
            # saves only (o, lse) per layer)
            import re as _re

            m = _re.search(
                r"Used [\d.]+\w* of [\d.]+\w* hbm"
                r"(?:\. Exceeded hbm capacity by [\d.]+\w*)?",
                msg,
            )
            msg = f"HBM OOM at compile: {m.group(0) if m else ''} | {msg}"
        errors[key] = msg[:400]
        print(f"bench {key} FAILED: {msg}", file=sys.stderr)
        return None
    if isinstance(val, dict):
        extras.update(val)
    else:
        extras[key] = round(val, 2)
    return val


# Impossible-rate gate for the artifact: bandwidth-like extras above this
# ceiling mean the measurement under them was a sentinel or a clock bug;
# they move to `errors` instead of shipping on the scoreboard.  50 TB/s is
# ~30x the best number ever captured here (cast_stochastic 1.6 TB/s) and
# far under the 16.7 Pb/s class of garbage this gate exists to catch.
_BANDWIDTH_KEY_PREFIXES = ("combine_", "allreduce_", "cast_", "quant_")
_BANDWIDTH_CEILING_GBS = float(
    os.environ.get("ACCL_BENCH_GBS_CEILING", "50000")
)


def _sanitize_extras(extras: dict, errors: dict) -> None:
    """Move physically impossible bandwidth extras into errors, in place,
    before emission, so the headline is never built from garbage."""
    for k in list(extras):
        if not k.startswith(_BANDWIDTH_KEY_PREFIXES):
            continue
        v = extras[k]
        if isinstance(v, (int, float)) and v > _BANDWIDTH_CEILING_GBS:
            errors[k] = (
                f"implausible {v:.2f} GB/s (> {_BANDWIDTH_CEILING_GBS:.0f} "
                "GB/s sanity ceiling): dropped from extras"
            )
            del extras[k]


def _headline(extras: dict) -> dict:
    """The one-line headline from whatever metrics exist: multi-chip
    allreduce bus bandwidth (vs the 100 GbE wire rate of
    12.5 GB/s) when present, else the single-chip combine datapath (vs
    the CCLO 16 GB/s envelope), preferring the Pallas number when it
    beats XLA's."""
    # allreduce headline prefers whichever implementation won, with an
    # impl marker when that is not the default XLA psum (mirrors the
    # combine branch's pallas marker)
    xla_bus = extras.get("allreduce_xla")
    ring_bus = extras.get("allreduce_ring")
    if xla_bus is not None or ring_bus is not None:
        result = {
            "metric": "allreduce_bus_bandwidth",
            "unit": "GB/s",
        }
        bus = max(x for x in (xla_bus, ring_bus) if x is not None)
        result.update(value=round(bus, 2), vs_baseline=round(bus / 12.5, 2))
        if xla_bus is None or (ring_bus is not None and ring_bus > xla_bus):
            result["impl"] = "ring"
        return result
    result = {
        "metric": "combine_datapath_bandwidth",
        "value": None,
        "unit": "GB/s",
        "vs_baseline": None,
    }
    xla = extras.get("combine_xla")
    pal = extras.get("combine_pallas")
    if xla is not None:
        result.update(value=round(xla, 2), vs_baseline=round(xla / 16.0, 2))
    if pal is not None and (xla is None or pal > xla):
        result.update(
            value=round(pal, 2), vs_baseline=round(pal / 16.0, 2),
            impl="pallas",
        )
    return result


def _device() -> dict:
    """The device jax found, as every result is stamped with it — and the
    refusal to measure anything else.  Off the TPU the run stops here
    unless it was asked for by name (``ACCL_BENCH_SMALL=1``, the CPU
    harness test, whose numbers are meaningless by construction); on a
    ``device_kind`` with no row in the peak table it stops too."""
    import jax

    from accl_tpu.utils import device_peaks

    d = jax.devices()[0]
    device = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }
    if not _SMALL:
        if d.platform != "tpu":
            raise SystemExit(
                f"bench: jax found {device}, not a TPU — a CPU run is not "
                "a measurement (ACCL_BENCH_SMALL=1 runs the harness test)"
            )
        try:
            device_peaks(d.device_kind)
        except KeyError as e:
            raise SystemExit(f"bench: {e.args[0]}") from None
    return device


def main() -> int:
    from accl_tpu.utils import use_compile_cache

    use_compile_cache()
    device = _device()
    ndev = device["count"]
    on_tpu = device["platform"] == "tpu"
    extras: dict = {}
    errors: dict = {}

    if ndev >= 2:
        _try(
            extras, errors, "allreduce_xla",
            lambda: _bench_ring_allreduce(ndev),
        )
        _try(
            extras, errors, "allreduce_ring",
            lambda: _bench_ring_allreduce(ndev, algo="ring"),
        )
    else:
        _try(extras, errors, "combine_xla", _bench_combine_xla)
        _try(extras, errors, "combine_pallas", _bench_combine_pallas)

    # per-kernel compression lanes: Mosaic-compiled on TPU, interpreted
    # at the ACCL_BENCH_SMALL sizes
    _try(extras, errors, "cast_pallas", _bench_cast_pallas)
    _try(
        extras, errors, "cast_stochastic_pallas",
        lambda: _bench_cast_pallas(stochastic=True),
    )
    _try(extras, errors, "quant_int8_pallas", _bench_quant_int8_pallas)

    _try(
        extras, errors, "facade_call_overhead_us", _bench_facade_overhead
    )
    _try(
        extras, errors, "monitor_overhead", _bench_monitor_overhead
    )
    _try(extras, errors, "arbiter", _bench_arbiter)
    _try(
        extras, errors, "gang_device_time", _bench_gang_device_time
    )
    _try(extras, errors, "cmdring", _bench_cmdring)
    _try(extras, errors, "compression", _bench_compression)
    _try(extras, errors, "topology", _bench_topology)

    _try(extras, errors, "attention", _bench_attention)

    # flagship train-step MFU; on the chip, also the naive-attention
    # comparison point
    _try(
        extras, errors, "train_mfu",
        lambda: _bench_train_mfu(small=_SMALL),
    )
    # the fused-slot variant of the train step (the kernel-initiated
    # collectives headline): needs a ring-capable gang, so only on a
    # >=4-device mesh — check_cmdring gates its counters on capture
    if ndev >= 4:
        _try(
            extras, errors, "train_mfu_fused",
            lambda: _bench_train_mfu(small=_SMALL, fused=True),
        )
    if on_tpu:
        # the with/without-fusion record: since the block-512 flash
        # kernel, "auto" resolves to FLASH at the bench's T=1024 (the
        # measured crossover moved to 1024: flash 75.4% vs naive 69.5%
        # train MFU), so the explicit blockwise run is the
        # without-fusion comparison point
        _try(
            extras, errors, "train_mfu_blockwise",
            lambda: _bench_train_mfu(small=_SMALL, attention="blockwise"),
        )
        # the former default, kept as the third point of the record
        # (auto measured it until the crossover moved to 1024)
        _try(
            extras, errors, "train_mfu_naive",
            lambda: _bench_train_mfu(small=_SMALL, attention="naive"),
        )
        # long-context training record (T=4096, where naive's score
        # residuals would OOM): "auto" resolves to the Pallas flash
        # kernel + its custom_vjp backward; blockwise is the XLA
        # comparison point
        if not _SMALL:
            _try(
                extras, errors, "train_mfu_t4096",
                lambda: _bench_train_mfu(seq=4096),
            )
            # bench hygiene: the T=4096 blockwise step's compile needs
            # ~17.9 GiB of HBM (per-q-block backward residuals; measured
            # 2026-08-01) and OOMs on 16 GiB-class chips — detect the
            # configuration up front and record a STRUCTURED skip instead
            # of polluting `errors` with an HTTP-500 compile failure in
            # every capture
            skip = _blockwise_t4096_oom_skip()
            if skip is not None:
                extras.setdefault("skipped", {})[
                    "train_mfu_t4096_blockwise"
                ] = skip
                print(
                    "bench train_mfu_t4096_blockwise SKIPPED: "
                    f"{skip['reason']}",
                    file=sys.stderr,
                )
            else:
                _try(
                    extras, errors, "train_mfu_t4096_blockwise",
                    lambda: _bench_train_mfu(
                        seq=4096, attention="blockwise"
                    ),
                )
            # 8K-context record: auto->flash exactly fills the VMEM
            # gate (K+V = 4 MiB at D=128 bf16); batch=1 keeps
            # tokens/step at the same 8K as every other seq point
            _try(
                extras, errors, "train_mfu_t8192",
                lambda: _bench_train_mfu(seq=8192),
            )
    _try(extras, errors, "decode_tokens_per_s", _bench_decode_throughput)

    # capture gates (benchmarks/parse_results.py): each refuses a capture
    # whose evidence for one plane is missing or out of its budget, and
    # a refusal is a failed run like any failed leg
    from benchmarks.parse_results import (
        check_arbiter,
        check_cmdring,
        check_compression,
        check_monitor,
        check_overlap,
        check_telemetry,
        check_topology,
        check_verify,
    )

    gates = [
        # a gang dispatch-floor number must ship with its overlap metric
        ("overlap_gate", check_overlap),
        # a ring floor must ship with its host-floor comparison + refill
        # amortization counters, engage the ring, and beat the host floor
        ("cmdring_gate", check_cmdring),
        # the verifier A/B evidence and its <=5% opt-in overhead verdict
        ("verify_gate", check_verify),
        # the live scrape-service A/B evidence and its <=5% verdict
        ("monitor_gate", check_monitor),
        # the disabled-warm-path budget, the adversarial per-tenant p99
        # contract, and the ring-share evidence
        ("arbiter_gate", check_arbiter),
        # fp8/int8 effective-bandwidth gains over the f32 wire and the
        # error-feedback convergence bound
        ("compression_gate", check_compression),
        # hierarchical allreduce against flat: wall clock, DCN bytes,
        # bit-identity
        ("topology_gate", check_topology),
    ]
    if "telemetry" in extras:
        # the snapshot sections + a within-budget always-on overhead
        # (only when the facade bench produced them)
        gates.insert(0, ("telemetry_gate", check_telemetry))
    for key, gate in gates:
        try:
            gate(extras)
        except ValueError as e:  # every *GateError is a ValueError
            errors[key] = str(e)

    # static-analysis gate (acclint): a capture taken from a tree that
    # violates the project invariants (unbounded waits, broken jax-free
    # imports, ...) is not evidence.  Pure AST: ~1 s wall, no device work.
    from accl_tpu.analysis import run_checks as _acclint

    _findings = [f for f in _acclint() if not f.suppressed]
    if _findings:
        errors["acclint"] = "; ".join(
            f.render() for f in _findings[:5]
        )[:400]

    _sanitize_extras(extras, errors)
    result = _headline(extras)
    result["device"] = device
    result["extras"] = extras
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    return 1 if errors else 0


if __name__ == "__main__":
    if os.environ.get("ACCL_BENCH_MODE") == "facade_decomp":
        # the facade overhead bench alone, on whatever backend
        # JAX_PLATFORMS selects: a dispatch decomposition of the host
        # path, not a device measurement.  ACCL_BENCH_SMALL=1 shortens
        # the loops.
        print(json.dumps(_bench_facade_overhead()))
    else:
        sys.exit(main())
