"""The benchmark's command.

    python -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: set-up (import, device, weights or buffers made on
the device from the seed, warm-up of the cell's own shapes, the
correctness check), then a measured window of ``--seconds``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``) and ``device``.

Off the TPU, with fewer chips than the cell asks for, or on a
``device_kind`` without a row in ``peaks.json`` it exits non-zero and
prints no result.  ``--rehearse`` is the CPU rehearsal: tiny sizes from
each data file's ``rehearsal`` block on four forced host devices,
stamped ``platform: cpu``; it prints counts only, never a time, a rate
or a share of the device.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def _args(argv):
    ap = argparse.ArgumentParser(prog="perfbench.run", description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def _peaks(kind: str) -> dict:
    from perfbench import manifest

    with open(os.path.join(manifest.HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise SystemExit(
            f"perfbench: device_kind {kind!r} has no row in perfbench/peaks.json"
        )
    return table[kind]


def _use_compile_cache(checkout: str) -> str:
    """jax's persistent cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), every program kept: the
    sweep's many sub-second programs are most of its cold set-up."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _reduce_slices(tracer) -> dict:
    from perfbench import trace_reduce

    out = {}
    for sl in tracer.slices:
        reduced = trace_reduce.load(trace_reduce.find_xplane(sl["dir"]))
        window = trace_reduce.window_of(reduced, "bench::")
        out[sl["label"]] = {"reduced": reduced, "window": window}
    return out


def _breakdown(slices: dict) -> dict:
    """The device ops that took most time and the longest idle gaps by
    the host span that covers them, summed over the run's slices."""
    from perfbench import trace_reduce

    ops: dict = {}
    gaps: dict = {}
    for sl in slices.values():
        for name, s in trace_reduce.ops_by_name(sl["reduced"], top=10 ** 9):
            ops[name] = ops.get(name, 0.0) + s
        for name, s in trace_reduce.gaps_by_span(
            sl["reduced"], sl["window"], top=10 ** 9
        ):
            gaps[name] = gaps.get(name, 0.0) + s
    top = lambda d: [
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def main(argv=None) -> int:
    args = _args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4"
        ).strip()

    # libtpu logs to a fixed /tmp/tpu_logs unless told: keep it under this
    # run's own TMPDIR, so that two sides of a comparison share nothing
    os.environ.setdefault(
        "TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs")
    )

    from perfbench import manifest

    doc = manifest.load()
    cell = manifest.cell(doc, args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None else doc["run_seconds"]

    import jax

    if not args.rehearse:
        _use_compile_cache(manifest.CHECKOUT)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if args.rehearse:
        peaks = None
    else:
        if device["platform"] != "tpu":
            print(f"perfbench: no TPU, jax found {device}", file=sys.stderr)
            return 2
        peaks = _peaks(device["kind"])
    if len(devices) < cell["chips"]:
        print(f"perfbench: cell {cell['name']!r} needs {cell['chips']} "
              f"chips, jax found {device}", file=sys.stderr)
        return 2
    used = devices[: cell["chips"]]

    from perfbench.tracing import Tracer

    driver_mod = importlib.import_module(
        "perfbench.drivers." + manifest.check_name(
            cell["traffic"]["driver"], "driver"
        )
    )
    tracer = Tracer(
        os.path.join(manifest.CHECKOUT, ".perfbench_trace", cell["name"]),
        enabled=bool(args.trace),
    )
    driver = driver_mod.Driver(cell, args.seed, used, args.rehearse)
    try:
        driver.setup()
        setup_s = time.perf_counter() - _T0
        result = driver.measure(seconds, tracer)
        tracer.stop()
        correct = driver.correct()
        # a driver may know a higher peak than the allocator reports (a
        # running program's scratch is not in peak_bytes_in_use)
        device["memory_peak_bytes"] = 0 if args.rehearse else max(
            _memory_peak(used), int(result.get("memory_peak_bytes", 0))
        )
    finally:
        driver.close()

    facts = result["facts"]
    values = dict(result["metrics"], setup_s=setup_s)
    breakdown = None
    if args.trace:
        from perfbench import trace_reduce

        slices = _reduce_slices(tracer)
        ctx = {"cell": cell, "facts": facts, "peaks": peaks,
               "device": device, "slices": slices}
        values = {}
        for m in cell["per_layer"]:
            if args.rehearse and m["source"] != "program_counter":
                continue  # a CPU run gives counts, never a device number
            reader = importlib.import_module(
                "perfbench.layer_metrics." + m["name"]
            )
            value = reader.read(ctx)
            if value is not None:
                values[m["name"]] = value
        window = sum(s["window"][1] - s["window"][0] for s in slices.values())
        busy = sum(
            trace_reduce.busy_ns(s["reduced"], s["window"])
            for s in slices.values()
        )
        if not args.rehearse:
            device["busy_s"] = busy / 1e9
            device["window_s"] = window / 1e9
        breakdown = _breakdown(slices)
        if not args.rehearse and busy <= 0:
            driver.problems.append("no op ran on the device in the trace")
            correct = False

    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        name = m["name"]
        if name not in values:
            continue
        if args.rehearse and m["source"] != "program_counter":
            continue  # a CPU run gives counts, never a device number
        metrics[name] = {"value": values[name], "unit": m["unit"]}
        if m["unit"] == "%" and values[name] > 100.0:
            driver.problems.append(f"{name} is {values[name]:.1f}%: above its peak")
            correct = False

    marks = [("start", _T0)] + driver.marks
    print(json.dumps({
        "perfbench": cell["name"], "seed": args.seed, "seconds": seconds,
        "rehearsal": args.rehearse, "samples": facts.get("samples"),
        "problems": driver.problems,
        # a rehearsal's times are the CPU's: not printed under any name
        "facts": None if args.rehearse else {
            k: v for k, v in facts.items() if k != "samples"
        },
        "trace_overhead_s": None if args.rehearse else tracer.overhead_s,
        "setup_parts_s": None if args.rehearse else {
            b[0]: b[1] - a[1] for a, b in zip(marks, marks[1:])
        },
        "memory_stats": used[0].memory_stats(),
    }), flush=True)
    line = {
        "correct": bool(correct),
        "attempted": driver.attempted,
        "failed": driver.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None and not args.rehearse:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
