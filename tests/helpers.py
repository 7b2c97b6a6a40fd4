"""Shared helpers: drive one call per rank concurrently, like the reference's
mpirun-launched per-rank host processes."""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
from typing import Callable, List, Sequence


def run_parallel(group: Sequence, fn: Callable, timeout: float = 60.0) -> List:
    """Call ``fn(accl_instance, rank)`` on one thread per rank; re-raise the
    first exception; return per-rank results."""
    results = [None] * len(group)
    errors = [None] * len(group)

    def runner(i):
        try:
            results[i] = fn(group[i], i)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    threads = [
        threading.Thread(target=runner, args=(i,), daemon=True)
        for i in range(len(group))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        if t.is_alive():
            raise TimeoutError("a rank did not finish its call (likely deadlock)")
    for e in errors:
        if e is not None:
            raise e
    return results


def launch_with_port_retry(fn, world, attempts=3, retry_if=None, **kwargs):
    """``launch_processes`` on a randomized base port, retrying clashes:
    a fixed port flakes under parallel test runs (TIME_WAIT/contention).

    ``retry_if(exc) -> bool`` narrows which RuntimeErrors are retried —
    tests that EXPECT a launch failure pass a predicate that excludes it
    so the expected error surfaces immediately instead of being retried
    as if it were a port clash."""
    import random

    from accl_tpu.launch import launch_processes

    last = None
    for _ in range(attempts):
        base = random.randint(30000, 55000)
        try:
            return launch_processes(fn, world, base_port=base, **kwargs)
        except RuntimeError as e:  # port clash: retry elsewhere
            if retry_if is not None and not retry_if(e):
                raise
            last = e
    raise last


def record_rank_traces(outdir, world=4):
    """One chrome trace a rank of a small gang group, written under
    ``outdir``: a few collectives (the cross-rank s/t/f flows), a plain
    send -> recv pair between ranks 0 and 1 (the p2p flow) and two
    batched windows (ring-resident spans).  What the merge CLI's tests
    merge; returns the files by rank."""
    import numpy as np

    from accl_tpu.core import xla_group

    n = 256
    g = xla_group(world)
    try:
        sends = [a.create_buffer_from(np.ones(n, np.float32)) for a in g]
        outs = [[a.create_buffer(n, np.float32) for a in g] for _ in range(2)]

        def collectives(a, r):
            for _ in range(3):
                a.allreduce(sends[r], outs[0][r], n)

        def pair(a, r):
            if r == 0:
                a.send(sends[0], n, 1, tag=7)
            elif r == 1:
                a.recv(outs[0][1], n, 0, tag=7)

        def window(a, r):
            with a.batch():
                reqs = [a.allreduce(sends[r], out[r], n, run_async=True)
                        for out in outs]
            for req in reqs:
                assert req.wait(60)
                req.check()

        for step in (collectives, pair, window, window):
            run_parallel(g, step)
        paths = [os.path.join(str(outdir), f"trace_rank{r}.json")
                 for r in range(world)]
        for a, path in zip(g, paths):
            a.export_chrome_trace(path)
        return paths
    finally:
        for a in g:
            a.deinit()


# -- a recorded profiler trace (.xplane.pb), read back ------------------------


def trace_spans(logdir):
    """thread -> [(name, start_ns, end_ns, stats)] by start, of the
    program's spans in the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 {str(k): str(v) for k, v in e.stats})
                for e in line.events if e.name.startswith("accl")
            ]
            if events:
                out[i] = sorted(events, key=lambda e: e[1])
    return out


def trace_inside(events, outer):
    return [e for e in events
            if e is not outer and outer[1] <= e[1] and e[2] <= outer[2]]


def trace_in_order(events):
    """Each span ends before the next starts."""
    return all(a[2] <= b[1] for a, b in zip(events, events[1:]))


# ---------------------------------------------------------------------------
# perfbench.run: the benchmark's command, rehearsed on the CPU
# ---------------------------------------------------------------------------

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

#: what a rehearsal may print: counts of the program, never a time, a
#: rate or a share of the device
REHEARSAL_COUNTS = {"plan_hit_share", "interactions_per_call",
                    "interactions_per_window", "ring_fallbacks", "peak_hbm"}

#: ``(cell, trace)`` rehearsals by the file that runs them
#: (``tests/test_bench_rehearsal_<group>.py``; the Nemotron-3 controls
#: are a file of their own): groups of about equal cost, because one
#: file is one worker under ``--dist loadfile`` and the rehearsals in
#: ONE file were most of tier-1's wall (ROADMAP D14).  Seconds beside a
#: case are the driver's, on six workers, at PR 50.
#: ``tests/test_bench_harness.py`` holds this to the manifest: a new
#: cell joins the lightest group.
REHEARSALS = {
    "solar2": [
        ("train_solar2_t8192_b1", 0),      # 237
        ("train_t8192_b1", 0),             # 18
        ("train_olmoh_t8192_b1", 0),       # PR 52: the lightest group's
    ],
    "recurrent": [
        ("train_ling3_t8192_b2", 0),       # 92
        ("train_sdar_t4096_b2", 0),        # 77
        ("train_nemotron3_t8192_b1", 0),   # 73
        ("coll_w4_sweep", 0),              # 19
        ("train_mimo_t8192_b1", 0),        # PR 54: the lightest group's
    ],
    "rest": [
        ("train_dsv2_t4096_b1", 0),        # 61
        ("train_trinity_t8192_b2", 0),     # 56
        ("train_olmoe_t4096_b2", 0),       # 48
        ("train_t1024_b8", 1),             # 44
        ("coll_w4_sweep", 1),              # 41
        ("train_t1024_b8", 0),             # 23
    ],
}


def nice_child(module: str, *args, timeout: float = 600, **env):
    """``python -m <module> ...`` as the driver runs the benchmark: a
    child process from the checkout.  ``--rehearse`` forces its own four
    host devices, so this process's ``XLA_FLAGS`` stay here.  The child
    compiles and runs whole train steps on every core it finds, beside
    the other xdist workers whose tests wait on threads and sockets: it
    runs at the lowest priority (``nice``), so that it takes the cores
    they leave and none they want.  One file is one worker under
    ``--dist loadfile``, so there is one such child a rehearsal file at
    a time."""
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        ["nice", "-n", "19", sys.executable, "-m", module, *args],
        cwd=CHECKOUT, env=dict(base, **env),
        capture_output=True, text=True, timeout=timeout,
    )


def perfbench(cell, *args, **env):
    """``python -m perfbench.run --workload <cell> ...`` in a
    :func:`nice_child`."""
    return nice_child("perfbench.run", "--workload", cell, *args, **env)


def check_rehearsal(cell: str, trace: int) -> None:
    """One cell's CPU rehearsal ends correct and prints counts only."""
    proc = perfbench(cell, "--seed", "3", "--seconds", "2",
                     "--trace", str(trace), "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-2000:]
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    values = {k: m["value"] for k, m in line["metrics"].items()}
    assert set(values) <= REHEARSAL_COUNTS
    if not trace:
        assert values == {}
    elif cell == "coll_w4_sweep":
        # the facade's counts by the benchmark's own readers: every warm
        # call a plan hit and one device interaction, a batched window
        # one interaction, nothing off the ring
        assert values == {
            "plan_hit_share": 100.0, "interactions_per_call": 1.0,
            "interactions_per_window": 1.0, "ring_fallbacks": 0,
        }
