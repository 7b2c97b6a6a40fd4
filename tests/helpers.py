"""Shared helpers: drive one call per rank concurrently, like the reference's
mpirun-launched per-rank host processes."""

from __future__ import annotations

import glob
import os
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
