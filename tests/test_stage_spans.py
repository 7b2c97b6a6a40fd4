"""The stage spans of the blocking gang call, on the CPU mesh.

``utils/profiling.py`` lists every span with its site.  Here four ranks
of ``xla_group(4)`` run warm blocking calls under ``utils.trace`` and
the recorded ``.xplane.pb`` is read back: which thread carries which
span, what lies in what, in which order.  (The profiler records host
TraceAnnotations on the CPU backend too; times are the CPU's and are
not looked at.)
"""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from helpers import (run_parallel, trace_in_order, trace_inside,
                     trace_spans)

WORLD = 4
N = 64
CALLS = 2
FACADE = ["prepare", "plan", "membership", "arbiter", "contract", "meta",
          "submit", "wait"]
GANG = ["assemble", "dispatch", "adopt", "park"]
#: op -> (send count, receive count), in units of N
SHAPES = {
    "allreduce": (1, 1),
    "allgather": (1, WORLD),
    "reduce_scatter": (WORLD, 1),
    "alltoall": (WORLD, WORLD),
}


@pytest.fixture(scope="module")
def group():
    from accl_tpu.core import xla_group

    g = xla_group(WORLD)
    yield g
    for a in g:
        a.deinit()


@pytest.mark.parametrize("op", sorted(SHAPES))
def test_stage_spans_of_a_blocking_gang_call(group, op, tmp_path):
    from accl_tpu import utils

    send_n, recv_n = SHAPES[op]
    bufs = [
        (a.create_buffer_from(np.full(send_n * N, float(r), np.float32)),
         a.create_buffer(recv_n * N, np.float32))
        for r, a in enumerate(group)
    ]
    gate = threading.Barrier(WORLD, timeout=60)

    def calls(a, r):
        for _ in range(CALLS):
            gate.wait()  # a call starts when the one before has returned
            getattr(a, op)(bufs[r][0], bufs[r][1], N)

    counter = group[0].engine.gang.interactions
    run_parallel(group, calls)  # cold, then warm
    before = counter.read()
    run_parallel(group, calls)
    assert counter.read() - before == CALLS  # profiler off: 1 a warm call
    before = counter.read()
    with utils.trace(str(tmp_path), host_tracer_level=1):
        run_parallel(group, calls)
    assert counter.read() - before == CALLS  # and 1 with it on

    by_thread = trace_spans(str(tmp_path))
    rank_threads = {t: ev for t, ev in by_thread.items()
                    if any(e[0] == "accl.facade::call" for e in ev)}
    assert len(rank_threads) == WORLD
    engine_spans = []
    for events in rank_threads.values():
        outer = [e for e in events if e[0] == "accl.facade::call"]
        assert len(outer) == CALLS and trace_in_order(outer)
        for call in outer:
            stages = [e for e in trace_inside(events, call)
                      if e[0].startswith("accl.facade::")]
            assert [e[0] for e in stages] == [
                "accl.facade::" + s for s in FACADE
            ]
            prepare, plan = stages[:2]  # the plan lookup lies in prepare
            assert prepare[1] <= plan[1] and plan[2] <= prepare[2]
            assert trace_in_order(stages[1:]) and prepare[2] <= stages[2][1]
            submit = stages[FACADE.index("submit")]
            ran = [e for e in trace_inside(events, submit)
                   if e[0].startswith("accl::")]
            for engine in ran:
                assert engine[0] == "accl::" + op
                gang = [e for e in trace_inside(events, engine)]
                assert [e[0] for e in gang] == [
                    "accl.gang::" + s for s in GANG
                ]
                assert trace_in_order(gang)
                assert all(e[3] == {"comm": "0"} for e in gang)
            engine_spans += ran
        # nothing of the engine or the window outside a submit
        assert sum(e[0].startswith(("accl::", "accl.gang::"))
                   for e in events) == (1 + len(GANG)) * sum(
            e[0].startswith("accl::") for e in events)
    # exactly one thread a gang call ran the program
    assert len(engine_spans) == CALLS and trace_in_order(
        sorted(engine_spans, key=lambda e: e[1])
    )
    # and a thread that is no rank's completed it: ready, then complete
    (drainer,) = [ev for t, ev in by_thread.items() if t not in rank_threads]
    assert [e[0] for e in drainer] == CALLS * [
        "accl.window::ready", "accl.window::complete"
    ]
    assert trace_in_order(drainer)
    for engine, ready in zip(sorted(engine_spans, key=lambda e: e[1]),
                             drainer[::2]):
        assert engine[1] <= ready[1]  # parked from inside the engine's span


def test_annotate_off_jax_is_the_shared_no_op():
    """In a process that has not imported jax, ``annotate`` imports
    nothing and hands out ONE null context; the in-flight window (shared
    with the jax-free emulator tiers) completes a parked call through
    its two spans."""
    code = """
import contextlib, sys, threading
from accl_tpu.utils.profiling import annotate, annotated
from accl_tpu.overlap import InflightWindow
a = annotate("accl.window::ready")
assert isinstance(a, contextlib.nullcontext)
assert a is annotate("accl.gang::assemble", comm=3)
assert annotated("accl.facade::call")(lambda x: x + 1)(1) == 2
done = threading.Event()
w = InflightWindow(depth=2)
w.park("k", lambda: None, lambda *facts: done.set(), lambda exc: None)
assert done.wait(30) and w.drain(30)
w.stop()
assert "jax" not in sys.modules
print("ok")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_annotate_on_jax_is_the_trace_annotation_itself():
    import jax

    from accl_tpu.utils.profiling import annotate

    span = annotate("accl.gang::dispatch", comm=0)
    assert type(span) is jax.profiler.TraceAnnotation
    with span:
        pass
