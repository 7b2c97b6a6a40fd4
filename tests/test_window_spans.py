"""The stage spans of the batched window, on the CPU mesh.

``utils/profiling.py`` lists every span with its site.  Here four ranks
of ``xla_group(4)`` run warm ``with a.batch():`` windows of the sweep's
eight collectives under ``utils.trace``, each inside a ``bench::window``
span as the benchmark wraps them, then one blocking collective; the
recorded ``.xplane.pb`` is read back: which thread carries which span,
what lies in what, in which order.  (Times are the CPU's and are not
looked at.)
"""

import os
import threading

import jax
import numpy as np
import pytest

from helpers import (run_parallel, trace_in_order, trace_inside,
                     trace_spans)

WORLD = 4
N = 64
WINDOWS = 3
#: the sweep's window (``perfbench/workloads/sweep.json``)
OPS = ["allreduce", "allreduce", "reduce_scatter", "allgather",
       "allreduce", "allreduce", "reduce_scatter", "allgather"]
#: op -> (send count, receive count), in units of N
SHAPES = {"allreduce": (1, 1), "allgather": (1, WORLD),
          "reduce_scatter": (WORLD, 1)}
RING = ["plan", "deps", "encode", "assemble", "cmdring", "adopt", "park"]
#: what ``accl::cmdring[n]`` and the window's ``accl.window::ready`` hold
OPENED = {"cmdring": ["accl.ring::slots", "accl.ring::program"],
          "ready": ["accl.ring::wait", "accl.ring::status",
                    "accl.ring::settle"]}
FIVE = OPENED["cmdring"] + OPENED["ready"]


def _short(name):
    """``accl.ring::plan`` -> ``plan``; ``accl::cmdring[8]`` -> ``cmdring``."""
    return name.split("::")[1].split("[")[0]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Warm windows traced, then a blocking call traced apart: the two
    traces' spans by thread, and the interactions each warm window
    cost with the profiler off and on."""
    from accl_tpu import utils
    from accl_tpu.core import xla_group

    group = xla_group(WORLD)
    bufs = [
        [(a.create_buffer_from(
            np.full(SHAPES[op][0] * N, float(r + k), np.float32)),
          a.create_buffer(SHAPES[op][1] * N, np.float32))
         for k, op in enumerate(OPS)]
        for r, a in enumerate(group)
    ]
    gate = threading.Barrier(WORLD, timeout=60)

    def windows(a, r):
        for _ in range(WINDOWS):
            gate.wait()  # a window starts when the one before is done
            with jax.profiler.TraceAnnotation("bench::window"):
                with a.batch():
                    reqs = [
                        getattr(a, op)(send, recv, N, run_async=True)
                        for op, (send, recv) in zip(OPS, bufs[r])
                    ]
                for q in reqs:
                    assert q.wait(60)
                    q.check()

    def blocking(a, r):
        gate.wait()
        with jax.profiler.TraceAnnotation("bench::small::allreduce"):
            a.allreduce(bufs[r][0][0], bufs[r][0][1], N)

    counter = group[0].engine.gang.interactions
    try:
        run_parallel(group, windows)  # cold, then warm
        run_parallel(group, blocking)
        before = counter.read()
        run_parallel(group, windows)
        off = counter.read() - before
        batched = tmp_path_factory.mktemp("batched")
        before = counter.read()
        with utils.trace(str(batched), host_tracer_level=1):
            run_parallel(group, windows)
        on = counter.read() - before
        alone = tmp_path_factory.mktemp("blocking")
        with utils.trace(str(alone), host_tracer_level=1):
            run_parallel(group, blocking)
        for r in range(WORLD):  # the last window's results stand
            for op, (_, recv) in zip(OPS, bufs[r]):
                recv.sync_from_device()
                assert np.isfinite(recv.data).all()
    finally:
        for a in group:
            a.deinit()
    return {"batched": trace_spans(str(batched)),
            "blocking": trace_spans(str(alone)), "dir": str(batched),
            "blocking_dir": str(alone),
            "interactions": {"off": off, "on": on}}


def _rank_threads(by_thread):
    return {t: ev for t, ev in by_thread.items()
            if any(e[0] == "accl.batch::flush" for e in ev)}


def _of(events, name):
    return [e for e in events if e[0] == name]


def test_each_rank_thread_flushes_a_window_once_submit_then_drain(recorded):
    rank_threads = _rank_threads(recorded["batched"])
    assert len(rank_threads) == WORLD
    for events in rank_threads.values():
        flushes = _of(events, "accl.batch::flush")
        assert len(flushes) == WINDOWS and trace_in_order(flushes)
        for flush in flushes:
            stages = [e for e in trace_inside(events, flush)
                      if e[0].startswith("accl.batch::")]
            assert [e[0] for e in stages] == [
                "accl.batch::submit", "accl.batch::drain"
            ]
            assert trace_in_order(stages)
            assert stages[0][3]["n"] == str(len(OPS))
        # the eight queued calls come before their window's flush
        calls = _of(events, "accl.facade::call")
        assert len(calls) == WINDOWS * len(OPS)
        for k, flush in enumerate(flushes):
            mine = calls[k * len(OPS):(k + 1) * len(OPS)]
            assert mine[-1][2] <= flush[1]
            assert k == 0 or flushes[k - 1][2] <= mine[0][1]
    # no other submit or drain anywhere: none outside a flush
    everything = [e for ev in recorded["batched"].values() for e in ev]
    for name in ("submit", "drain"):
        assert len(_of(everything, "accl.batch::" + name)) == WORLD * WINDOWS


def test_ring_stages_lie_in_one_ring_batch_on_one_thread_in_order(recorded):
    rank_threads = _rank_threads(recorded["batched"])
    rings = [(t, e) for t, ev in rank_threads.items()
             for e in _of(ev, "accl.ring::batch")]
    assert len(rings) == WINDOWS  # one thread a window ran the program
    staged = 0
    for t, ring in rings:
        events = rank_threads[t]
        assert ring[3] == {"comm": "0", "n": str(len(OPS))}
        inside = [e for e in trace_inside(events, ring)
                  if e[0] not in OPENED["cmdring"]]
        assert [_short(e[0]) for e in inside] == RING
        assert inside[RING.index("cmdring")][0] == f"accl::cmdring[{len(OPS)}]"
        assert trace_in_order(inside)
        encode, park = inside[RING.index("encode")], inside[-1]
        assert encode[3].keys() == {"window"} and encode[3] == park[3]
        # the slot executes inside the submit of the rank that completed it
        (submit,) = [e for e in _of(events, "accl.batch::submit")
                     if e[1] <= ring[1] and ring[2] <= e[2]]
        staged += len(inside)
    # every ring stage of the trace lies in one of those spans
    everything = [e for ev in recorded["batched"].values() for e in ev]
    assert sum(e[0].startswith(("accl.ring::", "accl::cmdring"))
               and e[0] not in FIVE
               for e in everything) == WINDOWS + staged
    windows = sorted(int(e[3]["window"]) for e in everything
                     if e[0] == "accl.ring::park")
    assert windows == list(range(windows[0], windows[0] + WINDOWS))


def test_the_drainer_learns_and_completes_each_window(recorded):
    by_thread = recorded["batched"]
    rank_threads = _rank_threads(by_thread)
    (drainer,) = [ev for t, ev in by_thread.items() if t not in rank_threads]
    drainer = [e for e in drainer if e[0] not in OPENED["ready"]]
    assert [e[0] for e in drainer] == WINDOWS * [
        "accl.window::ready", "accl.window::complete"
    ]
    assert trace_in_order(drainer)
    parks = sorted((e for ev in rank_threads.values()
                    for e in _of(ev, "accl.ring::park")), key=lambda e: e[1])
    flushes = [sorted(_of(ev, "accl.batch::flush"), key=lambda e: e[1])
               for ev in rank_threads.values()]
    for k, (park, ready, done) in enumerate(
            zip(parks, drainer[::2], drainer[1::2])):
        assert park[1] <= ready[1]  # parked from inside the ring's span
        # the next window's flushes start after this one is completed
        if k + 1 < WINDOWS:
            assert all(f[k + 1][1] >= done[2] for f in flushes)


def test_the_program_call_opens_into_slots_then_program(recorded):
    """Inside the ONE ``accl::cmdring[n]`` a window, on its thread: the
    slot words' put, then the program's lookup and call, and nothing
    else of the program's."""
    rank_threads = _rank_threads(recorded["batched"])
    found = 0
    for events in rank_threads.values():
        for cmdring in events:
            if not cmdring[0].startswith("accl::cmdring["):
                continue
            inside = trace_inside(events, cmdring)
            assert [e[0] for e in inside] == OPENED["cmdring"]
            assert trace_in_order(inside)
            assert all(e[3] == {} for e in inside)
            found += 1
    assert found == WINDOWS
    everything = [e for ev in recorded["batched"].values() for e in ev]
    for name in OPENED["cmdring"]:  # one a window, none elsewhere
        assert len(_of(everything, name)) == WINDOWS


def test_the_windows_ready_opens_into_wait_status_settle_by_id(recorded):
    """Inside the ONE ``accl.window::ready`` a window, on the drainer:
    the wait, the status words' read, the settle, in order, each with
    the ``window`` stat of that window's ``accl.ring::encode``."""
    by_thread = recorded["batched"]
    rank_threads = _rank_threads(by_thread)
    (drainer,) = [ev for t, ev in by_thread.items() if t not in rank_threads]
    encodes = sorted((e for ev in rank_threads.values()
                      for e in _of(ev, "accl.ring::encode")),
                     key=lambda e: e[1])
    readies = _of(drainer, "accl.window::ready")
    assert len(readies) == len(encodes) == WINDOWS
    for encode, ready in zip(encodes, readies):
        inside = trace_inside(drainer, ready)
        assert [e[0] for e in inside] == OPENED["ready"]
        assert trace_in_order(inside)
        assert all(e[3] == encode[3] for e in inside)
    everything = [e for ev in by_thread.values() for e in ev]
    for name in OPENED["ready"]:  # one a window, on the drainer alone
        assert len(_of(everything, name)) == len(_of(drainer, name)) == WINDOWS


@pytest.mark.parametrize("name", FIVE)
def test_a_blocking_collective_emits_none_of_the_five(recorded, name):
    everything = [e for ev in recorded["blocking"].values() for e in ev]
    assert len(_of(everything, "accl.window::ready")) == 1
    assert not _of(everything, name)


def test_the_flushes_of_a_window_share_a_batch_stat(recorded):
    rank_threads = _rank_threads(recorded["batched"])
    per_thread = [
        [e[3] for e in _of(ev, "accl.batch::flush")]
        for ev in rank_threads.values()
    ]
    for stats in zip(*per_thread):  # window by window, the four threads'
        assert all(s.keys() == {"comm", "batch"} for s in stats)
        assert len({s["batch"] for s in stats}) == 1
        assert {s["comm"] for s in stats} == {"0"}
    batches = [s["batch"] for s in per_thread[0]]
    assert len(set(batches)) == WINDOWS
    # submit and drain carry their window's number too
    for ev in rank_threads.values():
        for flush in _of(ev, "accl.batch::flush"):
            assert {e[3]["batch"] for e in trace_inside(ev, flush)
                    if e[0].startswith("accl.batch::")} == {
                flush[3]["batch"]}


def test_a_warm_window_is_one_interaction_profiler_on_or_off(recorded):
    assert recorded["interactions"] == {"off": WINDOWS, "on": WINDOWS}


def test_a_blocking_collective_emits_no_batch_or_ring_span(recorded):
    everything = [e for ev in recorded["blocking"].values() for e in ev]
    assert len(_of(everything, "accl.facade::call")) == WORLD
    assert len(_of(everything, "accl::allreduce")) == 1
    assert not [e[0] for e in everything
                if e[0].startswith(("accl.batch::", "accl.ring::",
                                    "accl::cmdring"))]


def test_the_benchmarks_reader_understands_the_recorded_windows(recorded):
    """``perfbench/window_spans.py`` on the trace recorded here: every
    window whole, every stage found, the stages tiling the union (no
    device plane on the CPU: the two lags have nothing to read)."""
    from perfbench import stage_spans, trace_reduce, window_spans as ws

    windows = ws.group(stage_spans.load(
        trace_reduce.find_xplane(recorded["dir"])
    ))
    assert len(windows) == WINDOWS
    for w in windows:
        assert len(ws.rank_windows(w)) == WORLD
        assert all(ws.queue(rw) > 0 for rw in ws.rank_windows(w))
        for stage in (ws.rendezvous, ws.arrival_spread, ws.deps_encode,
                      ws.adopt_park, ws.to_ready, ws.wake, ws.ring_rest):
            assert stage(w) >= 0
        assert ws.ready_lag(w) is None and ws.launch_lag(w) is None
        union = w["end"] - w["start"]
        assert abs(ws.tiled(w) - union) < 0.05 * union
    table = ws.report(windows)
    assert table["windows"] == WINDOWS
    assert all(table[name] is not None for name in (ws.RING, *ws.PARTS))


def test_the_runtime_reader_understands_the_recorded_windows(recorded):
    """``perfbench/runtime_spans.py`` on the trace recorded here: no
    window ``window_spans.group`` kept is lost to the runtime's events,
    the two opened spans are found, the program call with the runtime's
    events inside it and the slot words' span with none (nothing is put
    for a warm window), the
    drainer's three join the launching thread's by id, and the CPU
    client's execute event lies inside the program call (no device
    plane on the CPU: the two lags have nothing to read)."""
    from perfbench import (runtime_spans as rs, stage_spans, trace_reduce,
                           window_spans as ws)

    path = trace_reduce.find_xplane(recorded["dir"])
    windows = ws.group(rs.load(path))
    assert len(windows) == len(ws.group(stage_spans.load(path))) == WINDOWS
    for w in windows:
        cmdring, ready = ws.one(w, ws.CMDRING), ws.one(w, ws.READY)
        assert 0 < rs.slots_put(w) + rs.program_call(w) <= cmdring[2]
        assert 0 < rs.window_execute(w) <= rs.program_call(w)
        assert rs.inside(w, ws.one(w, rs.PROGRAM), ("PjitFunction(",))
        # a warm window: its slot words are on the devices already, the
        # span holds a lookup and no put (PR 51)
        assert not rs.inside(
            w, ws.one(w, rs.SLOTS), ("DevicePutWithSharding",)
        )
        wait = rs.duration(w, rs.WAIT)
        assert 0 < wait + rs.status_read(w) <= ready[2]
        assert rs.joined_by_id(w) is True
        assert rs.window_pickup(w) is not None
        assert rs.gate_spread(w) >= 0
        assert rs.launch_lag(w) is None and rs.wait_lag(w) is None
    table = rs.report_windows(windows)
    assert table["windows"] == WINDOWS
    assert table["joined_by_id_share"] == 1.0
    assert all(table[k] is not None for k in (
        rs.SLOTS, rs.PROGRAM, rs.WAIT, rs.STATUS, rs.SETTLE, "execute",
        "pickup", "gate_spread", "arrival_spread"))


def test_the_runtime_reader_understands_the_recorded_blocking_call(recorded):
    """The blocking call has no new span: its three readers read what
    was there (the dispatch with the client's execute event inside it,
    the hand-over to the drainer, the ``bench::`` starts)."""
    from perfbench import runtime_spans as rs, stage_spans, trace_reduce

    (call,) = stage_spans.group(rs.load(
        trace_reduce.find_xplane(recorded["blocking_dir"])
    ))
    assert len(call["bench"]) == WORLD
    dispatch = stage_spans.one(call, rs.DISPATCH)
    assert 0 < rs.call_execute(call) <= dispatch[2]
    assert rs.completion_pickup(call) is not None
    assert rs.gate_spread(call) >= 0
    table = rs.report_calls([call])
    assert table["calls"] == 1 and table["execute"] is not None


def test_every_recorded_span_has_its_row_in_the_span_table(recorded):
    """``utils/profiling.py``'s docstring lists every host span: each
    name the two traces hold is there (``accl::<op>`` and
    ``accl::cmdring[n]`` under their patterns)."""
    from accl_tpu.utils import profiling

    names = {e[0] for trace in ("batched", "blocking")
             for ev in recorded[trace].values() for e in ev}
    assert {n for n in names if n.startswith(("accl.batch::", "accl.ring::"))
            } == {"accl.batch::" + s for s in ("flush", "submit", "drain")
                  } | {"accl.ring::" + s for s in
                       ("batch", "plan", "deps", "encode", "assemble",
                        "adopt", "park")} | set(FIVE)
    table = profiling.__doc__
    for name in names:
        if name.startswith("accl::cmdring["):
            name = "accl::cmdring[n]"
        elif name.startswith("accl::"):
            name = "accl::<op>"
        assert f"``{name}``" in table, name


def test_annotate_is_the_one_place_a_trace_annotation_is_made():
    import accl_tpu

    root = os.path.dirname(accl_tpu.__file__)
    found = []
    for base, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as src:
                    if "TraceAnnotation" in src.read():
                        found.append(os.path.relpath(path, root))
    assert found == [os.path.join("utils", "profiling.py")]
