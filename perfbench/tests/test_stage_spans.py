"""stage_spans.py and the ten readers on a fixture with known answers.

``data/stage_calls.json`` is written by hand (ns): a world of four rank
threads R0-R3 and the drainer D.

* call A, allreduce, union 1000..9200.  R3 arrives last and runs the
  program: ``accl::allreduce`` 1600..5500 holding assemble 500, dispatch
  2200..4200, adopt 600, park 400.  Drainer: ready ..7000, complete
  ..8000.  Last device op ends 6000, first starts 4500.
  Intake a rank 200, 220, 240, 260; plan 30, 40, 50, 60; planes 75, 90,
  110, 130; prepare 75 on every rank (5 after the call starts to 10
  before membership), so 50, 55, 55, 55 of intake lie in no sub-span,
  and 3900 - 3500 = 400 of the engine's span.  Rendezvous 1600 - 1010 = 590, completion 8000 - 5500 =
  2500, wake 9200 - 8000 = 1200, ready lag 7000 - 6000 = 1000, launch
  lag 4500 - 2200 = 2300.
* call B, allgather, union 11000..19500.  R0 arrives last:
  ``accl::allgather`` 11510..16410, assemble 300, dispatch 12000..15000,
  adopt 400.  Drainer: ready ..17000, complete ..18000.  Device ops
  13000..16500.  Intake 190 on every rank, plan 20, planes 60, prepare
  75 (55 in no sub-span); 4900 - 4400 = 500 of the engine's span.
  Rendezvous 500, completion 1590, wake 1500, ready lag 500, launch lag
  1000.
* call C is cut by the slice's edge (three bench spans): left out.

Medians: over 8 rank calls the middle two; over 2 gang calls the mean.
"""

import importlib
import json
import os
import threading

import pytest

from perfbench import manifest, stage_spans as ss

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = ("facade_intake_us", "facade_plan_us", "facade_planes_us",
       "gang_rendezvous_us", "engine_assemble_us", "engine_dispatch_us",
       "engine_adopt_us", "completion_us", "wake_us", "ready_lag_us")


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(DATA, "stage_calls.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(fixture, monkeypatch):
    calls = ss.group(fixture)
    monkeypatch.setattr(ss, "calls_of", lambda ctx: calls)
    return {"cell": {"name": "coll_w4_sweep"}, "slices": {"small": {}}}


def test_grouping_cuts_the_slice_into_whole_gang_calls(fixture):
    calls = ss.group(fixture)
    assert [c["op"] for c in calls] == fixture["expect"]["ops"]
    a, b = calls
    assert (a["start"], a["end"]) == (1000, 9200)
    assert (b["start"], b["end"]) == (11000, 19500)
    assert sorted(e[3] for e in a["bench"]) == ["R0", "R1", "R2", "R3"]
    # everything that starts inside the union, and nothing of call C
    assert len(a["host"]) == 4 * 9 + 7 and len(b["host"]) == 4 * 9 + 7
    assert (a["device_start"], a["device_end"]) == (4500, 6000)
    assert (b["device_start"], b["device_end"]) == (13000, 16500)


def test_the_executing_thread_is_whichever_rank_arrived_last(fixture):
    a, b = ss.group(fixture)
    assert ss.one(a, ss.ENGINE)[3] == "R3"
    assert ss.one(b, ss.ENGINE)[3] == "R0"
    # the rank thread that ran the program holds the engine's span in
    # its submit; the others' submits are short
    by_thread = {rc[ss.CALL][3]: rc for rc in ss.rank_calls(a)}
    assert by_thread["R3"][ss.SUBMIT][2] == 4000
    assert by_thread["R0"][ss.SUBMIT][2] == 50
    assert [ss.intake(rc) for rc in ss.rank_calls(a)] == [200, 220, 240, 260]
    assert [ss.planes(rc) for rc in ss.rank_calls(a)] == [75, 90, 110, 130]


def test_stages_tile_the_call(fixture):
    for call in ss.group(fixture):
        engine = ss.one(call, ss.ENGINE)
        tiled = (ss.rendezvous(call) + engine[2] + ss.completion(call)
                 + ss.wake(call))
        # short of the union by the benchmark's own entry into the facade
        first_call = min(e[1] for e in ss.spans(call, ss.CALL))
        assert tiled == call["end"] - first_call


def test_report_gives_what_no_sub_span_covers_and_the_tiling(fixture):
    table = ss.report(ss.group(fixture))
    expect = fixture["expect"]
    assert table["calls"] == 2
    assert table["intake_rest"] == pytest.approx(expect["intake_rest_us"])
    assert table["engine_rest"] == pytest.approx(expect["engine_rest_us"])
    assert table["tiled"] == pytest.approx(expect["tiled_us"])
    assert table["union"] == pytest.approx(expect["union_us"])
    assert table[ss.PREPARE] == pytest.approx(0.075)
    assert table["ready_lag_negative_share"] == 0.0
    for name in NEW:  # the table and the readers agree
        key = {"facade_intake_us": "intake", "facade_planes_us": "planes",
               "gang_rendezvous_us": "rendezvous", "facade_plan_us": ss.PLAN,
               "engine_assemble_us": ss.ASSEMBLE,
               "engine_dispatch_us": ss.DISPATCH,
               "engine_adopt_us": ss.ADOPT}.get(name, name[:-3])
        assert table[key] == pytest.approx(expect[name])


def test_every_new_metric_is_in_the_manifest_for_the_sweep_only():
    entries = {m["name"]: m for m in manifest.load()["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == ["coll_w4_sweep"]
        assert entries[name]["moves"] == "coll_small_p50"
        assert entries[name]["unit"] == "us"


def test_launch_lag_is_read_beside_the_ready_lag(fixture):
    calls = ss.group(fixture)
    assert ss.median_us(ss.launch_lag(c) for c in calls) == pytest.approx(
        fixture["expect"]["launch_lag_us"]
    )


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_answer_worked_out_by_hand(name, ctx, fixture):
    reader = importlib.import_module("perfbench.layer_metrics." + name)
    assert reader.read(ctx) == pytest.approx(fixture["expect"][name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_stage_spans(
        name, fixture, monkeypatch):
    """The parent commit: bench:: and accl::<op> spans only.  Nothing to
    read is None, never an exception."""
    old = {"host": [e for e in fixture["host"]
                    if not e[0].startswith("accl.")],
           "devices": fixture["devices"]}
    calls = ss.group(old)
    assert len(calls) == 2
    monkeypatch.setattr(ss, "calls_of", lambda ctx: calls)
    reader = importlib.import_module("perfbench.layer_metrics." + name)
    assert reader.read({"cell": {"name": "x"}, "slices": {}}) is None


def test_no_slice_or_no_trace_file_is_no_call(tmp_path, monkeypatch):
    assert ss.calls_of({"cell": {"name": "x"}, "slices": {}}) == []
    monkeypatch.setattr(manifest, "CHECKOUT", str(tmp_path))
    assert ss.calls_of({"cell": {"name": "x"},
                        "slices": {"small": {}}}) == []


def test_a_call_with_two_engine_spans_is_left_out_not_guessed(fixture):
    twice = dict(fixture, host=fixture["host"] + [
        ["accl::allreduce", 1700, 100, "R2", {}]
    ])
    a, b = ss.group(twice)
    assert ss.rendezvous(a) is None and ss.completion(a) is None
    assert ss.rendezvous(b) == 500


def test_load_keeps_thread_and_stats_of_a_recorded_trace(tmp_path):
    """A trace recorded here, on the CPU: two threads, nested spans, a
    keyword stat.  (No device plane on the CPU: ``devices`` is empty.)"""
    import jax

    from perfbench import trace_reduce

    gate = threading.Barrier(2, timeout=30)  # both alive at once: two lines

    def rank():
        gate.wait()
        with jax.profiler.TraceAnnotation("bench::small::allreduce"):
            with jax.profiler.TraceAnnotation("accl.facade::call"):
                with jax.profiler.TraceAnnotation("accl.gang::dispatch",
                                                  comm=7):
                    pass
            with jax.profiler.TraceAnnotation("not ours"):
                pass
        gate.wait()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        threads = [threading.Thread(target=rank) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        jax.profiler.stop_trace()
    events = ss.load(trace_reduce.find_xplane(str(tmp_path)))
    assert events["devices"] == {}
    names = sorted(e[0] for e in events["host"])
    assert names == sorted(2 * ["bench::small::allreduce",
                                "accl.facade::call", "accl.gang::dispatch"])
    assert len({e[3] for e in events["host"]}) == 2
    for e in events["host"]:
        assert e[4] == ({"comm": "7"} if e[0] == ss.DISPATCH else {})
    # the older reduction does not see the stage spans
    reduced = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    assert {e[0] for e in reduced["host"]} == {"bench::small::allreduce"}
