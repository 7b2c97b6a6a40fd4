"""runtime_spans.py and the twelve readers on a fixture with known answers.

``data/runtime_calls.json`` is written by hand (ns): a world of four
rank threads R0-R3, the drainer D and two device planes; a ``window``
slice of two windows and a ``small`` slice of two gang calls, with the
runtime's own events (``PjitFunction(...)``, ``ParseArguments``,
``DevicePutWithSharding``, the client's execute) inside the dispatch
spans as a trace of the CPU mesh holds them.

* window A, run by R3.  ``bench::window`` starts 1000, 1100, 1250, 1400
  (the gate's spread 400); submits 1500, 1650, 1900, 2100 (arrival
  spread 600).  ``accl::cmdring[8]`` 5000..9000 holds ``accl.ring::slots``
  5010..6210 (1200) and ``accl.ring::program`` 6250..8950 (2700): 3900 of
  4000.  Inside the program, on R3, the execute event 6500..8500 (2000);
  R0 carries one in that interval too, which is not this call's.  Park
  ends 11500, ``accl.window::ready`` 11700..16100 (pickup 200) holds
  wait 11720..15000, status 800, settle 150 (status read 950; 4230 of
  4400), all with ``window`` 40 as encode and park.  Device ops
  8000..14000: launch lag 8000 - 6250 = 1750, wait lag 1000.
* window B, run by R1, every lag the other way round, under the TPU
  plugin's names as ``load`` hands them over (the calling thread's
  execute under R1; two chips' worker threads under their own: prepare
  1500 and 1400 with the output buffers' 1400 and 1300 inside, launch
  300 and 400).  Gate's spread 600, arrival spread 800; slots 1800,
  program 36400..39400 (3000; 4800 of 5000), execute 2400.  The first
  device op starts at 36000, 400
  BEFORE the program span; the drainer enters ``ready`` at 41000, 200
  before park's end; the wait ends 43500, 700 before the last op's end
  (44200).  Status 500 + settle 400 = 900.
* call A (allreduce), run by R2: ``bench::small::allreduce`` starts 1000,
  1300, 1380, 1150 (380); ``accl.gang::dispatch`` 2100..3000 with the
  execute event 2300..2900 (600); park ends 3400, ready starts 3450 (50).
* call B (allgather), run by R0: starts spread 400; execute 700 (R3
  carries another); ready starts 12560, 40 before park's end.

Medians over two are the mean.
"""

import importlib
import json
import os

import pytest

from perfbench import manifest, runtime_spans as rs, stage_spans
from perfbench import window_spans as ws

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
WINDOW = {"ring_slots_put_us": "command ring",
          "ring_program_call_us": "command ring",
          "ring_execute_us": "command ring", "ring_launch_lag_us": "device",
          "batch_gate_spread_us": "facade",
          "batch_arrival_spread_us": "facade",
          "window_pickup_us": "gang engine", "window_wait_lag_us": "device",
          "window_status_read_us": "command ring"}
SMALL = {"gate_release_spread_us": "facade",
         "engine_execute_us": "gang engine",
         "completion_pickup_us": "gang engine"}
NEW = {**WINDOW, **SMALL}
#: the readers that read a span this PR's program adds, or the client's
#: execute event: against the parent's program they find nothing
NEEDS_NEW_SPANS = ("ring_slots_put_us", "ring_program_call_us",
                   "ring_execute_us", "ring_launch_lag_us",
                   "window_wait_lag_us", "window_status_read_us")
NEEDS_EXECUTE = ("ring_execute_us", "engine_execute_us")
FIVE = (rs.SLOTS, rs.PROGRAM, rs.WAIT, rs.STATUS, rs.SETTLE)


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(DATA, "runtime_calls.json")) as f:
        return json.load(f)


def _ctx(monkeypatch, window_events, small_events):
    windows, calls = ws.group(window_events), stage_spans.group(small_events)
    monkeypatch.setattr(rs, "windows_of", lambda ctx: windows)
    monkeypatch.setattr(rs, "calls_of", lambda ctx: calls)
    return {"cell": {"name": "coll_w4_sweep"},
            "slices": {"window": {}, "small": {}}}


def _without(events, drop):
    return dict(events, host=[e for e in events["host"] if not drop(e[0])])


def _read(name, ctx):
    return importlib.import_module("perfbench.layer_metrics." + name).read(ctx)


def test_the_runtimes_events_do_not_cost_a_window_or_a_call(fixture):
    """Grouping is window_spans' and stage_spans' own: both windows and
    both calls whole, run by whichever rank arrived last, the runtime's
    events among their host events."""
    windows = ws.group(fixture["window"])
    calls = stage_spans.group(fixture["small"])
    assert [ws.one(w, ws.RING)[3] for w in windows] == fixture["expect"][
        "runners"]
    assert [stage_spans.one(c, stage_spans.ENGINE)[3] for c in calls] == (
        fixture["expect"]["call_runners"])
    plain = ws.group(_without(
        fixture["window"],
        lambda n: not n.startswith(stage_spans.HOST_PREFIXES)))
    assert [(w["start"], w["end"]) for w in windows] == [
        (w["start"], w["end"]) for w in plain]
    assert len(windows[0]["host"]) - len(plain[0]["host"]) == 7


def test_an_execute_event_counts_on_its_dispatchs_thread_alone(fixture):
    a, b = ws.group(fixture["window"])
    program = ws.one(a, rs.PROGRAM)
    assert [e[3] for e in a["host"] if e[0] == rs.EXECUTE[0]] == ["R3", "R0"]
    assert [e[3] for e in b["host"] if e[0] == rs.EXECUTE[1]] == ["R1"]
    assert [e[3] for e in rs.inside(a, program)] == ["R3"]
    assert rs.window_execute(a) == 2000 and rs.window_execute(b) == 2400
    assert [e[0] for e in rs.inside(a, program, rs.JAXLIB)] == [
        "PjitFunction(body)", "PjitFunction(body)", "ParseArguments"]
    call_a, call_b = stage_spans.group(fixture["small"])
    assert rs.call_execute(call_a) == 600 and rs.call_execute(call_b) == 700
    # two execute events inside one dispatch: not guessed at
    twice = dict(call_a, host=call_a["host"] + [
        [rs.EXECUTE[0], 2950, 20, "R2", {}]])
    assert rs.call_execute(twice) is None


def test_lags_and_hand_overs_are_not_clamped(fixture):
    a, b = ws.group(fixture["window"])
    assert (rs.launch_lag(a), rs.launch_lag(b)) == (1750, -400)
    assert (rs.window_pickup(a), rs.window_pickup(b)) == (200, -200)
    assert (rs.wait_lag(a), rs.wait_lag(b)) == (1000, -700)
    assert (rs.status_read(a), rs.status_read(b)) == (950, 900)
    assert (rs.gate_spread(a), rs.gate_spread(b)) == (400, 600)
    assert rs.joined_by_id(a) is True and rs.joined_by_id(b) is True
    call_a, call_b = stage_spans.group(fixture["small"])
    assert (rs.completion_pickup(call_a), rs.completion_pickup(call_b)) == (
        50, -40)
    table = rs.report_windows([a, b])
    assert table["windows"] == 2
    for key in ("launch_lag", "pickup", "wait_lag"):
        assert table[key + "_negative_share"] == 0.5
    assert table["cmdring_covered_share"] == pytest.approx(
        (3900 / 4000 + 4800 / 5000) / 2)
    assert table["ready_covered_share"] == pytest.approx(
        (4230 / 4400 + 3380 / 3600) / 2)
    assert table["runtime_in_program"]["PjitFunction(body)"] == (
        pytest.approx((2.6 + 2.9) / 2))  # nested namesakes: their union
    assert table["runtime_in_slots"] == {
        "DevicePutWithSharding": pytest.approx(0.35),
        "shard_args": pytest.approx(0.5)}
    # the chips' worker threads, inside window B's program call alone: a
    # thread's union, averaged over the threads
    assert table["workers_in_program"] == {
        "AllocateOutputBuffersWithInputReuse": pytest.approx(1.35),
        "CommonPjRtLoadedExecutable::ExecutePrepare": pytest.approx(1.45),
        "TpuLoadedExecutable::ExecuteLaunch": pytest.approx(0.35)}
    assert table["runtime_in_ready"] == {
        "CommonPjRtBuffer::ToLiteral": pytest.approx(0.04),
        "np.asarray(jax.Array)": pytest.approx(0.42)}
    assert table["device_start_before_program_end_share"] == 1.0
    small = rs.report_calls([call_a, call_b])
    assert small["pickup_negative_share"] == 0.5
    assert small["launch_lag"] == pytest.approx((0.8 + 0.95) / 2)


def test_a_window_whose_drainer_spans_carry_another_id_is_seen(fixture):
    host = [e if e[0] != rs.STATUS else e[:4] + [{"window": "99"}]
            for e in fixture["window"]["host"]]
    a, b = ws.group(dict(fixture["window"], host=host))
    assert rs.joined_by_id(a) is False
    assert rs.report_windows([a, b])["joined_by_id_share"] == 0.0


def test_every_new_metric_is_in_the_manifest_for_the_sweep_only():
    doc = manifest.load()
    entries = {m["name"]: m for m in doc["per_layer"]}
    for name, layer in NEW.items():
        assert entries[name] == {
            "name": name, "unit": "us", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": ("coll_batched_p50" if name in WINDOW
                      else "coll_small_p50"),
            "workloads": ["coll_w4_sweep"],
        }
    # appended after the 78 the parent had, together and in this order
    names = [m["name"] for m in doc["per_layer"]]
    first = names.index("ring_slots_put_us")
    assert first >= 78 and names[first:first + 12] == list(NEW)
    cell = manifest.cell(doc, "coll_w4_sweep")
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_answer_worked_out_by_hand(
        name, fixture, monkeypatch):
    ctx = _ctx(monkeypatch, fixture["window"], fixture["small"])
    assert _read(name, ctx) == pytest.approx(fixture["expect"][name])


@pytest.mark.parametrize("name", NEW)
def test_reader_against_the_parents_program(name, fixture, monkeypatch):
    """The parent commit has none of the five spans.  A reader of one of
    them finds nothing, never an exception; a reader of what the parent
    already emits (the ``bench::`` starts, the submits, park and ready,
    the blocking call's dispatch) reads the same number there."""
    ctx = _ctx(monkeypatch,
               _without(fixture["window"], lambda n: n in FIVE),
               fixture["small"])
    assert len(rs.windows_of(ctx)) == 2  # still whole windows
    if name in NEEDS_NEW_SPANS:
        assert _read(name, ctx) is None
    else:
        assert _read(name, ctx) == pytest.approx(fixture["expect"][name])


@pytest.mark.parametrize("name", NEW)
def test_reader_against_a_trace_without_the_clients_execute_event(
        name, fixture, monkeypatch):
    drop = lambda n: n in rs.EXECUTE  # noqa: E731
    ctx = _ctx(monkeypatch, _without(fixture["window"], drop),
               _without(fixture["small"], drop))
    if name in NEEDS_EXECUTE:
        assert _read(name, ctx) is None
    else:
        assert _read(name, ctx) == pytest.approx(fixture["expect"][name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_a_slice(name, monkeypatch, tmp_path):
    assert _read(name, {"cell": {"name": "x"}, "slices": {}}) is None
    monkeypatch.setattr(manifest, "CHECKOUT", str(tmp_path))
    assert _read(name, {"cell": {"name": "x"},
                        "slices": {"window": {}, "small": {}}}) is None


def test_idle_seconds_go_to_the_innermost_span_or_event():
    """Chip 0 runs one op 500..600 of a slice 0..1000: the gap before it
    is cut at the host events' edges, and each piece goes to the shortest
    event covering it, whatever its thread; the gap after it has the
    ``bench::`` span alone."""
    events = {
        "host": [["bench::window", 0, 1000, "R0", {}],
                 ["accl.ring::batch", 100, 300, "R0", {}],
                 ["AllocateOutputBuffersWithInputReuse", 200, 100,
                  "py_xla_execute/937#17", {}]],
        "devices": {"/device:TPU:0": [["fusion.1", 500, 100]],
                    "/device:TPU:1": [["fusion.1", 0, 1000]]},
    }
    assert rs.idle_by_innermost(events) == [
        ["bench::window", pytest.approx(600e-9)],
        ["accl.ring::batch", pytest.approx(200e-9)],
        ["AllocateOutputBuffersWithInputReuse", pytest.approx(100e-9)]]
    assert rs.idle_by_innermost(dict(events, devices={})) == []


class _Fake:
    """A plane, a line or an event of ``ProfileData``, as far as the
    loaders read one."""

    def __init__(self, name, children=(), start=0, dur=0):
        self.name, self.lines, self.events = name, children, children
        self.start_ns, self.duration_ns, self.stats = start, dur, ()


def test_load_hands_the_plugins_nameless_line_to_the_holders_thread(
        monkeypatch):
    """The TPU plugin's recorder writes a calling thread's events on a
    line WITHOUT a name: inside ONE thread's holder span they get that
    thread, inside two threads' or none they are left out; a chip's
    worker thread keeps its own; any other thread's events are not
    read."""
    import jax.profiler

    ev = _Fake
    lines = [
        ev("python3", [ev(rs.PROGRAM, start=100, dur=800),
                       ev("PjitFunction(body)", start=110, dur=780),
                       ev("PjitFunction(body)", start=2000, dur=10)]),
        ev("python3", [ev(rs.READY, start=850, dur=400),
                       ev("np.asarray(jax.Array)", start=900, dur=300)]),
        ev("", [ev(rs.EXECUTE[1], start=200, dur=600),
                ev(rs.EXECUTE[1], start=860, dur=20),     # in two threads'
                ev(rs.EXECUTE[1], start=3000, dur=600),   # in none
                ev("MemoryDeallocation", start=300, dur=5)]),
        ev("py_xla_execute/937", [ev(rs.WORKER[1], start=300, dur=400)]),
        ev("pjrt-tpu-tasks/644", [ev(rs.WORKER[1], start=300, dur=400)]),
    ]
    trace = _Fake("", [_Fake("/host:CPU", lines)])
    trace.planes = trace.lines
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: trace))
    kept = [e[:4] for e in rs.load("x.pb")["host"]]
    assert kept == [
        [rs.PROGRAM, 100.0, 800.0, "python3#0"],
        [rs.READY, 850.0, 400.0, "python3#1"],
        ["PjitFunction(body)", 110.0, 780.0, "python3#0"],
        ["np.asarray(jax.Array)", 900.0, 300.0, "python3#1"],
        [rs.EXECUTE[1], 200.0, 600.0, "python3#0"],
        [rs.WORKER[1], 300.0, 400.0, "py_xla_execute/937#3"],
    ]


def test_a_recorded_trace_keeps_the_runtimes_events_of_the_holders_thread(
        tmp_path, monkeypatch):
    """A trace recorded here, on the CPU, under the cell's directory: a
    jitted call inside ``accl.gang::dispatch`` leaves jaxlib's
    ``PjitFunction(`` and the CPU client's execute event in ``load``'s
    host events, the same call outside any holder span leaves none, and
    the slice is read once a process."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(manifest, "CHECKOUT", str(tmp_path))
    trace_dir = tmp_path / ".perfbench_trace" / "cell" / "small"
    double, x = jax.jit(lambda x: x * 2), jnp.ones(8)
    double(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench::small::allreduce"):
            with jax.profiler.TraceAnnotation(rs.DISPATCH):
                double(x).block_until_ready()
            double(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    ctx = {"cell": {"name": "cell"}, "slices": {"small": {}}}
    loads = []
    real = rs.load
    monkeypatch.setattr(rs, "load",
                        lambda path: loads.append(path) or real(path))
    (call,) = rs.calls_of(ctx)
    assert rs.calls_of(ctx) == [call] and len(loads) == 1
    assert rs.windows_of(ctx) == []
    kept = [e for e in call["host"] if rs.named(e[0], rs.RUNTIME)]
    dispatch = stage_spans.one(call, rs.DISPATCH)
    assert kept and all(
        dispatch[1] <= e[1] and rs.end(e) <= rs.end(dispatch) for e in kept)
    assert any(e[0].startswith("PjitFunction(") for e in kept)
    assert rs.call_execute(call) > 0
    # what stage_spans.load keeps is all there, in the same form
    plain = stage_spans.load(loads[0])["host"]
    assert [e for e in real(loads[0])["host"]
            if e[0].startswith(stage_spans.HOST_PREFIXES)] == plain
