"""window_spans.py and the ten readers on a fixture with known answers.

``data/window_calls.json`` is written by hand (ns): a world of four rank
threads R0-R3, the drainer D and two device planes.  A rank queues two
calls of 100 (a window of the sweep queues eight) and flushes; a flush
that finds nothing launched returns at once.

* window A, union 1000..20000.  Flushes start 400, 420, 440, 500 after
  their ``bench::window``; submits start 1410, 1530, 1650, 1810, so the
  first arrives 410 in and the last 400 later.  R3 runs the program:
  ``accl.ring::batch`` 2000..12000 (190 after the last arrival) holding
  plan 1000, deps 100, encode 750, assemble 950, ``accl::cmdring[8]``
  5050..9050, adopt 1900, park 450 (..11500): 9150 of 10000, so 850 in
  no sub-span; R3's drain 6300.  Drainer: ready ..16000, complete
  16020..18000.  Device ops 6000..14000 (7500 and 7400 busy a plane).
  Park to ready 4500, ready lag 2000, launch lag 950, wake 2000 (the
  ranks' own 1000, 1500, 2000, 1800).  Tiled 410 + 400 + 190 + 10000 +
  4500 + 1980 + 2000 = 19480 of 19000 (park ends 500 before the ring's
  span does, and complete starts 20 after ready ends).
* window B, union 30000..52000, run by R1.  Queue 300, 800, 300, 400;
  first arrival 310, spread 600, rendezvous 90; ring 31000..42000: plan
  1200, deps 100, encode 850, assemble 1150, program 34550..39550,
  adopt 1600, park 550 (..41800): 10450 of 11000; R1's drain 7400.
  Ready ..44000, complete 44030..49000.  Device ops 36000..45000 (8500
  and 7800 busy): the ready lag is 1000 NEGATIVE, the launch lag 1450.
  Park to ready 2200, wake 3000 (1000, 2000, 3000, 2500).  Tiled 22170
  of 22000.
* window C has two ``accl::cmdring`` spans (a batch deeper than the
  ring): left out, not guessed at.
* window D is cut by the slice's edge (three bench spans): left out.

Medians: over 8 rank-thread windows the middle two; over 2 windows the
mean.
"""

import importlib
import json
import os

import pytest

from perfbench import manifest, window_spans as ws

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = {"batch_queue_us": "facade", "batch_rendezvous_us": "gang engine",
       "ring_plan_us": "command ring", "ring_encode_us": "command ring",
       "ring_assemble_us": "command ring", "ring_dispatch_us": "command ring",
       "ring_adopt_us": "command ring", "window_ready_lag_us": "device",
       "window_complete_us": "gang engine", "window_wake_us": "facade"}
#: the reader's name of each metric in ``report``
KEYS = {"batch_queue_us": "queue", "batch_rendezvous_us": "rendezvous",
        "ring_plan_us": ws.PLAN, "ring_encode_us": "deps_encode",
        "ring_assemble_us": ws.ASSEMBLE, "ring_dispatch_us": ws.CMDRING,
        "ring_adopt_us": "adopt_park", "window_ready_lag_us": "ready_lag",
        "window_complete_us": ws.COMPLETE, "window_wake_us": "wake"}


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(DATA, "window_calls.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx(fixture, monkeypatch):
    windows = ws.group(fixture)
    monkeypatch.setattr(ws, "windows_of", lambda ctx: windows)
    return {"cell": {"name": "coll_w4_sweep"}, "slices": {"window": {}}}


def test_grouping_keeps_the_whole_windows_it_understands(fixture):
    a, b = ws.group(fixture)
    assert (a["start"], a["end"]) == (1000, 20000)
    assert (b["start"], b["end"]) == (30000, 52000)
    assert sorted(e[3] for e in a["bench"]) == ["R0", "R1", "R2", "R3"]
    # everything that starts inside the union: a rank's two calls, its
    # flush, submit and drain; the ring's span and seven stages; ready
    # and complete
    assert len(a["host"]) == 4 * 5 + 8 + 2 and len(b["host"]) == 30
    assert (a["device_start"], a["device_end"]) == (6000, 14000)
    assert (b["device_start"], b["device_end"]) == (36000, 45000)
    assert (a["busy"], b["busy"]) == (7450, 8150)


def test_the_executing_thread_is_whichever_rank_arrived_last(fixture):
    windows = ws.group(fixture)
    assert [ws.one(w, ws.RING)[3] for w in windows] == fixture["expect"][
        "runners"]
    a, b = windows
    assert [ws.queue(rw) for rw in ws.rank_windows(a)] == [400, 420, 440, 500]
    assert [ws.queue(rw) for rw in ws.rank_windows(b)] == [300, 800, 300, 400]
    # only the drain on the thread that ran the window finds it in flight
    assert ws.ring_thread_drain(a) == 6300 and ws.ring_thread_drain(b) == 7400
    assert sorted(e[2] for e in ws.spans(a, ws.DRAIN)) == [20, 20, 20, 6300]


def test_a_window_cut_by_the_edge_or_not_understood_is_left_out(fixture):
    cut = {"host": [e for e in fixture["host"] if e[1] >= 80000],
           "devices": {}}
    assert ws.group(cut) == []
    twice = {"host": [e for e in fixture["host"] if 60000 <= e[1] < 80000],
             "devices": fixture["devices"]}
    assert len({e[3] for e in twice["host"]
                if e[0] == ws.BENCH}) == 4  # whole, and yet
    assert ws.group(twice) == []
    # with one program call it would have been read
    once = dict(twice, host=[e for e in twice["host"]
                             if e[0] != "accl::cmdring[4]"])
    (c,) = ws.group(once)
    assert ws.one(c, ws.CMDRING)[0] == "accl::cmdring[8]"
    assert ws.deps_encode(c) is None and ws.ring_rest(c) is None
    assert ws.tiled(c) is None and ws.wake(c) == 70300 - 68000


def test_stages_tile_the_window(fixture):
    for w, union, tiled in zip(ws.group(fixture), (19000, 22000),
                               (19480, 22170)):
        assert w["end"] - w["start"] == union
        assert ws.tiled(w) == tiled
        park, ring = ws.one(w, ws.PARK), ws.one(w, ws.RING)
        ready, done = ws.one(w, ws.READY), ws.one(w, ws.COMPLETE)
        assert tiled - union == (ws.end(ring) - ws.end(park)) - (
            done[1] - ws.end(ready))


def test_report_gives_the_table_worked_out_by_hand(fixture):
    table = ws.report(ws.group(fixture))
    expect = fixture["expect"]
    assert table["windows"] == 2
    for key in ("union", "tiled", "stages_sum", "first_arrival",
                "arrival_spread", "queued_calls", "ring_rest", "to_ready",
                "rank_wake", "ring_thread_drain", "launch_lag",
                "device_busy"):
        assert table[key] == pytest.approx(expect[key + "_us"]), key
    assert table["ring_covered_share"] == pytest.approx(
        expect["ring_covered_share"])
    # window B's host learnt before the device's last op ended: counted
    assert table["ready_lag_negative_share"] == 0.5
    assert table["launch_lag_negative_share"] == 0.0
    for name, key in KEYS.items():  # the table and the readers agree
        assert table[key] == pytest.approx(expect[name]), name


def test_every_new_metric_is_in_the_manifest_for_the_sweep_only():
    doc = manifest.load()
    entries = {m["name"]: m for m in doc["per_layer"]}
    for name, layer in NEW.items():
        assert entries[name] == {
            "name": name, "unit": "us", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "coll_batched_p50", "workloads": ["coll_w4_sweep"],
        }
    # appended: the ten are the list's last, and the cell reports them
    assert [m["name"] for m in doc["per_layer"][-10:]] == list(NEW)
    cell = manifest.cell(doc, "coll_w4_sweep")
    assert set(NEW) <= {m["name"] for m in cell["per_layer"]}


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_answer_worked_out_by_hand(name, ctx, fixture):
    reader = importlib.import_module("perfbench.layer_metrics." + name)
    assert reader.read(ctx) == pytest.approx(fixture["expect"][name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_spans(
        name, fixture, monkeypatch):
    """The parent commit: ``bench::window``, the queued calls' facade
    spans, ``accl::cmdring[n]`` and the drainer's two.  No window is
    understood; nothing to read is None, never an exception."""
    old = {"host": [e for e in fixture["host"]
                    if not e[0].startswith(("accl.batch::", "accl.ring::"))],
           "devices": fixture["devices"]}
    assert any(e[0].startswith("accl::cmdring") for e in old["host"])
    windows = ws.group(old)
    assert windows == []
    monkeypatch.setattr(ws, "windows_of", lambda ctx: windows)
    reader = importlib.import_module("perfbench.layer_metrics." + name)
    assert reader.read({"cell": {"name": "x"}, "slices": {}}) is None
    assert ws.report(windows)["union"] is None


def test_no_slice_or_no_trace_file_is_no_window(tmp_path, monkeypatch):
    assert ws.windows_of({"cell": {"name": "x"}, "slices": {}}) == []
    monkeypatch.setattr(manifest, "CHECKOUT", str(tmp_path))
    assert ws.windows_of({"cell": {"name": "x"},
                          "slices": {"window": {}}}) == []


def test_a_recorded_trace_is_read_once_a_process(tmp_path, monkeypatch):
    """A trace recorded here, on the CPU, under the cell's directory:
    ``windows_of`` finds it, reads it once, and a parent-like program
    (bench spans only) gives no window."""
    import jax

    monkeypatch.setattr(manifest, "CHECKOUT", str(tmp_path))
    trace_dir = tmp_path / ".perfbench_trace" / "cell" / "window"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench::window"):
            with jax.profiler.TraceAnnotation("accl::cmdring[8]"):
                pass
    finally:
        jax.profiler.stop_trace()
    ctx = {"cell": {"name": "cell"}, "slices": {"window": {}}}
    loads = []
    real = ws.stage_spans.load
    monkeypatch.setattr(ws.stage_spans, "load",
                        lambda path: loads.append(path) or real(path))
    assert ws.windows_of(ctx) == [] and ws.windows_of(ctx) == []
    assert len(loads) == 1
    (event,) = [e for e in real(loads[0])["host"] if e[0] == ws.BENCH]
    assert event[2] > 0
