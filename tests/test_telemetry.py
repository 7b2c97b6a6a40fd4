"""Telemetry plane: flight recorder, metrics registry, trace export.

The observability contract (telemetry-plane PR):

* the flight recorder is a bounded ring appended at Request.complete on
  every tier, its tail riding into ACCLError.details under faults;
* ``telemetry_snapshot()`` returns ONE merged dict of identical shape
  on the emulator, gang (and native, when built) tiers;
* exporters produce valid Prometheus text / JSON / Chrome traces, and
  the merge CLI folds per-rank files into one timeline with
  monotonically consistent ``ts``;
* warm-path recording adds ZERO device interactions (counter-asserted)
  and the ``ACCL_TELEMETRY=0`` kill switch really kills it;
* ``ACCL_DEBUG=TRACE`` wire events buffer into the telemetry ring, not
  synchronous stderr (stderr stays opt-in).
"""

import json
import os
import re

import numpy as np
import pytest

from helpers import record_rank_traces, run_parallel

from accl_tpu import ACCLError, ErrorCode, emulated_group
from accl_tpu import telemetry as T
from accl_tpu.core import xla_group

#: the one-merged-dict contract
SNAPSHOT_KEYS = (
    "flight_recorder", "metrics", "plan_cache", "health",
    "device_interactions", "engine", "faults", "wire_trace", "rank",
    "tier", "schema_version",
)


def _deinit(group):
    for a in group:
        a.deinit()


# ---------------------------------------------------------------------------
# flight recorder mechanics
# ---------------------------------------------------------------------------


def _rec(i: int) -> T.CallRecord:
    return T.CallRecord(
        "allreduce", 0, 1, "FLOAT32", i, 4 * i, 3, None, True, True,
        1000 * (i + 1), 0, "OK", 10_000 + i,
    )


def test_ring_bounds_and_rollover():
    ring = T.FlightRecorder(capacity=8)
    assert len(ring) == 0 and ring.tail() == []
    for i in range(20):
        ring.append(_rec(i))
    assert len(ring) == 8
    assert ring.total == 20
    tail = ring.tail()
    assert [r.count for r in tail] == list(range(12, 20))  # oldest first
    assert [r.count for r in ring.tail(3)] == [17, 18, 19]
    assert ring.tail_dicts(1)[0]["count"] == 19


def test_metrics_registry_histogram_shape():
    m = T.MetricsRegistry()
    for us in (10, 100, 1000, 1500):
        m.observe("allreduce", 6, us * 1000)
    m.observe("bcast", 2, 50_000)
    m.inc("accl_calls_total", ("allreduce",), 4)
    snap = m.snapshot()
    h = snap["histograms"]["allreduce/b6"]
    assert h["count"] == 4 and h["sum_ns"] == (10 + 100 + 1000 + 1500) * 1000
    # log2(us) buckets: 10us->3, 100us->6, 1000us->9, 1500us->10
    assert h["log2_us"] == {"3": 1, "6": 1, "9": 1, "10": 1}
    assert snap["counters"]["accl_calls_total|allreduce"] == 4
    assert "bcast/b2" in snap["histograms"]


def test_record_call_matches_separate_updates():
    """The single-lock completion fast lane must account identically to
    the generic inc/observe surface."""
    a, b = T.MetricsRegistry(), T.MetricsRegistry()
    a.record_call("reduce", 4, 250_000, 11, "SEND_TIMEOUT", False, 3)
    b.inc("accl_calls_total", ("reduce",))
    b.inc("accl_call_errors_total", ("reduce", "SEND_TIMEOUT"))
    b.inc("accl_plan_misses_total", ("reduce",))
    b.inc("accl_call_attempts_total", ("reduce",), 3)
    b.observe("reduce", 4, 250_000)
    assert a.snapshot() == b.snapshot()


# ---------------------------------------------------------------------------
# the merged snapshot, across tiers
# ---------------------------------------------------------------------------


def _exercise(group, n=64):
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(group)
    ]
    recv = [a.create_buffer(n, np.float32) for a in group]
    run_parallel(group, lambda a, r: a.allreduce(send[r], recv[r], n))
    return send, recv


def _assert_snapshot_shape(snap, tier):
    for key in SNAPSHOT_KEYS:
        assert key in snap, f"{tier}: snapshot missing {key}"
    assert snap["tier"] == tier
    assert snap["telemetry_enabled"] is True
    records = snap["flight_recorder"]
    assert records, f"{tier}: no flight records"
    last = records[-1]
    for field in ("op", "comm", "epoch", "dtype", "count", "nbytes",
                  "bucket", "duration_ns", "retcode", "retcode_name"):
        assert field in last, f"{tier}: record missing {field}"
    assert last["op"] == "allreduce"
    assert last["retcode_name"] == "OK"
    assert last["duration_ns"] > 0
    m = snap["metrics"]
    assert m["counters"].get("accl_calls_total|allreduce", 0) >= 1
    assert any(k.startswith("allreduce/") for k in m["histograms"])


def test_snapshot_emulator_tier():
    g = emulated_group(2)
    try:
        _exercise(g)
        snap = g[0].telemetry_snapshot()
        _assert_snapshot_shape(snap, "EmuEngine")
        # the emulator report carries the recovery/rx counters
        eng = snap["engine"]
        assert eng["rx_pool"]["total"] > 0
        assert eng["retransmits_total"] == 0
        assert eng["dedup_discards_total"] == 0
        # a warm emulator call is a plan hit, stamped per record
        assert snap["flight_recorder"][-1]["plan_hit"] in (True, False)
    finally:
        _deinit(g)


def test_snapshot_xla_gang_tier(gang4):
    _exercise(gang4)
    snap = gang4[0].telemetry_snapshot()
    _assert_snapshot_shape(snap, "XLAEngine")
    assert isinstance(snap["device_interactions"], int)
    assert snap["engine"]["gang_pending_slots"] == 0


def test_snapshot_native_tier():
    from accl_tpu.backends.native import engine_library_available, native_group

    if not engine_library_available():
        pytest.skip("native engine library unavailable")
    g = native_group(2)
    try:
        _exercise(g)
        _assert_snapshot_shape(g[0].telemetry_snapshot(), "NativeEngine")
    finally:
        _deinit(g)


def test_kill_switch_disables_recording(monkeypatch):
    monkeypatch.setenv("ACCL_TELEMETRY", "0")
    g = emulated_group(2)
    try:
        _exercise(g)
        snap = g[0].telemetry_snapshot()
        assert snap["telemetry_enabled"] is False
        assert snap["flight_recorder"] == []
        assert snap["metrics"] == {}
        assert g[0].capabilities()["telemetry"] is False
        assert g[0].telemetry_trace_events() == []
        # the other sections still merge (they don't need the recorder)
        assert "plan_cache" in snap and "health" in snap
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_prometheus_and_json_export():
    g = emulated_group(2)
    try:
        _exercise(g)
        text = g[0].telemetry_prometheus()
        assert "# TYPE accl_calls_total counter" in text
        assert 'accl_calls_total{op="allreduce"' in text
        assert "# TYPE accl_call_duration_us histogram" in text
        assert 'le="+Inf"' in text
        # cumulative buckets: every _bucket count <= the +Inf count
        assert "accl_call_duration_us_count" in text
        assert "# TYPE accl_engine_rx_pool_total gauge" in text
        doc = json.loads(g[0].telemetry_json())  # valid JSON round-trip
        assert doc["tier"] == "EmuEngine"
    finally:
        _deinit(g)


def test_chrome_trace_valid_and_monotonic(tmp_path):
    g = emulated_group(2)
    try:
        _exercise(g)
        _exercise(g)
        path = tmp_path / "rank0.json"
        g[0].export_chrome_trace(str(path))
        doc = json.loads(path.read_text())
        evs = doc["traceEvents"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert spans, "no spans exported"
        ts = [e["ts"] for e in evs if "ts" in e]
        assert ts == sorted(ts), "ts must be monotonically consistent"
        for e in spans:
            assert e["dur"] >= 0
            assert e["pid"] == 0
            assert e["name"].startswith("accl::")
            # span duration consistent with the recorded engine duration
            assert abs(e["dur"] * 1e3 - e["args"]["duration_ns"]) < 1e3
    finally:
        _deinit(g)


def test_merge_cli_on_committed_artifacts(tmp_path, capsys):
    """A multi-rank run's per-rank files merge into ONE
    Perfetto-loadable trace via the CLI (acceptance criterion)."""
    inputs = record_rank_traces(tmp_path)
    out = tmp_path / "merged.json"
    assert T.main(["merge", "--out", str(out)] + inputs) == 0
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    # rank rows 0..3 plus the process-wide rows (cmdring spans / wire
    # instants export under the OS pid)
    assert {e["pid"] for e in evs} >= {0, 1, 2, 3}
    ts = [e["ts"] for e in evs if "ts" in e]
    assert ts == sorted(ts)
    # the merge keeps every rank's own events
    for r, path in enumerate(inputs):
        mine = [e for e in json.load(open(path))["traceEvents"]
                if e.get("pid") == r and e.get("ph") == "X"]
        assert mine and len(
            [e for e in evs if e.get("pid") == r and e.get("ph") == "X"]
        ) == len(mine)


def test_merge_cli_refuses_malformed(tmp_path):
    bad = tmp_path / "empty.json"
    bad.write_text(json.dumps({"traceEvents": []}))
    with pytest.raises(SystemExit):
        T.main(["merge", "--out", str(tmp_path / "out.json"), str(bad)])


# ---------------------------------------------------------------------------
# failure paths: the flight recorder rides ACCLError.details
# ---------------------------------------------------------------------------


def test_induced_fault_surfaces_flight_recorder(fault_plan):
    """An induced drop (FaultPlan machinery) fails with the last-N
    flight-recorder records attached to ACCLError.details — including
    the failing call itself, retcode stamped."""
    g = emulated_group(2)
    a, b = g
    try:
        # a little healthy history first, so the tail has context
        _exercise(g, n=16)
        a.engine.fabric.install_fault_plan(fault_plan(
            dict(action="drop", msg_type="EAGER", src=1, dst=0),
        ))
        a.set_timeout(0.3)
        data = np.arange(16, dtype=np.float32)
        sb = b.create_buffer_from(data)
        b.send(sb, 16, dst=0, tag=9)
        rb = a.create_buffer(16, np.float32)
        with pytest.raises(ACCLError) as exc:
            a.recv(rb, 16, src=1, tag=9)
        assert exc.value.code == ErrorCode.RECEIVE_TIMEOUT
        records = exc.value.details["flight_recorder"]
        assert isinstance(records, list) and records
        # the failed call is the LAST record, with its retcode
        assert records[-1]["op"] == "recv"
        assert records[-1]["retcode_name"] == "RECEIVE_TIMEOUT"
        # healthy history precedes it
        assert any(r["retcode_name"] == "OK" for r in records)
        # the message summarizes instead of dumping the records
        assert "flight_recorder=<" in str(exc.value)
        # the armed plan's fire counters surface in the snapshot
        snap = a.telemetry_snapshot()
        assert snap["faults"]["fired_total"] >= 1
        assert snap["faults"]["by_action"].get("drop", 0) >= 1
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# overhead: recording must be free of device interactions
# ---------------------------------------------------------------------------


def test_warm_path_recording_adds_zero_device_interactions(gang4):
    """The always-on budget, counter-asserted: a warm gang collective
    with telemetry armed is STILL exactly one device interaction — the
    recorder is host-side ring writes only."""
    n = 64
    assert all(a._telemetry is not None for a in gang4)
    send, recv = _exercise(gang4, n)  # cold: plan + program

    def work(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(gang4, work)  # first warm: prepares the plan handle
    ic0 = gang4[0].capabilities()["device_interactions"]
    total0 = gang4[0]._telemetry.recorder.total
    run_parallel(gang4, work)
    assert gang4[0].capabilities()["device_interactions"] - ic0 == 1
    assert gang4[0]._telemetry.recorder.total == total0 + 1
    rec = gang4[0]._telemetry.recorder.tail(1)[0]
    assert rec.plan_hit is True and rec.retcode == 0


# ---------------------------------------------------------------------------
# wire-event routing (ACCL_DEBUG=TRACE through the ring)
# ---------------------------------------------------------------------------


def test_trace_events_buffer_into_ring_not_stderr(capsys, monkeypatch):
    from accl_tpu.utils.logging import Log, LogLevel

    monkeypatch.delenv("ACCL_TRACE_STDERR", raising=False)
    T.wire_reset()
    log = Log("wiretest", level=LogLevel.TRACE)
    log.trace("send EAGER comm=0 src=0 dst=1")
    assert capsys.readouterr().err == ""  # nothing synchronous
    snap = T.wire_snapshot()
    assert snap["seen"] == 1
    assert snap["events"][-1]["src"] == "wiretest"
    assert "EAGER" in snap["events"][-1]["event"]
    # non-TRACE levels keep stderr
    log.error("boom")
    assert "boom" in capsys.readouterr().err
    T.wire_reset()


def test_trace_stderr_opt_in(capsys, monkeypatch):
    from accl_tpu.utils.logging import Log, LogLevel

    monkeypatch.setenv("ACCL_TRACE_STDERR", "1")
    T.wire_reset()
    log = Log("wiretest", level=LogLevel.TRACE)
    log.trace("synchronous again")
    assert "synchronous again" in capsys.readouterr().err
    assert T.wire_snapshot()["seen"] == 0
    T.wire_reset()


def test_wire_sampling(monkeypatch):
    monkeypatch.setenv("ACCL_TELEMETRY_SAMPLE", "4")
    T.wire_reset()
    for i in range(16):
        T.wire_event("s", f"ev{i}")
    snap = T.wire_snapshot()
    assert snap["seen"] == 16
    assert snap["recorded"] == 4  # 1-in-4
    T.wire_reset()


def test_fabric_send_traces_wire_events(fault_plan, monkeypatch):
    """ACCL_DEBUG=TRACE on the fabric: per-message events land in the
    ring (buffered), visible in the snapshot's wire_trace section."""
    from accl_tpu.backends.emulator import fabric as fabric_mod

    monkeypatch.delenv("ACCL_TRACE_STDERR", raising=False)
    monkeypatch.setattr(
        fabric_mod._WIRE_LOG, "level", fabric_mod.LogLevel.TRACE
    )
    T.wire_reset()
    g = emulated_group(2)
    try:
        _exercise(g, n=16)
        snap = g[0].telemetry_snapshot()["wire_trace"]
        assert snap["seen"] > 0
        assert any("EAGER" in e["event"] for e in snap["events"])
        # wire events render as instants in the exported trace
        evs = g[0].telemetry_trace_events()
        assert any(e.get("cat") == "wire" for e in evs)
    finally:
        _deinit(g)
        T.wire_reset()


# ---------------------------------------------------------------------------
# structured dumps (one source, two views)
# ---------------------------------------------------------------------------


def test_dump_communicator_structured():
    g = emulated_group(2)
    try:
        doc = g[0].dump_communicator(as_dict=True)
        assert doc["comm"]["size"] == 2
        assert doc["comm"]["ranks"][1]["address"] == "inproc:1"
        assert 1 in doc["health"]
        text = g[0].dump_communicator()
        # the string renders from the dict: same facts, same tokens
        assert f"communicator {doc['comm']['id']}:" in text
        assert "health rank 1: ok" in text
        assert "addr=inproc:1" in text
    finally:
        _deinit(g)


def test_dump_rx_buffers_structured():
    g = emulated_group(2)
    try:
        doc = g[0].dump_rx_buffers(as_dict=True)
        assert doc["engine"] == "EmuEngine"
        assert doc["report"]["rx_pool"]["total"] > 0
        assert g[0].dump_rx_buffers() == "\n".join(doc["lines"])
    finally:
        _deinit(g)


def test_sync_completed_failure_carries_flight_recorder():
    """A call that fails SYNCHRONOUSLY inside engine.start (the gang's
    known-dead-peer intake fail-fast) must still raise with the
    flight-recorder tail attached — attach() arms check() even on the
    already-completed branch."""
    g = xla_group(2)
    try:
        _exercise(g, n=8)  # healthy history
        # two watchdog strikes mark global rank 1 dead -> intake fail-fast
        g[0].engine.gang.health[1] = {
            "state": "dead", "timeouts": 2, "failures": 0,
            "last_event": "gang_timeout",
        }
        s = g[0].create_buffer_from(np.ones(8, np.float32))
        d = g[0].create_buffer(8, np.float32)
        with pytest.raises(ACCLError) as exc:
            g[0].allreduce(s, d, 8)
        records = exc.value.details["flight_recorder"]
        assert records and records[-1]["op"] == "allreduce"
        assert records[-1]["retcode_name"] != "OK"
    finally:
        _deinit(g)


def test_deferred_adoption_failure_amends_record():
    """A deferred-result adoption failure downgrades the retcode AFTER
    completion; the flight recorder gets an amended record with the
    downgraded code (error counted once, call not double-counted)."""
    from accl_tpu.request import Request

    tel = T.Telemetry(0, "XLAEngine")
    meta = {"op": "allreduce", "comm": 0, "epoch": 1, "dtype": "FLOAT32",
            "count": 8, "nbytes": 32, "bucket": 3, "algorithm": None,
            "plan_hit": True, "eager": True}
    req = Request("ALLREDUCE")
    tel.attach(req, meta)

    def bad_resolver():
        raise RuntimeError("adoption failed")

    req.defer_result(bad_resolver)
    req.complete(ErrorCode.OK, 1000)
    assert req.wait(1)
    with pytest.raises(ACCLError):
        req.check()
    recs = tel.recorder.tail()
    assert len(recs) == 2
    assert recs[0].retcode_name == "OK"  # the completion-time record
    assert recs[1].retcode_name == "INVALID_OPERATION"  # the amendment
    counters = tel.metrics.snapshot()["counters"]
    assert counters["accl_calls_total|allreduce"] == 1
    assert counters[
        "accl_call_errors_total|allreduce|INVALID_OPERATION"
    ] == 1


def test_merge_dedups_shared_process_wire_ring():
    """In-process multi-rank exports each embed the SAME process-wide
    wire ring; the merged timeline must carry one copy (under the OS
    pid, never a rank pid)."""
    T.wire_reset()
    T.wire_event("wire", "send EAGER comm=0 src=0 dst=1")
    T.wire_event("wire", "send EAGER comm=0 src=1 dst=0")
    t0 = T.Telemetry(0, "EmuEngine")
    t1 = T.Telemetry(1, "EmuEngine")
    t0.record({"op": "allreduce", "comm": 0, "epoch": 1, "dtype": "F",
               "count": 1, "nbytes": 4, "bucket": 0, "algorithm": None,
               "plan_hit": None, "eager": None}, 1000, 0)
    merged = T.merge_traces([
        T.chrome_trace(t0.chrome_events()),
        T.chrome_trace(t1.chrome_events()),
    ])
    wire = [e for e in merged["traceEvents"] if e.get("cat") == "wire"]
    assert len(wire) == 2, "each wire event exactly once after merge"
    assert all(e["pid"] == os.getpid() for e in wire), (
        "wire events belong to the process row, not a rank"
    )
    T.wire_reset()


def test_deadlock_error_carries_flight_recorder(gang4):
    """The facade's watchdog path (DEADLOCK_SUSPECTED) ships the tail
    too."""
    err = gang4[0]._deadlock_error("test-context")
    assert isinstance(err.details["flight_recorder"], list)
    assert err.code == ErrorCode.DEADLOCK_SUSPECTED


# ---------------------------------------------------------------------------
# schema_version + exporter round-trip (monitor-plane PR satellites)
# ---------------------------------------------------------------------------

#: one Prometheus exposition line: name{labels} value
_PROM_LINE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{(?P<labels>[^{}]*)\})? (?P<value>[^ ]+)$'
)
#: one label pair inside {...}; values may contain escaped \\ \" \n
_PROM_LABEL_RE = re.compile(
    r'(?P<key>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<val>(?:[^"\\]|\\.)*)"'
)


def _prom_parse(text: str):
    """Re-parse Prometheus exposition text into
    [(name, {label: unescaped value}, raw value)] — the round-trip
    proof that every emitted line survives a real scrape parser."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE_RE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        labels = {}
        raw = m.group("labels")
        if raw:
            consumed = ",".join(
                lm.group(0) for lm in _PROM_LABEL_RE.finditer(raw)
            )
            assert consumed == raw, f"malformed label block: {raw!r}"
            for lm in _PROM_LABEL_RE.finditer(raw):
                val = (
                    lm.group("val")
                    .replace("\\n", "\n")
                    .replace('\\"', '"')
                    .replace("\\\\", "\\")
                )
                labels[lm.group("key")] = val
        out.append((m.group("name"), labels, m.group("value")))
    return out


def test_snapshot_carries_schema_version():
    g = emulated_group(2)
    try:
        snap = g[0].telemetry_snapshot()
        assert snap["schema_version"] == T.SCHEMA_VERSION == 6
        # the JSON exporter round-trips it
        assert json.loads(g[0].telemetry_json())["schema_version"] == 6
    finally:
        _deinit(g)


def test_prometheus_round_trip_reparses():
    """Every line of a live scrape re-parses: names, label blocks,
    values — and the emitted metric set survives with its counts."""
    g = emulated_group(2)
    try:
        _exercise(g, n=16)
        parsed = _prom_parse(g[0].telemetry_prometheus())
        names = {p[0] for p in parsed}
        assert "accl_calls_total" in names
        assert "accl_call_duration_us_bucket" in names
        calls = [
            p for p in parsed
            if p[0] == "accl_calls_total" and p[1].get("op") == "allreduce"
        ]
        assert calls and int(calls[0][2]) >= 1
        # histogram cumulative buckets end with +Inf == _count
        infs = [
            p for p in parsed
            if p[0] == "accl_call_duration_us_bucket"
            and p[1].get("le") == "+Inf"
        ]
        counts = {
            (p[1].get("op"), p[1].get("size_bucket")): p[2]
            for p in parsed if p[0] == "accl_call_duration_us_count"
        }
        for p in infs:
            key = (p[1].get("op"), p[1].get("size_bucket"))
            assert counts[key] == p[2]
    finally:
        _deinit(g)


def test_prometheus_label_escaping_round_trip():
    """Label values carrying quotes, backslashes and newlines (an op or
    comm id gone weird) must escape on emission and unescape to the
    original on re-parse — one bad value must not corrupt the scrape."""
    weird_ops = ['all"reduce', "bc\\ast", "gat\nher", "plain"]
    snap = {
        "rank": 0,
        "tier": 'Emu"Engine\\odd',
        "metrics": {
            "counters": {
                f"accl_calls_total|{op}": 3 for op in weird_ops
            },
            "histograms": {},
        },
    }
    text = T.to_prometheus(snap)
    parsed = _prom_parse(text)
    got_ops = {
        p[1]["op"] for p in parsed if p[0] == "accl_calls_total"
    }
    assert got_ops == set(weird_ops)
    tiers = {p[1].get("tier") for p in parsed if "tier" in p[1]}
    assert tiers == {'Emu"Engine\\odd'}


def test_prometheus_type_lines_unique_across_label_sets():
    """One '# TYPE' line per metric name however many label sets carry
    it — a duplicate TYPE line is invalid exposition and fails the whole
    scrape (the per-(comm, peer) straggler gauges regressed this)."""
    snap = {
        "rank": 0,
        "tier": "EmuEngine",
        "metrics": {"counters": {}, "histograms": {}},
        "stragglers": {
            "ewma_wait_lag_us": {"0": {"0": 1.0, "1": 2.0, "2": 3.0}},
            "ewma_latency_us": {"0": {"0": 4.0, "1": 5.0, "2": 6.0}},
            "standing": {},
            "verdicts": [],
            "windows_judged": 3,
        },
    }
    text = T.to_prometheus(snap)
    type_lines = [l for l in text.splitlines() if l.startswith("# TYPE")]
    assert len(type_lines) == len(set(type_lines)), type_lines
    parsed = _prom_parse(text)
    lags = [p for p in parsed if p[0] == "accl_straggler_ewma_wait_lag_us"]
    assert len(lags) == 3  # all three peers' gauges survived the dedup
