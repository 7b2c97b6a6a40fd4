"""Monitor-plane tests: the live scrape service, streaming trace
export, cross-rank straggler diagnosis and the anomaly watchdog.

The straggler acceptance pair: a seeded one-rank ``delay`` FaultRule on
the emulator tier must produce a ``slow_rank`` verdict naming that rank
within two exchange windows — deterministically (same plan, same
convicted rank) — while an unfaulted run over the same traffic produces
ZERO verdicts (the false-positive guard)."""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from helpers import run_parallel

from accl_tpu.core import emulated_group
from accl_tpu.constants import ACCLError, ErrorCode
from accl_tpu.faults import FaultPlan, FaultRule
from accl_tpu import monitor as monitor_mod
from accl_tpu.monitor import (
    AnomalyWatchdog,
    MonitorServer,
    SkewJudge,
    SkewTracker,
    TraceStreamWriter,
)


def _get(port: int, route: str, timeout: float = 5.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{route}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


def _drive(g, rounds: int, n: int = 64):
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g]
    for _ in range(rounds):
        run_parallel(g, lambda a, r: a.allreduce(send[r], recv[r], n))
    return recv


#: a Prometheus exposition line: name{labels} value (labels optional)
_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$'
)


# ---------------------------------------------------------------------------
# scrape service
# ---------------------------------------------------------------------------


def test_scrape_endpoints_smoke():
    """start → GET all three routes → well-formed payloads → stop joins
    the accl-monitor thread."""
    g = emulated_group(2)
    try:
        _drive(g, 3)
        a = g[0]
        port = a.start_monitor(0)
        assert port > 0
        # idempotent while serving
        assert a.start_monitor(0) == port

        status, body = _get(port, "/metrics")
        assert status == 200
        assert "accl_calls_total" in body
        for line in body.splitlines():
            if not line or line.startswith("#"):
                continue
            assert _PROM_LINE.match(line), f"malformed prom line: {line!r}"

        status, body = _get(port, "/snapshot")
        assert status == 200
        snap = json.loads(body)
        assert snap["schema_version"] == 6
        for key in ("flight_recorder", "metrics", "stragglers",
                    "anomalies", "monitor", "health"):
            assert key in snap
        assert snap["monitor"]["serving"] is True

        status, body = _get(port, "/trace")
        assert status == 200
        doc = json.loads(body)
        assert doc["traceEvents"]
        assert any(
            e.get("name") == "accl::allreduce" for e in doc["traceEvents"]
        )

        status, body = _get(port, "/")
        assert status == 200 and "/metrics" in body
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(port, "/nope")
        assert e.value.code == 404

        # the service counts its scrapes (bench evidence)
        srv = a.capabilities()["monitor"]["server"]
        assert srv["scrapes"]["/metrics"] >= 1

        assert a.stop_monitor() is True
        assert not any(
            t.name.startswith("accl-monitor-") and t.is_alive()
            for t in threading.enumerate()
        )
        # stopped: the port no longer answers
        with pytest.raises(Exception):
            _get(port, "/metrics", timeout=1.0)
    finally:
        for a in g:
            a.deinit()


def test_monitor_env_port_autostart(monkeypatch):
    monkeypatch.setenv("ACCL_MONITOR_PORT", "0")
    g = emulated_group(1)
    try:
        caps = g[0].capabilities()
        assert caps["monitor"]["serving"] is True
        port = caps["monitor"]["server"]["port"]
        status, _ = _get(port, "/metrics")
        assert status == 200
    finally:
        for a in g:
            a.deinit()
    # deinit stopped the service
    assert not any(
        t.name.startswith("accl-monitor-") and t.is_alive()
        for t in threading.enumerate()
    )


def test_start_monitor_requires_telemetry(monkeypatch):
    monkeypatch.setenv("ACCL_TELEMETRY", "0")
    g = emulated_group(1)
    try:
        with pytest.raises(ACCLError) as e:
            g[0].start_monitor(0)
        assert e.value.code == ErrorCode.INVALID_OPERATION
    finally:
        for a in g:
            a.deinit()


def test_monitor_server_render_failure_is_500():
    srv = MonitorServer(
        {"/boom": (lambda: 1 / 0, "text/plain")}, port=0
    ).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(srv.port, "/boom")
        assert e.value.code == 500
        assert srv.snapshot()["errors"] == 1
    finally:
        assert srv.stop() is True


# ---------------------------------------------------------------------------
# streaming trace export
# ---------------------------------------------------------------------------


def test_trace_stream_rollover_and_prune(tmp_path):
    """Files roll at max_events and the oldest beyond max_files are
    pruned; every file on disk is a complete, loadable trace doc."""
    batches = [[{"name": f"ev{i}", "ph": "X", "ts": i} for i in range(3)]]

    def pull():
        return batches.pop(0) if batches else []

    w = TraceStreamWriter(
        str(tmp_path), rank=0, pull_fn=pull,
        interval_s=3600.0, max_events=2, max_files=2,
    )
    try:
        w.flush()
        files = sorted(tmp_path.glob("accl_trace_rank0_*.json"))
        # 3 events at max_events=2: one full rolled file + the current
        assert len(files) == 2
        total = 0
        for f in files:
            doc = json.loads(f.read_text())
            assert "traceEvents" in doc
            total += len(doc["traceEvents"])
        assert total == 3
        # keep rolling: pruning holds the file count at max_files
        for k in range(4):
            batches.append(
                [{"name": f"b{k}", "ph": "X", "ts": 100 + k},
                 {"name": f"c{k}", "ph": "X", "ts": 200 + k}]
            )
            w.flush()
        files = sorted(tmp_path.glob("accl_trace_rank0_*.json"))
        assert len(files) <= 3  # max_files rolled + current
        snap = w.snapshot()
        assert snap["events_streamed"] == 11
    finally:
        assert w.stop() is True


def test_trace_stream_env_crash_leaves_valid_trace(tmp_path, monkeypatch):
    """ACCL_TRACE_STREAM arms the streamer at handle construction; the
    on-disk file is a loadable timeline WITHOUT any clean shutdown (the
    crash contract: every write is an atomic whole-document replace)."""
    monkeypatch.setenv("ACCL_TRACE_STREAM", str(tmp_path))
    monkeypatch.setenv("ACCL_TRACE_STREAM_INTERVAL_S", "0.05")
    g = emulated_group(2)
    try:
        _drive(g, 3)
        deadline = time.monotonic() + 10.0
        events = []
        while time.monotonic() < deadline:
            events = [
                e
                for f in tmp_path.glob("accl_trace_rank*.json")
                for e in json.loads(f.read_text())["traceEvents"]
            ]
            if any(e.get("name") == "accl::allreduce" for e in events):
                break
            time.sleep(0.05)
        # validated MID-RUN — no stop(), no deinit: what a crash leaves
        assert any(e.get("name") == "accl::allreduce" for e in events)
    finally:
        for a in g:
            a.deinit()
    # post-deinit the final flush drained the rest, still loadable
    for f in tmp_path.glob("accl_trace_rank*.json"):
        json.loads(f.read_text())


# ---------------------------------------------------------------------------
# cross-rank straggler diagnosis
# ---------------------------------------------------------------------------


def _delay_plan(rank: int, seed: int = 7,
                delay_s: float = 0.02) -> FaultPlan:
    return FaultPlan(
        rules=[FaultRule(action="delay", src=rank, delay_s=delay_s,
                         msg_type="EAGER")],
        seed=seed,
    )


def _seeded_run(plan, rounds: int = 8):
    g = emulated_group(2)
    try:
        if plan is not None:
            g[0].engine.fabric.install_fault_plan(plan)
        _drive(g, rounds)
        return [a.telemetry_snapshot() for a in g]
    finally:
        for a in g:
            a.deinit()


@pytest.mark.chaos
def test_seeded_slow_rank_detection(monkeypatch):
    """A delay FaultRule on rank 1's outbound convicts rank 1 on BOTH
    handles within two exchange windows, annotates the health map
    suspect_slow (annotation only — state stays ok), and exports the
    verdict as Prometheus gauges."""
    monkeypatch.setenv("ACCL_SKEW_INTERVAL", "4")
    snaps = _seeded_run(_delay_plan(1))
    for snap in snaps:
        verdicts = snap["stragglers"]["verdicts"]
        assert verdicts, "no slow_rank verdict on a seeded delay fault"
        v = verdicts[0]
        assert v["kind"] == "slow_rank"
        assert v["rank"] == 1
        # "within two exchange windows": windows are 0-indexed
        assert v["window"] <= 1
        assert v["latency_us"] > snap["stragglers"]["min_us"]
    # health annotation on the observing rank — annotation ONLY
    h = snaps[0]["health"][1]
    assert h["suspect_slow"] is True
    assert h["state"] == "ok"  # never escalated to suspect/dead

    # collectives keep WORKING against a slow (not dead) rank
    # (no fail-fast: slowness is an operator signal)
    g = emulated_group(2)
    try:
        g[0].engine.fabric.install_fault_plan(_delay_plan(1))
        recv = _drive(g, 9)
        recv[0].sync_from_device()
        np.testing.assert_allclose(recv[0].data, 3.0)
        assert g[0].telemetry_snapshot()["stragglers"]["standing"]

        # Prometheus surface
        prom = g[0].telemetry_prometheus()
        assert "accl_straggler_slow_rank" in prom
        assert "accl_straggler_ewma_latency_us" in prom
    finally:
        for a in g:
            a.deinit()


@pytest.mark.chaos
def test_seeded_slow_rank_detection_deterministic(monkeypatch):
    """Same plan, same convicted rank, same conviction window — twice,
    from fresh groups."""
    monkeypatch.setenv("ACCL_SKEW_INTERVAL", "4")
    first = _seeded_run(_delay_plan(1))[0]["stragglers"]["verdicts"]
    second = _seeded_run(_delay_plan(1))[0]["stragglers"]["verdicts"]
    assert first and second
    assert first[0]["rank"] == second[0]["rank"] == 1
    assert first[0]["window"] == second[0]["window"]


def test_uniform_load_no_verdict(monkeypatch):
    """The false-positive guard: uniform traffic produces ZERO
    straggler verdicts and no health annotations — µs-scale in-process
    latencies never clear the absolute floor."""
    monkeypatch.setenv("ACCL_SKEW_INTERVAL", "4")
    snaps = _seeded_run(None, rounds=12)
    for snap in snaps:
        assert snap["stragglers"]["verdicts"] == []
        assert snap["stragglers"]["standing"] == {}
        assert snap["stragglers"]["windows_judged"] >= 2
        for h in snap["health"].values():
            assert "suspect_slow" not in h


def test_soft_reset_clears_straggler_state(monkeypatch):
    monkeypatch.setenv("ACCL_SKEW_INTERVAL", "4")
    g = emulated_group(2)
    try:
        g[0].engine.fabric.install_fault_plan(_delay_plan(1))
        _drive(g, 8)
        assert g[0].telemetry_snapshot()["stragglers"]["standing"]
        # heal the network, then the collective recovery point
        g[0].engine.fabric.install_fault_plan(None)
        run_parallel(g, lambda a, r: a.soft_reset())
        snap = g[0].telemetry_snapshot()
        assert snap["stragglers"]["standing"] == {}
        assert snap["stragglers"]["verdicts"] == []
        assert "suspect_slow" not in snap["health"][1]
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# SkewJudge / SkewTracker units
# ---------------------------------------------------------------------------


def test_skew_judge_median_discounts_one_receiver():
    """One weird receiver cannot frame a peer: the aggregate is the
    MEDIAN of receivers' observations."""
    j = SkewJudge(world=4, min_us=200.0, factor=4.0, persist=1)
    # rank 3 claims rank 0 is slow; ranks 1 and 2 disagree
    j.post_latency(0, 0, 1, {0: 10.0, 2: 12.0, 3: 9.0})
    j.post_latency(0, 0, 2, {0: 11.0, 1: 10.0, 3: 8.0})
    j.post_latency(0, 0, 3, {0: 90000.0, 1: 12.0, 2: 11.0})
    v = j.post_latency(0, 0, 0, {1: 9.0, 2: 10.0, 3: 11.0})
    assert v is None
    assert j.slow_ranks(0) == []


def test_skew_judge_floor_dominance_and_persistence():
    j = SkewJudge(world=2, min_us=200.0, factor=4.0, persist=2)
    # window 0: dominant and beyond floor — but persist=2 defers
    j.post_latency(0, 0, 0, {1: 5000.0})
    v = j.post_latency(0, 0, 1, {0: 10.0})
    assert v is None
    # window 1: still beyond — convicts now
    j.post_latency(0, 1, 0, {1: 6000.0})
    v = j.post_latency(0, 1, 1, {0: 12.0})
    assert v is not None and v["rank"] == 1 and v["streak"] == 2
    assert v["basis"] == "majority"
    assert j.slow_ranks(0) == [1]
    # beyond-floor but NOT dominant: no conviction
    j2 = SkewJudge(world=2, min_us=200.0, factor=4.0, persist=1)
    j2.post_latency(0, 0, 0, {1: 5000.0})
    assert j2.post_latency(0, 0, 1, {0: 4000.0}) is None


def test_skew_tracker_wire_mode_pairwise():
    """Without a shared judge (socket tier) the tracker convicts from
    its OWN latency observations — pairwise basis, correct on the
    conforming side like the contract plane's pairwise verdict.  Needs
    >= 2 observed sources for the runner-up comparison (world >= 3)."""
    t = SkewTracker(rank=0, world=3, interval=2)
    assert not t.shared_judge
    for _window in range(2):
        for _ in range(2):
            t.on_message(0, 1, 30_000_000)  # 30 ms from rank 1
            t.on_message(0, 2, 400_000)     # 400 us from rank 2
            t.observe(0, duration_ns=1_000_000)
    snap = t.snapshot()
    assert snap["exchange"] == "wire"
    assert snap["standing"]["0"]["rank"] == 1
    assert snap["standing"]["0"]["basis"] == "pairwise"


def test_skew_single_source_never_convicts():
    """A 2-rank wire-mode group has no runner-up to dominate: however
    high the single observed source's latency, it folds into baselines
    but NEVER convicts — localhost-TCP-scale fabric latency must not
    frame an innocent peer (the board path keeps convicting at world 2:
    it aggregates both observers)."""
    t = SkewTracker(rank=0, world=2, interval=2)
    for _window in range(4):
        for _ in range(2):
            t.on_message(0, 1, 50_000_000)  # 50 ms, every window
            t.observe(0, duration_ns=1_000_000)
    snap = t.snapshot()
    assert snap["ewma_latency_us"]["0"]["1"] > 0  # baseline recorded
    assert snap["verdicts"] == [] and snap["standing"] == {}


def test_skew_tracker_wait_baselines_never_convict():
    """Wait-lag asymmetry alone (roots wait less than leaves by
    construction) folds into baselines but NEVER yields a verdict."""
    t = SkewTracker(rank=0, world=2, interval=2)
    j = t.judge
    # rank 0 waits 10x less than rank 1, persistently
    for w in range(4):
        j.post_wait(0, w, 0, 100.0, world=2)
        j.post_wait(0, w, 1, 1000.0, world=2)
    snap = j.snapshot()
    assert snap["ewma_wait_lag_us"]["0"]["0"] > 0  # baseline recorded
    assert snap["verdicts"] == []  # no conviction from wait lag


def test_anomaly_watchdog_alerts_bounded():
    w = AnomalyWatchdog(factor=4.0, warmup=4)
    for _ in range(4):
        assert w.observe("allreduce", 3, 100_000) is None  # 100 us
    alert = w.observe("allreduce", 3, 10_000_000)  # 10 ms: 100x baseline
    assert alert is not None
    assert alert["op"] == "allreduce" and alert["factor"] > 4.0
    # bounded: the ring never exceeds the cap
    for _ in range(200):
        w.observe("allreduce", 3, 50_000_000)
    snap = w.snapshot()
    assert len(snap["alerts"]) <= monitor_mod._ALERT_CAP
    assert snap["alerts_total"] >= 1
    # a persistent regime shift becomes the new baseline: after many
    # 50 ms samples a 50 ms call no longer alerts
    assert w.observe("allreduce", 3, 50_000_000) is None


def test_anomaly_alert_reaches_snapshot_and_prom(monkeypatch):
    monkeypatch.setenv("ACCL_ANOMALY_FACTOR", "10.0")
    g = emulated_group(2)
    try:
        _drive(g, 20)  # past warmup
        # inject one slow call by delaying rank 1's sends hard — 100 ms
        # per hop dominates any loaded-box baseline inflation, so the
        # >=10x regression holds even when the suite shares the machine
        g[0].engine.fabric.install_fault_plan(_delay_plan(1, delay_s=0.1))
        _drive(g, 1)
        g[0].engine.fabric.install_fault_plan(None)
        snap = g[0].telemetry_snapshot()
        assert snap["anomalies"]["alerts_total"] >= 1
        assert snap["anomalies"]["alerts"][0]["op"] == "allreduce"
        assert "accl_anomaly_alerts_total" in g[0].telemetry_prometheus()
    finally:
        for a in g:
            a.deinit()


def test_skew_tracker_begin_comm_resolves_early_claims():
    """A piggybacked claim arriving BEFORE this rank's first completion
    on a subcomm must resolve against the registered comm-relative
    identity and member count — not the world fallbacks (which would
    drop a claim from the peer sharing our world rank number, or post
    with the wrong completeness threshold)."""
    t = SkewTracker(rank=2, world=4, interval=2)
    # subcomm of 3 where our comm-relative rank is 1
    t.begin_comm(77, comm_rank=1, comm_world=3)
    # a claim from subcomm rank 2: without registration the world
    # fallback (me=2) would discard it as self
    t.observe_claim(77, src_rank=2, window=0, mean_us=100.0)
    assert t.judge._wait_posts[(77, 0)] == {2: 100.0}
    # ...and our own claim IS discarded under the registered identity
    t.observe_claim(77, src_rank=1, window=0, mean_us=50.0)
    assert t.judge._wait_posts[(77, 0)] == {2: 100.0}


def test_skew_streak_broken_by_quiet_window():
    """'persist CONSECUTIVE windows' means consecutive: a window where
    the candidate goes unobserved (absent from every vector) resets its
    streak, so two NON-consecutive dominant windows never sum to a
    conviction."""
    j = SkewJudge(world=3, min_us=200.0, factor=4.0, persist=2)

    def window(w, lat1):
        # observers 0 and 2 post; rank 1's latency is `lat1` (None =
        # rank 1 unobserved this window)
        v0 = {2: 10.0} if lat1 is None else {1: lat1, 2: 10.0}
        v2 = {0: 11.0} if lat1 is None else {1: lat1, 0: 11.0}
        j.post_latency(0, w, 0, v0)
        j.post_latency(0, w, 2, v2)
        return j.post_latency(0, w, 1, {0: 9.0, 2: 12.0})

    assert window(0, 9000.0) is None      # dominant: streak 1
    assert window(1, None) is None        # quiet: streak broken
    assert window(2, 9000.0) is None      # dominant again: streak 1
    v = window(3, 9000.0)                 # consecutive: streak 2 convicts
    assert v is not None and v["rank"] == 1 and v["streak"] == 2


# ---------------------------------------------------------------------------
# ScaleAdvisor: traffic-aware grow/shrink advice (ISSUE 17, advisory only)
# ---------------------------------------------------------------------------


def _tenant(p99, count, queued=0, limit=8, cls="batch"):
    return {
        "class": cls,
        "latency": {"p99_us": p99, "count": count},
        "queued": queued,
        "outstanding_limit": limit,
    }


def test_scale_advisor_grow_shrink_hold():
    from accl_tpu.monitor import SCALE_MIN_SAMPLES, ScaleAdvisor

    adv = ScaleAdvisor(grow_p99_us=1000.0, shrink_p99_us=100.0)
    # no data at all -> hold, never shrink-on-silence
    out = adv.advise(None, world=4)
    assert (out["recommendation"], out["reason"]) == \
        ("hold", "insufficient_data")
    assert out["advisory_only"] is True
    # a sampled tenant over the high-water p99 -> grow
    out = adv.advise(
        {"tenants": {"0": _tenant(5000.0, SCALE_MIN_SAMPLES)}}, world=4
    )
    assert (out["recommendation"], out["reason"]) == \
        ("grow", "tail_pressure")
    assert out["hot_tenants"][0]["reason"] == "p99_over_high_water"
    # queue backlog beyond the outstanding window -> grow, even with a
    # cold histogram (grant starvation precedes tail evidence)
    out = adv.advise(
        {"tenants": {"1": _tenant(None, 0, queued=20, limit=8)}}, world=4
    )
    assert out["recommendation"] == "grow"
    assert out["hot_tenants"][0]["reason"] == "queue_backlog"
    # every sampled tenant under the low-water mark, no queues -> shrink
    out = adv.advise(
        {"tenants": {"0": _tenant(50.0, SCALE_MIN_SAMPLES)}}, world=4
    )
    assert (out["recommendation"], out["reason"]) == ("shrink", "idle_tail")
    # mid-band -> hold
    out = adv.advise(
        {"tenants": {"0": _tenant(500.0, SCALE_MIN_SAMPLES)}}, world=4
    )
    assert (out["recommendation"], out["reason"]) == ("hold", "within_band")
    # under-sampled tenants never count (a cold histogram is not idle)
    out = adv.advise(
        {"tenants": {"0": _tenant(50.0, SCALE_MIN_SAMPLES - 1)}}, world=4
    )
    assert (out["recommendation"], out["reason"]) == \
        ("hold", "insufficient_data")


def test_scale_advisor_deterministic_and_latched():
    """A pure function of the snapshot: same tenant pressure, same
    advice — and the last advisory latches for the snapshot surface."""
    from accl_tpu.monitor import SCALE_MIN_SAMPLES, ScaleAdvisor

    snap = {"tenants": {
        "3": _tenant(9000.0, SCALE_MIN_SAMPLES, cls="latency"),
        "5": _tenant(40.0, SCALE_MIN_SAMPLES),
    }}
    a = ScaleAdvisor(grow_p99_us=1000.0, shrink_p99_us=100.0)
    b = ScaleAdvisor(grow_p99_us=1000.0, shrink_p99_us=100.0)
    assert a.advise(snap, world=4) == b.advise(snap, world=4)
    assert a.last() == b.last()
    assert a.snapshot()["advisories"] == 1
    assert a.snapshot()["last"]["recommendation"] == "grow"


def test_scale_advice_on_live_surfaces():
    """The advisory rides telemetry_snapshot()["membership"] and the
    /membership monitor route — surfaced, never acted on."""
    g = emulated_group(2)
    try:
        doc = g[0].telemetry_snapshot()["membership"]
        advice = doc.get("scale_advice")
        assert advice is not None
        assert advice["advisory_only"] is True
        assert advice["recommendation"] in ("grow", "shrink", "hold")
        port = g[0].start_monitor(0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/membership", timeout=5
        ).read().decode()
        served = json.loads(body)
        assert served["scale_advice"]["recommendation"] == \
            advice["recommendation"]
        index = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=5
        ).read().decode()
        assert "/membership" in index
    finally:
        for a in g:
            a.deinit()
