"""Causal trace plane + postmortem bundles.

The acceptance matrix of the observability PR: deterministic cross-rank
trace ids (same collective → same id on every rank, zero wire bytes),
flow events that survive a merge with every start matched to a finish,
command-ring introspection (window log, /cmdring route, ring-resident
spans), and automatic postmortem bundles on structured failures —
bounded and best-effort under chaos (a dead solicited peer degrades to
a partial bundle, never a hang).
"""

import json
import os
import socket as socketlib
import threading
import time

import numpy as np
import pytest

from accl_tpu import telemetry as T
from accl_tpu.constants import ACCLError, ErrorCode
from accl_tpu.core import emulated_group, socket_group_member, xla_group
from accl_tpu.faults import FaultPlan, FaultRule
from accl_tpu.monitor import BlackBox, load_bundle
from helpers import record_rank_traces, run_parallel


def _deinit(group):
    for a in group:
        a.deinit()


def _records(a, op=None):
    recs = a.telemetry_snapshot()["flight_recorder"]
    return [r for r in recs if op is None or r["op"] == op]


def _free_addrs(n):
    ports, socks = [], []
    for _ in range(n):
        s = socketlib.socket()
        s.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return [f"127.0.0.1:{p}" for p in ports]


# ---------------------------------------------------------------------------
# trace-id derivation
# ---------------------------------------------------------------------------


def test_trace_id_derivation_units():
    """Deterministic, nonzero, keyed on every basis field — and NEVER
    process-salted (crc32 of a canonical string, so a re-derivation in
    another process/run agrees)."""
    a = T.collective_trace_id("allreduce", 7, 1, 3)
    assert a == T.collective_trace_id("allreduce", 7, 1, 3)
    assert a != 0
    assert a != T.collective_trace_id("allgather", 7, 1, 3)
    assert a != T.collective_trace_id("allreduce", 8, 1, 3)
    assert a != T.collective_trace_id("allreduce", 7, 2, 3)  # generation
    assert a != T.collective_trace_id("allreduce", 7, 1, 4)  # seqn
    p = T.p2p_trace_id(7, 0, 1, 5, 2)
    assert p == T.p2p_trace_id(7, 0, 1, 5, 2)
    assert p != T.p2p_trace_id(7, 1, 0, 5, 2)  # directed channel
    # stream-port variants live on their own id space: their intake
    # counters are separate, so without the discriminator a stream_put
    # and a plain send on one (comm, dst, tag) would collide at seqn 0
    assert p != T.p2p_trace_id(7, 0, 1, 5, 2, stream=4)


def test_trace_ids_match_across_ranks_inproc():
    """Every rank of one collective derives the SAME trace id with zero
    wire bytes, and each rank's flow phase is its deterministic role
    (rank 0 starts, the last rank finishes)."""
    g = emulated_group(3)
    try:
        send = [a.create_buffer_from(np.ones(16, np.float32)) for a in g]
        recv = [a.create_buffer(16, np.float32) for a in g]

        def step(a, r):
            for _ in range(4):
                a.allreduce(send[r], recv[r], 16)

        run_parallel(g, step, timeout=60.0)
        ids = [
            [r["trace_id"] for r in _records(a, "allreduce")] for a in g
        ]
        assert ids[0] == ids[1] == ids[2]
        assert len(ids[0]) == 4 and all(ids[0])
        # roles: exactly one s (rank 0), one f (last rank), middles t
        flows = []
        for a in g:
            evs = a.telemetry_trace_events()
            flows.append({
                e["ph"] for e in evs if e.get("cat") == "accl.flow"
            })
        assert flows[0] == {"s"}
        assert flows[1] == {"t"}
        assert flows[2] == {"f"}
    finally:
        _deinit(g)


def test_p2p_trace_ids_match_and_flows_validate():
    """A plain send→recv pair derives one id on both ends (directed
    channel match counter) — sender s, receiver f — and the merged
    export validates with no unmatched flow ends."""
    g = emulated_group(2)
    try:
        src = g[0].create_buffer_from(np.arange(8, dtype=np.float32))
        dst = g[1].create_buffer(8, np.float32)

        def step(a, r):
            if r == 0:
                a.send(src, 8, 1, tag=3)
            else:
                a.recv(dst, 8, 0, tag=3)

        for _ in range(3):
            run_parallel(g, step, timeout=60.0)
        sends = _records(g[0], "send")
        recvs = _records(g[1], "recv")
        assert [r["trace_id"] for r in sends] == [
            r["trace_id"] for r in recvs
        ]
        merged = T.merge_traces([
            {"traceEvents": a.telemetry_trace_events()} for a in g
        ])
        assert T.validate_flows(merged["traceEvents"]) == []
        phases = [
            (e["ph"], e["pid"]) for e in merged["traceEvents"]
            if e.get("cat") == "accl.flow"
        ]
        assert ("s", 0) in phases and ("f", 1) in phases
    finally:
        _deinit(g)


def test_trace_ids_match_on_socket_tier_and_wire_stamp():
    """The socket tier (one fabric per rank, no shared anchor) derives
    the same ids from the same basis — zero wire bytes for the id
    itself — and the trc piggyback records wire-hop flow steps at
    delivery."""
    T.wire_reset()
    addrs = _free_addrs(2)
    g = [socket_group_member(i, addrs) for i in range(2)]
    try:
        send = [a.create_buffer_from(np.ones(16, np.float32)) for a in g]
        recv = [a.create_buffer(16, np.float32) for a in g]

        def step(a, r):
            for _ in range(3):
                a.allreduce(send[r], recv[r], 16)

        run_parallel(g, step, timeout=60.0)
        ids = [
            [r["trace_id"] for r in _records(a, "allreduce")] for a in g
        ]
        assert ids[0] == ids[1] and len(ids[0]) == 3
        # the delivery side recorded piggybacked wire-hop steps whose
        # ids are real collective ids
        steps = T.wire_flow_events()
        assert steps, "no wire flow steps recorded at delivery"
        assert {s["id"] for s in steps} & set(ids[0])
    finally:
        _deinit(g)
        T.wire_reset()


def test_soft_reset_rekeys_trace_generation():
    """soft_reset starts a new id generation (collective by contract):
    the same call sequence derives DIFFERENT ids after the reset — and
    they still match across ranks."""
    g = emulated_group(2)
    try:
        send = [a.create_buffer_from(np.ones(8, np.float32)) for a in g]
        recv = [a.create_buffer(8, np.float32) for a in g]

        def step(a, r):
            a.allreduce(send[r], recv[r], 8)

        run_parallel(g, step, timeout=60.0)
        pre = [_records(a, "allreduce")[-1]["trace_id"] for a in g]
        run_parallel(g, lambda a, r: a.soft_reset(), timeout=60.0)
        run_parallel(g, step, timeout=60.0)
        post = [_records(a, "allreduce")[-1]["trace_id"] for a in g]
        assert pre[0] == pre[1] and post[0] == post[1]
        assert pre[0] != post[0]
    finally:
        _deinit(g)


def test_pipelined_segments_nest_under_aggregate():
    """Segmented pipelining: the aggregate's span parents its segments
    (parent_id on every segment record = the aggregate's trace id)."""
    g = emulated_group(2)
    try:
        for a in g:
            a.set_tuning("PIPELINE_THRESHOLD", 64)
            a.set_tuning("RING_SEGMENTS", 2)
        n = 4096
        send = [
            a.create_buffer_from(np.ones(n, np.float32)) for a in g
        ]
        recv = [a.create_buffer(n, np.float32) for a in g]

        def step(a, r):
            a.allreduce(send[r], recv[r], n)

        run_parallel(g, step, timeout=60.0)
        recs = _records(g[0], "allreduce")
        parents = [r.get("parent_id") for r in recs if r.get("parent_id")]
        aggs = [r for r in recs if not r.get("parent_id")]
        assert parents, "no segment records carried a parent id"
        assert set(parents) <= {r["trace_id"] for r in aggs}
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# merge CLI: flow validation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rank_traces(tmp_path_factory):
    return record_rank_traces(tmp_path_factory.mktemp("rank_traces"))


def test_merge_cli_validates_committed_artifact(rank_traces, tmp_path, capsys):
    """A 4-rank gang run's traces merge cleanly through the CLI
    (flow validation on), and the merged artifact carries cross-rank
    flow events plus ring-resident spans."""
    out = str(tmp_path / "merged.json")
    assert T.main(["merge", "--out", out] + rank_traces) == 0
    with open(out) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert T.validate_flows(evs) == []
    flows = [e for e in evs if e.get("cat") == "accl.flow"]
    assert {e["ph"] for e in flows} >= {"s", "f"}
    assert any(e.get("cat") == "cmdring" for e in evs), (
        "no ring-resident spans in the merged trace"
    )
    # p2p flows (send→recv): both ends of at least one pair
    p2p_ids = {
        e["id"] for e in flows
        if e.get("args", {}).get("op") in ("send", "recv")
    }
    assert p2p_ids


def test_merge_cli_errors_when_rank_file_missing(rank_traces, tmp_path):
    """Merging only 3 of the 4 rank files drops rank 0's
    flow starts: the CLI refuses the merge (the artifact would claim
    cross-rank coverage it doesn't have)."""
    with pytest.raises(SystemExit, match="unmatched flow"):
        T.main(["merge", "--out", str(tmp_path / "m.json")] + rank_traces[1:])


def test_flow_validation_exempts_ring_truncation():
    """A flow whose start rolled out of one rank's bounded flight ring
    (older than the merge's common covered window) is exempt — routine
    truncation on a long run must not read as a broken artifact."""
    ev = lambda ph, fid, ts: {  # noqa: E731 - tiny local ctor
        "name": "accl::flow", "cat": "accl.flow", "ph": ph,
        "id": fid, "ts": ts, "pid": 0, "tid": 0,
    }
    # rank A's ring evicted the old flow 0xaa entirely; rank B still
    # holds its finish.  Both hold the fresh flow 0xbb.
    doc_a = {"traceEvents": [ev("s", "0xbb", 100.0)]}
    doc_b = {"traceEvents": [ev("f", "0xaa", 5.0), ev("f", "0xbb", 101.0)]}
    assert T.validate_flow_docs([doc_a, doc_b]) == []
    # the raw (non-truncation-aware) check still reports it
    assert T.validate_flows(
        doc_a["traceEvents"] + doc_b["traceEvents"]
    ) != []
    # a fresh unmatched end (inside the covered window) still errors
    doc_b2 = {"traceEvents": [ev("f", "0xcc", 102.0),
                              ev("f", "0xbb", 101.0)]}
    assert T.validate_flow_docs([doc_a, doc_b2]) != []


def test_merge_cli_errors_on_unmatched_flow(tmp_path):
    """An `s` with no matching `f` (a rank file missing from the merge)
    is an ERROR, not a silently broken artifact."""
    doc = {"traceEvents": [
        {"name": "accl::flow", "cat": "accl.flow", "ph": "s",
         "id": "0xdeadbeef", "ts": 1.0, "pid": 0, "tid": 0},
    ]}
    p = tmp_path / "half.json"
    p.write_text(json.dumps(doc))
    with pytest.raises(SystemExit, match="unmatched flow"):
        T.main(["merge", "--out", str(tmp_path / "m.json"), str(p)])
    # the explicit escape hatch still merges
    assert T.main([
        "merge", "--no-flow-check",
        "--out", str(tmp_path / "m.json"), str(p),
    ]) == 0


# ---------------------------------------------------------------------------
# command-ring introspection
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ring4():
    g = xla_group(4)
    yield g
    _deinit(g)


def _ring_window(g, send, out1, out2, n):
    def work(a, r):
        with a.batch():
            q1 = a.allreduce(send[r], out1[r], n, run_async=True)
            q2 = a.allreduce(send[r], out2[r], n, run_async=True)
        q1.wait()
        q2.wait()

    run_parallel(g, work, timeout=90.0)


def test_ring_window_log_and_spans(ring4):
    g = ring4
    n = 128
    send = [
        a.create_buffer_from(np.full(n, r + 1.0, np.float32))
        for r, a in enumerate(g)
    ]
    out1 = [a.create_buffer(n, np.float32) for a in g]
    out2 = [a.create_buffer(n, np.float32) for a in g]
    for _ in range(2):
        _ring_window(g, send, out1, out2, n)
    for a in g:
        a.flush()
    ring = g[0].engine.telemetry_report()["cmdring"]
    assert ring["windows_logged"] >= 1
    assert ring["window_latency_log2_us"]
    win = ring["windows"][-1]
    assert win["basis"] == "host"
    assert win["slots"] and all(
        s["opcode"] == "ALLREDUCE" and s["retcode"] == 1
        and s["seqn"] >= 0 and s["trace_id"]
        for s in win["slots"]
    )
    # ring-resident spans ride the trace export, flow-linked (t steps)
    # to the issuing calls' ids
    evs = g[0].telemetry_trace_events()
    spans = [e for e in evs if e.get("cat") == "cmdring"]
    assert any(e["name"].startswith("cmdring::window") for e in spans)
    slot_flow_ids = {
        e["id"] for e in spans
        if e.get("ph") == "t" and e["name"] == "accl::flow"
    }
    call_ids = {
        f"0x{r['trace_id']:08x}"
        for r in _records(g[0], "allreduce") if r.get("trace_id")
    }
    assert slot_flow_ids & call_ids
    # merged across all four ranks: one copy of the shared ring rows,
    # flows still well-formed
    merged = T.merge_traces([
        {"traceEvents": a.telemetry_trace_events()} for a in g
    ])
    assert T.validate_flows(merged["traceEvents"]) == []
    merged_spans = [
        json.dumps(e, sort_keys=True)
        for e in merged["traceEvents"] if e.get("cat") == "cmdring"
    ]
    assert len(merged_spans) == len(set(merged_spans))
    # prometheus: the ring introspection gauges render
    prom = g[0].telemetry_prometheus()
    assert "accl_cmdring_run_state" in prom
    assert "accl_cmdring_window_latency_us" in prom


def test_cmdring_route_and_index_page(ring4):
    import urllib.request

    g = ring4
    port = g[0].start_monitor(0)
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/cmdring", timeout=10
        ) as r:
            ring = json.loads(r.read().decode())
        assert ring.get("enabled") is True
        assert "windows" in ring and "window_latency_log2_us" in ring
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10
        ) as r:
            index = r.read().decode()
        assert "/cmdring" in index
        assert "cmdring: state=" in index
        assert "postmortem:" in index
        assert "membership: epoch=" in index
    finally:
        g[0].stop_monitor()


def test_ring_stats_schema(ring4):
    """The introspection block after a window: every key a reader has
    (perfbench reads ``fallbacks`` and ``lowering``, the monitor and the
    gauges the rest) is there, a refill is a doorbell is a dispatch, and
    the keys that described the resident run are gone, with their
    gauges."""
    g = ring4
    n = 64
    send = [
        a.create_buffer_from(np.full(n, r + 1.0, np.float32))
        for r, a in enumerate(g)
    ]
    out1 = [a.create_buffer(n, np.float32) for a in g]
    out2 = [a.create_buffer(n, np.float32) for a in g]
    _ring_window(g, send, out1, out2, n)
    for a in g:
        a.flush()
    ring = g[0].engine.telemetry_report()["cmdring"]
    kept = {
        "enabled", "mode", "lowering", "depth", "state", "refills",
        "doorbells", "dispatches", "slot_hits", "slot_puts", "slots",
        "wraps", "resets",
        "max_window", "occupancy", "ops", "fallbacks", "chaos_faults",
        "breakers", "slot_budgets", "comm_slots", "budgeted_windows",
        "windows_logged", "window_latency_sum_us",
        "window_latency_log2_us", "windows",
    }
    assert set(ring) == kept
    assert ring["state"] in ("parked", "armed")
    assert ring["refills"] == ring["doorbells"] == ring["dispatches"] >= 1
    # a window's slot words were on the chips already, or were put
    assert ring["slot_hits"] + ring["slot_puts"] == ring["dispatches"]
    assert ring["lowering"] == "xla"
    win = ring["windows"][-1]
    assert set(win) == {
        "window_id", "comm", "ts_us", "dur_us", "slots", "basis",
    }
    prom = g[0].telemetry_prometheus()
    for gone in ("mailbox", "sustained_occupancy", "persistent",
                 "run_windows", "linger"):
        assert gone not in prom
    assert "accl_engine_cmdring_dispatches" in prom
    assert "accl_cmdring_op_slots_total" in prom


# ---------------------------------------------------------------------------
# postmortem bundles
# ---------------------------------------------------------------------------


def test_contract_violation_writes_single_bundle(tmp_path, monkeypatch):
    """An induced CONTRACT_VIOLATION produces exactly ONE bundle per
    failing handle, with >= 2 ranks' evidence merged and the path named
    in ACCLError.details['postmortem']."""
    monkeypatch.setenv("ACCL_POSTMORTEM_DIR", str(tmp_path))
    g = emulated_group(3)
    try:
        for a in g:
            a.set_contract_verify(True, interval=2)
        g[0].engine.fabric.install_fault_plan(FaultPlan(
            rules=[FaultRule(action="diverge", rank=2)], seed=7,
        ))
        send = [a.create_buffer_from(np.ones(8, np.float32)) for a in g]
        recv = [a.create_buffer(8, np.float32) for a in g]
        errs = {}

        def step(a, r):
            try:
                for _ in range(10):
                    a.allreduce(send[r], recv[r], 8)
            except ACCLError as e:
                errs[r] = e

        run_parallel(g, step, timeout=90.0)
        assert errs, "divergence was not detected"
        for r, e in errs.items():
            assert e.code == ErrorCode.CONTRACT_VIOLATION
            path = e.details.get("postmortem")
            assert path and os.path.exists(path)
            bundle = load_bundle(path)
            assert bundle["code"] == "CONTRACT_VIOLATION"
            assert len(bundle["reachable"]) >= 2
            assert bundle["absent"] == []
            # the evidence carries the sections the forensics need
            ev = bundle["ranks"][str(r)]
            assert ev["flight_recorder"]
            assert "membership" in ev["snapshot"]
            assert "contract" in ev["snapshot"]
            assert "stragglers" in ev["snapshot"]
        # counter-asserted: ONE bundle per failing handle (the latch),
        # however many calls failed after the standing verdict
        for r in errs:
            snap = g[r].telemetry_snapshot()["postmortem"]
            assert snap["bundles_written"] == 1
    finally:
        _deinit(g)


def test_rank_evicted_writes_single_bundle(tmp_path, monkeypatch):
    """An induced RANK_EVICTED (explicit eviction) captures one bundle
    per surviving handle — latched on the membership epoch, so the
    cutover hook and the raise paths collapse to one artifact."""
    monkeypatch.setenv("ACCL_POSTMORTEM_DIR", str(tmp_path))
    g = emulated_group(3)
    try:
        for a in g:
            a.set_elastic(True)

        res = run_parallel(g[:2], lambda a, r: a.evict_rank(2),
                           timeout=60.0)
        assert all(p is not None for p in res)
        for r in range(2):
            snap = g[r].telemetry_snapshot()["postmortem"]
            assert snap["bundles_written"] == 1
            bundle = load_bundle(snap["last_bundle"])
            assert bundle["code"] == "RANK_EVICTED"
            assert len(bundle["reachable"]) >= 2
        # the evicted handle's self-eviction raise also rides the plane
        with pytest.raises(ACCLError) as exc:
            g[2].evict_rank(2)
        assert exc.value.code == ErrorCode.RANK_EVICTED
        assert exc.value.details.get("postmortem")
    finally:
        _deinit(g)


def test_postmortem_disabled_is_free(tmp_path):
    """Without ACCL_POSTMORTEM_DIR the plane stays disabled: failures
    carry no postmortem key and nothing is written."""
    g = emulated_group(2)
    try:
        assert g[0]._blackbox is not None
        assert g[0]._blackbox.enabled is False
        err = g[0]._deadlock_error("test")
        assert "postmortem" not in err.details
    finally:
        _deinit(g)


def test_deadlock_error_captures_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv("ACCL_POSTMORTEM_DIR", str(tmp_path))
    g = emulated_group(2)
    try:
        err = g[0]._deadlock_error("wedged-drain")
        assert err.code == ErrorCode.DEADLOCK_SUSPECTED
        path = err.details["postmortem"]
        bundle = load_bundle(path)
        assert bundle["code"] == "DEADLOCK_SUSPECTED"
        # latched: a second deadlock in the same generation reuses it
        err2 = g[0]._deadlock_error("wedged-again")
        assert err2.details["postmortem"] == path
        assert g[0].telemetry_snapshot()["postmortem"][
            "bundles_written"] == 1
        # soft_reset clears the latch — a fresh regime bundles fresh
        run_parallel(g, lambda a, r: a.soft_reset(), timeout=60.0)
        err3 = g[0]._deadlock_error("post-reset")
        assert err3.details["postmortem"] != path
    finally:
        _deinit(g)


def test_wire_solicitation_merges_peer_evidence(tmp_path, monkeypatch):
    """Socket tier: the POSTMORTEM wire frames solicit peers' evidence
    within the bounded deadline and merge it into the bundle."""
    monkeypatch.setenv("ACCL_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("ACCL_POSTMORTEM_WAIT_S", "5.0")
    addrs = _free_addrs(2)
    g = [socket_group_member(i, addrs) for i in range(2)]
    try:
        send = [a.create_buffer_from(np.ones(8, np.float32)) for a in g]
        recv = [a.create_buffer(8, np.float32) for a in g]
        run_parallel(
            g, lambda a, r: a.allreduce(send[r], recv[r], 8),
            timeout=60.0,
        )
        path = g[0]._blackbox.capture("DEADLOCK_SUSPECTED", "test")
        bundle = load_bundle(path)
        assert sorted(bundle["reachable"]) == [0, 1]
        assert bundle["absent"] == []
        assert bundle["ranks"]["1"]["flight_recorder"]
    finally:
        _deinit(g)


def test_dead_peer_degrades_to_partial_bundle_bounded(
    tmp_path, monkeypatch
):
    """kill_rank mid-bundle: a dead solicited peer never answers — the
    capture returns a PARTIAL bundle within the bounded deadline (never
    a hang) and documents the peer as absent."""
    monkeypatch.setenv("ACCL_POSTMORTEM_DIR", str(tmp_path))
    monkeypatch.setenv("ACCL_POSTMORTEM_WAIT_S", "1.0")
    addrs = _free_addrs(3)
    g = [socket_group_member(i, addrs) for i in range(3)]
    try:
        send = [a.create_buffer_from(np.ones(8, np.float32)) for a in g]
        recv = [a.create_buffer(8, np.float32) for a in g]
        run_parallel(
            g, lambda a, r: a.allreduce(send[r], recv[r], 8),
            timeout=60.0,
        )
        # rank 2 dies (its fabric closes: frames to it fail or vanish)
        g[2].engine.shutdown()
        t0 = time.monotonic()
        path = g[0]._blackbox.capture("DEADLOCK_SUSPECTED", "test")
        elapsed = time.monotonic() - t0
        assert elapsed < 10.0, "capture was not bounded"
        bundle = load_bundle(path)
        assert 0 in bundle["reachable"]
        assert 2 in bundle["absent"]
    finally:
        for a in g[:2]:
            a.deinit()
        try:
            g[2].deinit()
        except Exception:
            pass


def test_blackbox_units(tmp_path):
    """BlackBox protocol units: latch keys, reply delivery, bounded
    solicitation accounting."""
    bb = BlackBox(
        rank=0, world=3,
        evidence_fn=lambda: {"flight_recorder": [1]},
        directory=str(tmp_path),
        wait_s=0.2,
        solicit_fn=lambda token: 2,  # asks 2 peers; only 1 answers
    )
    done = []

    def late_reply():
        time.sleep(0.05)
        bb.deliver_reply(1, 1, {"flight_recorder": [2]})
        done.append(True)

    t = threading.Thread(target=late_reply, name="accl-test-reply")
    t.start()
    t0 = time.monotonic()
    path = bb.capture("RING_FAILURE", "test", key=("k", 1))
    assert time.monotonic() - t0 < 2.0
    t.join(5.0)
    bundle = load_bundle(path)
    assert bundle["reachable"] == [0, 1]
    assert bundle["absent"] == [2]
    assert bb.solicit_timeouts == 1
    # latched: same key returns the same artifact, no second write
    assert bb.capture("RING_FAILURE", "again", key=("k", 1)) == path
    assert bb.bundles_written == 1
    # a different key writes a fresh bundle
    p2 = bb.capture("RING_FAILURE", "other", key=("k", 2))
    assert p2 != path and bb.bundles_written == 2
    bb.reset()
    assert bb.capture("RING_FAILURE", "post-reset", key=("k", 1)) != path


def test_load_bundle_rejects_malformed(tmp_path):
    p = tmp_path / "bundle.json"
    p.write_text(json.dumps({"code": "X"}))
    with pytest.raises(ValueError, match="missing"):
        load_bundle(str(p))
