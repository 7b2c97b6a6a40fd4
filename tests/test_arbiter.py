"""Multi-tenant QoS arbiter (accl_tpu.arbiter): tenant classes, DRR
admission, quota enforcement at the in-flight window and command-ring
refill windows, latched SPMD-uniform decisions, and the adversarial
cross-tenant fairness contract (a BEST_EFFORT flooder absorbs the
backpressure while a GUARANTEED tenant's p99 stays bounded)."""

from __future__ import annotations

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from accl_tpu.arbiter import (
    CLASS_WEIGHTS,
    QosArbiter,
    TenantClass,
    TokenBucket,
    coerce_class,
    hist_p99_us,
)
from accl_tpu.constants import ACCLError, ConfigFunction, ErrorCode
from accl_tpu.core import emulated_group, xla_group

from helpers import run_parallel


def _deinit(group):
    for a in group:
        a.deinit()


# ---------------------------------------------------------------------------
# unit: classes, buckets, p99 estimator
# ---------------------------------------------------------------------------


def test_tenant_class_coercion_and_weights():
    assert coerce_class("guaranteed") is TenantClass.GUARANTEED
    assert coerce_class(TenantClass.BURST) is TenantClass.BURST
    assert coerce_class(2) is TenantClass.BEST_EFFORT
    with pytest.raises(ValueError):
        coerce_class("platinum")
    # guaranteed outweighs burst outweighs best-effort
    assert (
        CLASS_WEIGHTS[TenantClass.GUARANTEED]
        > CLASS_WEIGHTS[TenantClass.BURST]
        > CLASS_WEIGHTS[TenantClass.BEST_EFFORT]
    )


def test_token_bucket_deterministic_clock():
    now = [0.0]
    tb = TokenBucket(1000.0, burst_bytes=1000, clock=lambda: now[0])
    assert tb.throttle_ns(600) == 0          # burst covers it
    owed = tb.throttle_ns(1000)              # 600 tokens short
    assert owed == pytest.approx(0.6e9, rel=0.01)
    now[0] += 1.0                            # a second refills 1000
    assert tb.throttle_ns(300) == 0
    # rate 0 = uncapped
    assert TokenBucket(0.0).throttle_ns(10**9) == 0


def test_hist_p99_estimator():
    assert hist_p99_us({"count": 0, "log2_us": {}}) is None
    # 99/100 samples in bucket 3 ([8,16) us): p99 = that bucket's edge
    assert hist_p99_us({"count": 100, "log2_us": {"3": 99, "10": 1}}) == 16.0
    # a 10% tail in bucket 10 drags p99 to the tail bucket's edge
    assert (
        hist_p99_us({"count": 100, "log2_us": {"3": 90, "10": 10}})
        == 2 ** 11
    )


# ---------------------------------------------------------------------------
# unit: the DRR admission machine
# ---------------------------------------------------------------------------


def test_admission_decision_latched_per_seq():
    """First rank to a call index computes the decision (consuming the
    token bucket ONCE); every later rank replays the identical record —
    the DemotionLedger discipline."""
    now = [0.0]
    arb = QosArbiter(clock=lambda: now[0])
    arb.armed = True
    arb.register(7, name="serve", cls="guaranteed", world=2)
    arb.set_quota(7, bytes_per_s=1000)
    t = arb.tenant(7)
    t.bucket = TokenBucket(1000.0, burst_bytes=1000, clock=lambda: now[0])
    d0 = arb.admit(7, 0, 800)
    d1 = arb.admit(7, 0, 800)  # the second rank of the same call
    assert d0["throttle_ns"] == d1["throttle_ns"] == 0
    assert d0["class"] == d1["class"] == "GUARANTEED"
    # bucket charged once (800), not twice: the next call owes 600 ns,
    # not 1400 — the latch consumed the bucket exactly once per call
    d2 = arb.admit(7, 1, 800)
    assert d2["throttle_ns"] == pytest.approx(0.6e9, rel=0.01)
    arb.reset_ledger()
    assert arb.admit(7, 0, 1)["throttle_ns"] >= 0  # fresh ledger space


def test_outstanding_backpressure_flooder_queues():
    """A tenant at its in-flight share queues further admissions; a
    guaranteed tenant's calls keep flowing; releases drain the queue in
    order.  No over-admissions under normal operation."""
    arb = QosArbiter()
    arb.armed = True
    arb.register(1, name="serve", cls="guaranteed", world=1)
    arb.register(2, name="bulk", cls="best_effort", world=1)
    arb.set_quota(2, window_share=1)  # flooder: ONE outstanding
    granted = []
    threads = [
        threading.Thread(
            target=lambda i=i: granted.append(
                (i, arb.admit(2, i, 100, timeout_s=20))
            ),
            name=f"accl-test-flood-{i}",
        )
        for i in range(4)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if arb.tenant(2).in_flight() == 1 and arb.tenant(2).queued() == 3:
            break
        time.sleep(0.01)
    snap = arb.snapshot()["tenants"]["2"]
    assert snap["outstanding"] == 1
    assert snap["queued"] == 3
    # the guaranteed tenant is untouched by the flooder's backlog
    d = arb.admit(1, 0, 100, timeout_s=5)
    assert d is not None and d["wait_ns"] < 2e9
    for _ in range(4):
        arb.release(2)
    for t in threads:
        t.join(10)
    assert len(granted) == 4
    done = arb.snapshot()["tenants"]["2"]
    assert done["over_admissions"] == 0
    assert done["admitted"] == 4


def test_bounded_wait_over_admits_instead_of_wedging():
    """A starved ticket over-admits with a counted reason after the
    bounded wait — the park_timeout_s discipline: intake never wedges."""
    arb = QosArbiter()
    arb.armed = True
    arb.register(2, name="bulk", cls="best_effort", world=1)
    arb.set_quota(2, window_share=1)
    assert arb.admit(2, 0, 100) is not None  # takes the only slot
    t0 = time.monotonic()
    d = arb.admit(2, 1, 100, timeout_s=0.2)  # nobody will release
    took = time.monotonic() - t0
    assert d is not None  # over-admitted, not wedged
    assert took < 5.0
    snap = arb.snapshot()
    assert snap["grant_timeouts"] == 1
    assert snap["tenants"]["2"]["over_admissions"] == 1


def test_drr_shares_track_weights_under_saturation():
    """Both tenants saturated at equal offered load: the DRR grant
    stream favors the heavier weight — the guaranteed tenant's grant
    waits stay well below the flooder's."""
    arb = QosArbiter()
    arb.armed = True
    arb.register(1, name="serve", cls="guaranteed", world=1)   # weight 8
    arb.register(2, name="bulk", cls="best_effort", world=1)   # weight 1
    arb.set_quota(1, window_share=2)
    arb.set_quota(2, window_share=2)

    def worker(cid, n):
        for i in range(n):
            arb.admit(cid, i, 32 * 1024, timeout_s=20)
            arb.release(cid)

    threads = [
        threading.Thread(
            target=worker, args=(cid, 300), name=f"accl-test-drr-{cid}"
        )
        for cid in (1, 2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    snap = arb.snapshot()
    assert snap["grant_timeouts"] == 0
    g = snap["tenants"]["1"]
    f = snap["tenants"]["2"]
    assert g["admitted"] == f["admitted"] == 300
    # per-admission wait: the weighted queue must not make the
    # guaranteed tenant wait longer than the flooder
    g_wait = g["grant_wait_ns_total"] / g["admitted"]
    f_wait = f["grant_wait_ns_total"] / f["admitted"]
    assert g_wait <= f_wait * 1.5, (g_wait, f_wait)


def test_admission_slot_released_when_dispatch_raises():
    """A raise between admission and the completion hooks (a contract
    verdict, a failed engine start) must free the tenant's outstanding
    slot — caught-and-retried failures must not pin the owner at its
    limit (each retry would then stall the bounded wait and over-admit
    forever)."""
    g = emulated_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "guaranteed", name="serve", window_share=1)
        a = g[0]
        a.set_timeout(1.0)  # keeps a would-be leak stall short
        send = a.create_buffer_from(np.ones(8, np.float32))
        recv = a.create_buffer(8, np.float32)
        orig = a.engine.start

        def boom(options):
            raise RuntimeError("dispatch exploded")

        a.engine.start = boom
        try:
            for _ in range(3):  # > window_share: would wedge on a leak
                with pytest.raises(RuntimeError):
                    a.allreduce(send, recv, 8)
        finally:
            a.engine.start = orig
        t = a._arbiter.tenant(a.comm.id)
        assert t.in_flight() == 0
        assert t.queued() == 0
        snap = a._arbiter.snapshot()
        assert snap["grant_timeouts"] == 0
        assert snap["tenants"][str(a.comm.id)]["over_admissions"] == 0
    finally:
        _deinit(g)


def test_disarmed_is_passthrough():
    arb = QosArbiter()
    arb.register(1, name="serve", cls="guaranteed", world=1)
    assert arb.admit(1, 0, 100) is None  # disarmed
    arb.armed = True
    assert arb.admit(99, 0, 100) is None  # unregistered comm
    assert arb.snapshot()["passthrough"] == 2


# ---------------------------------------------------------------------------
# unit: the overlap window's per-key (per-tenant) depth
# ---------------------------------------------------------------------------


def test_inflight_window_per_key_depth():
    """set_key_depth bounds ONE key's in-flight launches at its tenant
    share while other keys ride the global depth — counter-asserted via
    max_depth_seen and the blocking park."""
    from accl_tpu.overlap import InflightWindow

    w = InflightWindow(depth=4, park_timeout_s=5.0)
    w.set_key_depth("bulk", 1)
    assert w.depth_for("bulk") == 1
    assert w.depth_for("serve") == 4
    release = threading.Event()
    parked = []

    def park_one(key, i):
        w.park(
            key, release.wait,
            lambda *_a: parked.append((key, i)), lambda _e: None,
        )

    # bulk's second park must BLOCK at depth 1 until the first completes
    t1 = threading.Thread(
        target=park_one, args=("bulk", 0), name="accl-test-park-0"
    )
    t1.start()
    t2 = threading.Thread(
        target=park_one, args=("bulk", 1), name="accl-test-park-1"
    )
    t2.start()
    time.sleep(0.2)
    assert w.in_flight() == 1  # the second launch is parked-blocked
    # serve still has depth 4: two parks land without blocking
    park_one("serve", 0)
    park_one("serve", 1)
    assert w.in_flight() >= 3
    release.set()
    t1.join(10)
    t2.join(10)
    assert w.drain(10)
    assert len(parked) == 4
    stats = w.stats()
    assert stats["key_depths"] == {"bulk": 1}
    w.set_key_depth("bulk", None)
    assert w.depth_for("bulk") == 4
    w.stop()


# ---------------------------------------------------------------------------
# facade: registration, config surface, telemetry, soft_reset
# ---------------------------------------------------------------------------


def _record_blocked_admissions(group, monkeypatch):
    """Who ever BLOCKS for a grant: the names of the threads that reach
    the shared arbiter's condition wait, appended as they do.  An
    admission granted inline or by its own pump never gets there, so a
    tenant whose threads are absent was never held behind another's
    grant, however slowly a shared CPU ran either."""
    arb = group[0]._arbiter
    assert all(a._arbiter is arb for a in group)
    cv_wait, blocked = arb._cv.wait, []

    def counted_wait(timeout=None):
        blocked.append(threading.current_thread().name)
        return cv_wait(timeout)

    monkeypatch.setattr(arb._cv, "wait", counted_wait, raising=False)
    return arb, blocked


def _register_all(group, cls, comm=None, name=None, **quota):
    def reg(a, r):
        a.set_tenant_class(cls, comm=comm, name=name)
        if quota:
            a.set_tenant_quota(comm=comm, **quota)

    run_parallel(group, reg)


def test_facade_registration_and_engine_mirror():
    g = emulated_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(
            g, "guaranteed", name="serve",
            window_share=2, ring_slots=4, bytes_per_s=0,
        )
        # the engine mirrors every SET_TENANT_* write
        mirror = g[0].engine.tenants[g[0].comm.id]
        assert mirror["class"] == float(TenantClass.GUARANTEED)
        assert mirror["window_share"] == 2.0
        assert mirror["ring_slots"] == 4.0
        # a bad class value is CONFIG_ERROR through the config path
        with pytest.raises(ACCLError) as ei:
            g[0]._config(ConfigFunction.SET_TENANT_CLASS, 9, key=0)
        assert ei.value.code & ErrorCode.CONFIG_ERROR
        # in-process rank handles share ONE arbiter (the board anchor
        # discipline): one registration, visible from both handles
        assert g[0]._arbiter is g[1]._arbiter
        snap = g[0]._arbiter.snapshot()
        assert snap["tenants"]["0"]["class"] == "GUARANTEED"
    finally:
        _deinit(g)


def test_facade_admission_counters_and_latency():
    g = emulated_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "guaranteed", name="serve")
        send = [
            a.create_buffer_from(np.full(64, r + 1.0, np.float32))
            for r, a in enumerate(g)
        ]
        recv = [a.create_buffer(64, np.float32) for a in g]
        for _ in range(5):
            run_parallel(
                g, lambda a, r: a.allreduce(send[r], recv[r], 64)
            )
        recv[0].sync_from_device()
        assert recv[0].data[0] == 3.0
        snap = g[0].telemetry_snapshot()
        assert snap["schema_version"] == 6
        # per-call tenant forensics: flight records carry the admitting
        # tenant (the attribution the arbiter plane documents)
        assert any(
            rec.get("tenant") == "serve"
            for rec in snap["flight_recorder"]
        ), snap["flight_recorder"][-3:]
        t = snap["tenants"]["tenants"]["0"]
        assert t["admitted"] == 10      # 5 rounds x 2 ranks
        assert t["completed"] == 10
        assert t["outstanding"] == 0    # every admission released
        assert t["latency"]["count"] == 10
        assert t["latency"]["p99_us"] is not None
        # the Prometheus surface carries the per-tenant counters AND a
        # real histogram (cumulative buckets) for histogram_quantile
        prom = g[0].telemetry_prometheus()
        assert "accl_tenant_admitted_total" in prom
        assert "accl_tenant_call_duration_us_bucket" in prom
        assert 'tenant="serve"' in prom
    finally:
        _deinit(g)


def test_tenants_route_and_index_summary():
    g = emulated_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "burst", name="jobs")
        send = [
            a.create_buffer_from(np.ones(32, np.float32)) for a in g
        ]
        recv = [a.create_buffer(32, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(send[r], recv[r], 32))
        port = g[0].start_monitor(0)
        doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tenants", timeout=10
            ).read().decode()
        )
        assert doc["enabled"] is True
        assert doc["tenants"]["0"]["class"] == "BURST"
        assert doc["tenants"]["0"]["latency"]["p99_us"] is not None
        index = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=10
        ).read().decode()
        assert "/tenants" in index
        assert "tenant jobs:" in index
    finally:
        g[0].stop_monitor()
        _deinit(g)


def test_soft_reset_clears_ledger_keeps_registration():
    g = emulated_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "guaranteed", name="serve", bytes_per_s=10**9)
        send = [
            a.create_buffer_from(np.ones(16, np.float32)) for a in g
        ]
        recv = [a.create_buffer(16, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(send[r], recv[r], 16))
        arb = g[0]._arbiter
        assert arb._decisions  # a latched decision exists
        run_parallel(g, lambda a, r: a.soft_reset())
        assert not arb._decisions          # ledger cleared with seq space
        assert arb.tenant(0) is not None   # registration survives
        # post-reset traffic re-latches from index 0 without replaying
        # pre-reset throttles
        run_parallel(g, lambda a, r: a.allreduce(send[r], recv[r], 16))
        assert (0, 0) in arb._decisions
    finally:
        _deinit(g)


def test_disarmed_facade_is_unobservable():
    """Tier-1 guard: with the arbiter disarmed (the default), the gate
    is a no-op — no tenants, no counters, identical call behavior."""
    g = emulated_group(2)
    try:
        send = [
            a.create_buffer_from(np.ones(16, np.float32)) for a in g
        ]
        recv = [a.create_buffer(16, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(send[r], recv[r], 16))
        snap = g[0].telemetry_snapshot()["tenants"]
        assert snap["enabled"] is False
        assert snap["tenants"] == {}
        assert snap["passthrough"] == 0  # disarmed: not even counted
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# gang tier: window shares + command-ring slot budgets
# ---------------------------------------------------------------------------


def test_gang_quotas_window_share_and_ring_budget():
    """Quota enforcement where contention lives on the device tier: the
    tenant's in-flight window share becomes a per-key depth override,
    and its ring slot budget clamps refill windows — counter-asserted
    against the configured quotas."""
    g = xla_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        _register_all(
            g, "best_effort", name="bulk", window_share=2, ring_slots=2,
        )
        eng = g[0].engine
        world_id = g[0].comm.id
        assert eng.gang.window.depth_for(world_id) == 2
        assert eng.gang.cmdring.slot_budget_of(world_id) == 2
        send = [
            a.create_buffer_from(np.full(32, r + 1.0, np.float32))
            for r, a in enumerate(g)
        ]
        recv = [a.create_buffer(32, np.float32) for a in g]

        def batch(a, r):
            with a.batch():
                for _ in range(6):
                    a.allreduce(send[r], recv[r], 32, run_async=True)

        for _ in range(2):  # warm, then steady
            run_parallel(g, batch, timeout=120)
        st = eng.gang.cmdring.stats()
        # 6-slot batches chunk into budget-2 windows: the configured
        # ring share IS the observed per-window occupancy bound
        assert st["max_window"] <= 2
        assert st["budgeted_windows"] >= 2
        assert st["slot_budgets"] == {str(world_id): 2}
        assert st["comm_slots"].get(str(world_id), 0) >= 12
        recv[0].sync_from_device()
        assert recv[0].data[0] == 3.0
        # admissions all charged + released (batched calls hold no slot)
        t = g[0].telemetry_snapshot()["tenants"]["tenants"][str(world_id)]
        assert t["admitted"] == 24
        assert t["outstanding"] == 0
    finally:
        _deinit(g)


def test_gang_two_tenant_ring_shares_match_quotas():
    """Two tenants on ONE gang fabric with weight-proportional ring
    budgets: each tenant's refill windows respect ITS budget — the
    per-tenant ring-slot share matches the configured split."""
    g = xla_group(2)
    try:
        for a in g:
            a.set_arbiter(True)
        subs = run_parallel(
            g, lambda a, r: a.create_communicator([0, 1])
        )
        _register_all(g, "guaranteed", name="serve", ring_slots=6)

        def reg_bulk(a, r):
            a.set_tenant_class("best_effort", comm=subs[r], name="bulk")
            a.set_tenant_quota(comm=subs[r], ring_slots=2)

        run_parallel(g, reg_bulk)
        ring = g[0].engine.gang.cmdring
        assert ring.slot_budget_of(g[0].comm.id) == 6
        assert ring.slot_budget_of(subs[0].id) == 2
        send = [
            a.create_buffer_from(np.full(32, r + 1.0, np.float32))
            for r, a in enumerate(g)
        ]
        out_g = [a.create_buffer(32, np.float32) for a in g]
        out_b = [a.create_buffer(32, np.float32) for a in g]

        def drive(a, r):
            with a.batch():
                for _ in range(6):
                    a.allreduce(send[r], out_g[r], 32, run_async=True)
            with a.batch():
                for _ in range(6):
                    a.allreduce(
                        send[r], out_b[r], 32, comm=subs[r],
                        run_async=True,
                    )

        for _ in range(2):
            run_parallel(g, drive, timeout=120)
        # per-comm window occupancy from the window log: each tenant's
        # windows bounded by ITS budget
        sizes: dict = {}
        for w in ring.window_log():
            sizes.setdefault(w["comm"], []).append(len(w["slots"]))
        assert max(sizes[g[0].comm.id]) <= 6
        assert max(sizes[subs[0].id]) <= 2
        # both tenants' traffic all executed ring-resident
        st = ring.stats()
        assert st["comm_slots"].get(str(g[0].comm.id), 0) >= 12
        assert st["comm_slots"].get(str(subs[0].id), 0) >= 12
    finally:
        _deinit(g)


# ---------------------------------------------------------------------------
# adversarial cross-tenant load (the fairness contract)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_adversarial_flooder_vs_guaranteed_p99(fault_plan, monkeypatch):
    """A BEST_EFFORT flooder plus a GUARANTEED small-message tenant on
    the same fabric under a seeded fault plan (every flooder-comm frame
    wire-delayed).  What the arbiter DECIDES is asserted, as counts and
    an order, read from the live ``/tenants`` route the monitor plane
    serves: the flooder absorbs the backpressure (its admissions queue
    at the arbiter, one in flight a rank, and only its threads ever
    block for a grant), every guaranteed call is granted in its own
    admission, and both latency histograms hold every call.  How fast a
    shared CPU ran the fabric is no part of it."""
    # 64 KiB flooder payloads ride rendezvous: a SERIALIZED delayed
    # handshake a call, so the first call of a rank is still in flight
    # when the second asks for admission
    FLOOD_CALLS = 16
    FLOOD_COUNT = 16384
    SERVE_CALLS = 40
    WINDOW_SHARE = 1

    g = emulated_group(2)
    try:
        subs = run_parallel(
            g, lambda a, r: a.create_communicator([0, 1])
        )
        plan = fault_plan(
            {
                "action": "delay", "comm": subs[0].id,
                "delay_s": 0.001, "nth": 1,
            },
            seed=1234,
        )
        g[0].engine.fabric.install_fault_plan(plan)
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "guaranteed", name="serve")

        def reg_bulk(a, r):
            a.set_tenant_class("best_effort", comm=subs[r], name="bulk")
            a.set_tenant_quota(comm=subs[r], window_share=WINDOW_SHARE)

        run_parallel(g, reg_bulk)

        arb, blocked = _record_blocked_admissions(g, monkeypatch)

        fsend = [
            a.create_buffer_from(np.ones(FLOOD_COUNT, np.float32))
            for a in g
        ]
        frecv = [a.create_buffer(FLOOD_COUNT, np.float32) for a in g]
        gsend = [
            a.create_buffer_from(np.ones(64, np.float32)) for a in g
        ]
        grecv = [a.create_buffer(64, np.float32) for a in g]
        before = {
            cid: (t["admitted"], t["latency"]["count"])
            for cid, t in arb.snapshot()["tenants"].items()
        }

        def flood(a, r):
            # offered load deeper than the share: the surplus queues AT
            # THE ARBITER (window_share=1 -> one in flight per rank),
            # which is exactly the backpressure the flooder must absorb
            reqs: list = []
            for _ in range(FLOOD_CALLS):
                reqs.append(a.allreduce(
                    fsend[r], frecv[r], FLOOD_COUNT, comm=subs[r],
                    run_async=True,
                ))
                if len(reqs) >= 2:
                    q = reqs.pop(0)
                    assert q.wait(120)
                    q.check()
            for q in reqs:
                assert q.wait(120)
                q.check()

        def serve(a, r):
            time.sleep(0.05)  # let the flood establish itself
            for _ in range(SERVE_CALLS):
                a.allreduce(gsend[r], grecv[r], 64)

        def drive(a, r):
            f = threading.Thread(
                target=flood, args=(a, r), name=f"accl-test-flood-{r}",
            )
            f.start()
            serve(a, r)
            f.join(120)
            assert not f.is_alive()

        run_parallel(g, drive, timeout=180)
        # the seeded plan really shaped the load
        inj = g[0].engine.fabric.fault_injector
        assert inj.stats()["by_action"].get("delay", 0) > 0

        # counts from the LIVE monitor surface, not local state
        port = g[0].start_monitor(0)
        doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/tenants", timeout=10
            ).read().decode()
        )
        g[0].stop_monitor()
        serve_t = doc["tenants"][str(g[0].comm.id)]
        bulk_t = doc["tenants"][str(subs[0].id)]
        # every call of both tenants was admitted, completed and timed
        # into its tenant's histogram, on each of the two ranks
        for t, calls in ((serve_t, SERVE_CALLS), (bulk_t, FLOOD_CALLS)):
            admitted0, samples0 = before[str(t["comm"])]
            assert t["admitted"] - admitted0 == 2 * calls, t
            assert t["latency"]["count"] - samples0 == 2 * calls, t
            assert t["admitted"] == t["completed"], t
            assert t["outstanding"] == 0 and t["queued"] == 0, t
            assert t["over_admissions"] == 0, t
            assert t["latency"]["p99_us"] is not None
        # backpressure absorbed at the arbiter: the flooder queued, held
        # to its share of one call in flight a rank ...
        assert bulk_t["queued_peak"] >= 1
        assert bulk_t["outstanding_limit"] == WINDOW_SHARE
        assert bulk_t["outstanding_peak"] <= WINDOW_SHARE * len(g)
        # ... and only ITS threads ever blocked for a grant: every
        # guaranteed call was granted in its own admission, never
        # behind a flooder's
        assert blocked and set(blocked) <= {
            "accl-test-flood-0", "accl-test-flood-1",
        }, set(blocked)
        assert (
            bulk_t["grant_wait_ns_total"] > serve_t["grant_wait_ns_total"]
        ), (serve_t, bulk_t)
        # SPMD uniformity: one latched record per (comm, call index) —
        # both in-process ranks replayed the same decisions
        for (comm_id, seq), dec in g[0]._arbiter._decisions.items():
            assert dec["seq"] == seq
            assert dec["class"] in ("GUARANTEED", "BEST_EFFORT")
    finally:
        _deinit(g)


def test_gang_flooder_absorbs_backpressure_serve_never_blocks(monkeypatch):
    """The fairness mechanism on the device tier, counter-asserted on a
    steady flood: with the flooder held to window_share=1, its
    per-admission grant wait dwarfs the guaranteed tenant's by an order
    of magnitude (the flooder absorbs the backpressure at the arbiter:
    it queues, one call in flight a rank, and only its threads ever
    block for a grant), while every guaranteed call is granted in its
    own admission, lands in the live histogram, and nothing
    over-admits.  The guaranteed tenant's tail is held by that ORDER
    (no guaranteed call ever waits behind a flooder's grant), not by a
    wall-clock bound: on the CPU mesh gang calls are host-bound, so a
    p99 there measures the shared CPU.  (The arbitrated-vs-unarbitrated
    wall-clock contrast is a chip-tier claim no cell of the benchmark
    measures yet.)"""
    g = xla_group(2)
    try:
        subs = run_parallel(
            g, lambda a, r: a.create_communicator([0, 1])
        )
        N = 1 << 14  # 64 KiB flooder payloads
        fs = [a.create_buffer_from(np.ones(N, np.float32)) for a in g]
        fr = [a.create_buffer(N, np.float32) for a in g]
        gs = [
            a.create_buffer_from(np.ones(64, np.float32)) for a in g
        ]
        gr = [a.create_buffer(64, np.float32) for a in g]
        # warm both program shapes BEFORE arming: the first-call XLA
        # compile must not land in either tenant's histogram
        def warm(a, r):
            a.allreduce(gs[r], gr[r], 64)
            a.allreduce(fs[r], fr[r], N, comm=subs[r])

        run_parallel(g, warm, timeout=120)
        for a in g:
            a.set_arbiter(True)
        _register_all(g, "guaranteed", name="serve")

        def reg_bulk(a, r):
            a.set_tenant_class("best_effort", comm=subs[r], name="bulk")
            a.set_tenant_quota(comm=subs[r], window_share=1)

        run_parallel(g, reg_bulk)
        arb, blocked = _record_blocked_admissions(g, monkeypatch)
        stop = threading.Event()
        # symmetric stop via publish-and-reconcile: both ranks converge
        # on the max issued call count, so no gang collective is left
        # half-posted to burn the slot watchdog at drain time
        latch = {"stop_at": None, "issued": {}}
        llock = threading.Lock()

        def flood(a, r):
            reqs: list = []

            def one(i):
                reqs.append(a.allreduce(
                    fs[r], fr[r], N, comm=subs[r], run_async=True,
                ))
                if len(reqs) > 8:
                    reqs.pop(0).wait(60)

            n = 0
            while True:
                with llock:
                    if stop.is_set() and latch["stop_at"] is None:
                        latch["stop_at"] = n
                    if (
                        latch["stop_at"] is not None
                        and n >= latch["stop_at"]
                    ):
                        break
                one(n)
                n += 1
            with llock:
                latch["issued"][r] = n
            deadline = time.monotonic() + 30.0
            target = n
            while time.monotonic() < deadline:
                with llock:
                    if len(latch["issued"]) == 2:
                        target = max(latch["issued"].values())
                        break
                time.sleep(0.005)
            while n < target:
                one(n)
                n += 1
            for q in reqs:
                assert q.wait(60)

        def serve(a, r):
            time.sleep(0.3)  # let the flood reach steady state
            for _ in range(40):
                a.allreduce(gs[r], gr[r], 64)
            stop.set()

        def drive(a, r):
            f = threading.Thread(
                target=flood, args=(a, r), name=f"accl-test-gflood-{r}",
            )
            f.start()
            serve(a, r)
            f.join(120)
            assert not f.is_alive()

        run_parallel(g, drive, timeout=300)
        snap = g[0].telemetry_snapshot()["tenants"]["tenants"]
        serve_t = snap[str(g[0].comm.id)]
        bulk_t = snap[str(subs[0].id)]
        # both tenants really ran, nothing over-admitted or leaked
        assert serve_t["admitted"] == 80 and serve_t["outstanding"] == 0
        assert bulk_t["admitted"] > 0 and bulk_t["outstanding"] == 0
        assert serve_t["over_admissions"] == 0
        assert bulk_t["over_admissions"] == 0
        # the flooder absorbed the backpressure: per-admission grant
        # wait an order of magnitude above the guaranteed tenant's
        g_wait = serve_t["grant_wait_ns_total"] / serve_t["admitted"]
        f_wait = bulk_t["grant_wait_ns_total"] / bulk_t["admitted"]
        assert f_wait > 10 * g_wait, (g_wait, f_wait)
        # ... held to its share of one call in flight a rank, its
        # surplus queued at the arbiter ...
        assert bulk_t["queued_peak"] >= 1
        assert bulk_t["outstanding_limit"] == 1
        assert bulk_t["outstanding_peak"] <= 1 * len(g)
        # ... and only ITS threads ever blocked for a grant: each of
        # the 80 guaranteed calls was granted in its own admission,
        # never behind a flooder's.  That order is the guaranteed
        # tenant's tail guarantee (a wall-clock p99 on the CPU mesh
        # would measure the shared CPU instead)
        assert blocked and set(blocked) <= {
            "accl-test-gflood-0", "accl-test-gflood-1",
        }, set(blocked)
        # and every guaranteed call was timed into the live histogram
        assert serve_t["latency"]["count"] == 80
        assert serve_t["latency"]["p99_us"] is not None
    finally:
        _deinit(g)

@pytest.mark.chaos
def test_adversarial_determinism_same_seed_same_decisions():
    """Same seeded fault plan + same call sequence -> identical
    admission ledgers (class + throttle per call index), twice, from
    fresh groups — the latched-decision half of determinism."""

    def run_once():
        g = emulated_group(2)
        try:
            for a in g:
                a.set_arbiter(True)
            _register_all(
                g, "guaranteed", name="serve", bytes_per_s=512 * 1024,
            )
            send = [
                a.create_buffer_from(np.ones(256, np.float32)) for a in g
            ]
            recv = [a.create_buffer(256, np.float32) for a in g]
            for _ in range(6):
                run_parallel(
                    g, lambda a, r: a.allreduce(send[r], recv[r], 256)
                )
            ledger = {
                k: (v["class"], v["throttle_ns"] > 0)
                for k, v in g[0]._arbiter._decisions.items()
            }
            return ledger
        finally:
            _deinit(g)

    assert run_once() == run_once()


# ---------------------------------------------------------------------------
# acclint: decision accessors sanitize; raw tenant-class branches flag
# ---------------------------------------------------------------------------


def _seq_findings(tmp_path, code):
    import textwrap

    from accl_tpu.analysis import run_checks

    p = tmp_path / "scenario.py"
    p.write_text(textwrap.dedent(code))
    return [
        f for f in run_checks([str(p)], ["collective-sequence"])
        if not f.suppressed
    ]


def test_acclint_flags_raw_tenant_class_branch(tmp_path):
    """A collective branched on a locally-read tenant class is exactly
    the divergence bug the latched decision exists to prevent — the
    known-bad fixture still flags."""
    findings = _seq_findings(tmp_path, """
    def work(accl, comm):
        tenant_class = accl.capabilities()["tenant_class"]
        if tenant_class == 2:
            accl.allreduce(a, b, 64, comm=comm)
    """)
    assert findings, "raw tenant-class branch must flag"
    assert any("collective-sequence" == f.check for f in findings)


def test_acclint_admit_decision_sanitizes(tmp_path):
    """The arbiter's latched decision accessor is SPMD-uniform by
    construction (the DemotionLedger discipline): branching on the
    admitted record passes the sanitizer list."""
    findings = _seq_findings(tmp_path, """
    def work(accl, arbiter, comm, seq):
        d = arbiter.admit(comm.id, seq, 64)
        if d is not None and d["class"] == "BEST_EFFORT":
            accl.allreduce(a, b, 64, comm=comm)
        else:
            accl.allreduce(a, b, 64, comm=comm)
    """)
    assert not findings, [f.message for f in findings]


def test_arbiter_module_is_jax_free():
    """The arbiter joins the jax-free closure (acclint enforces the
    static half; this is the runtime proof for THIS module)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import accl_tpu.arbiter\n"
        "assert 'jax' not in sys.modules, 'arbiter pulled jax'\n"
        "print('ok')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "ok" in out.stdout


# ---------------------------------------------------------------------------
# cross-process tenant registry (the KV-plane ledger)
# ---------------------------------------------------------------------------


class _FakeKV:
    """Dict-backed stand-in for the compat-wrapped jax KV client: the
    three calls kv_tenant_exchange needs, shared across "processes" the
    way the dist tier's KV service is."""

    def __init__(self):
        self.store: dict = {}
        self.ctrs: dict = {}
        self.lock = threading.Lock()

    def key_value_set_bytes(self, key, value):
        with self.lock:
            self.store[key] = bytes(value)

    def key_value_try_get_bytes(self, key):
        with self.lock:
            return self.store.get(key)

    def key_value_increment(self, key, amount):
        with self.lock:
            self.ctrs[key] = self.ctrs.get(key, 0) + int(amount)
            return self.ctrs[key]


def test_kv_tenant_exchange_rendezvous_and_sweep():
    from accl_tpu.contract import kv_tenant_exchange

    kv = _FakeKV()
    st_a: dict = {}
    st_b: dict = {}
    fa, out_a = kv_tenant_exchange(kv, "A", {"serve": 8}, st_a)
    # first claimer: dense slot 0, posts, sees nobody
    assert st_a["slot"] == 0
    assert out_a == {"posted": 1, "peers": 0, "errors": 0}
    assert fa == {}
    fb, out_b = kv_tenant_exchange(kv, "B", {"bulk": 1, "logs": 2}, st_b)
    assert st_b["slot"] == 1
    assert out_b["posted"] == 1 and out_b["peers"] == 1
    assert fb["A"] == {"weights": {"serve": 8}, "total": 8}
    # warm exchange: unchanged table is NOT re-posted, sweep still runs
    fa2, out_a2 = kv_tenant_exchange(kv, "A", {"serve": 8}, st_a)
    assert out_a2["posted"] == 0 and out_a2["peers"] == 1
    assert fa2["B"]["total"] == 3
    # changed table re-posts
    _, out_a3 = kv_tenant_exchange(kv, "A", {"serve": 4}, st_a)
    assert out_a3["posted"] == 1
    fb2, _ = kv_tenant_exchange(kv, "B", {"bulk": 1, "logs": 2}, st_b)
    assert fb2["A"]["total"] == 4


def test_kv_tenant_exchange_skips_stale_self_and_gaps():
    from accl_tpu.contract import kv_tenant_exchange

    kv = _FakeKV()
    # a restarted process re-claims a fresh slot; its old slot still
    # carries the same process key and must not count as a peer
    st_old: dict = {}
    kv_tenant_exchange(kv, "A", {"serve": 8}, st_old)
    # a peer claims slot 1 but never posts (crashed mid-rendezvous)
    kv.key_value_increment("accl/arb/slots", 1)
    st_new: dict = {}
    f, out = kv_tenant_exchange(kv, "A", {"serve": 8}, st_new)
    assert st_new["slot"] == 2
    assert f == {} and out["peers"] == 0
    # D posts above A; A's sweep must skip the unposted gap at slot 1
    # (below its own slot → a lagging claimant, not the frontier) and
    # still reach D, while the stale slot-0 self stays excluded
    st_d: dict = {}
    kv_tenant_exchange(kv, "D", {"bulk": 1}, st_d)
    assert st_d["slot"] == 3
    f2, out2 = kv_tenant_exchange(kv, "A", {"serve": 8}, st_new)
    assert "D" in f2 and f2["D"]["total"] == 1
    assert out2["peers"] == 1  # D only: gap skipped, stale self skipped


def test_ledger_fabric_shares_adversarial_pair_soak():
    """Two per-process arbiters sharing one KV plane: a GUARANTEED(8)
    serving tenant in one process and a BEST_EFFORT(1) bulk flooder in
    the other converge to ~8:1 fabric-share rates, hold the split
    across repeated exchanges, and re-derive when weights churn."""
    from accl_tpu.arbiter import TenantLedger

    kv = _FakeKV()
    serve_arb = QosArbiter()
    bulk_arb = QosArbiter()
    serve_arb.register(1, name="serving", cls=TenantClass.GUARANTEED,
                       weight=8)
    bulk_arb.register(2, name="bulk", cls=TenantClass.BEST_EFFORT,
                      weight=1)
    serve_arb.attach_ledger(TenantLedger("proc-serve",
                                         fabric_bytes_s=9e9))
    bulk_arb.attach_ledger(TenantLedger("proc-bulk", fabric_bytes_s=9e9))

    # before any peer is visible: no auto cap (nothing to share with)
    serve_arb.ledger_exchange(kv)
    assert serve_arb.tenant(1).bucket is None
    # priming round: bulk posts and sees serve; serve's NEXT exchange
    # sees bulk — the registry is eventually consistent by design
    bulk_arb.ledger_exchange(kv)

    # soak: interleaved exchanges, rates must settle and STAY at the
    # 8:1 split of the modeled fabric
    for _ in range(20):
        serve_arb.ledger_exchange(kv)
        bulk_arb.ledger_exchange(kv)
        ts, tb = serve_arb.tenant(1), bulk_arb.tenant(2)
        assert ts.bucket is not None and ts.auto_rate
        assert tb.bucket is not None and tb.auto_rate
        assert ts.bucket.rate == pytest.approx(8e9, rel=1e-6)
        assert tb.bucket.rate == pytest.approx(1e9, rel=1e-6)

    # the derived cap actually paces: the bulk flooder owes throttle
    # time at its 1e9 B/s share while the serving tenant's 8e9 share
    # absorbs the same burst untouched
    owed_bulk = bulk_arb.tenant(2).bucket.throttle_ns(int(4e9))
    owed_serve = serve_arb.tenant(1).bucket.throttle_ns(int(4e9))
    assert owed_bulk > owed_serve

    # weight churn re-derives: serving drops to weight 1 → even split
    serve_arb.register(1, name="serving", cls=TenantClass.GUARANTEED,
                       weight=1)
    serve_arb.ledger_exchange(kv)
    bulk_arb.ledger_exchange(kv)
    serve_arb.ledger_exchange(kv)
    assert serve_arb.tenant(1).bucket.rate == pytest.approx(
        4.5e9, rel=1e-6
    )
    assert bulk_arb.tenant(2).bucket.rate == pytest.approx(
        4.5e9, rel=1e-6
    )

    # an explicit operator rate is never overwritten by the ledger
    bulk_arb.set_quota(2, bytes_per_s=123.0)
    bulk_arb.ledger_exchange(kv)
    assert bulk_arb.tenant(2).bucket.rate == pytest.approx(123.0)
    assert not bulk_arb.tenant(2).auto_rate

    # telemetry: the ledger rides the snapshot
    snap = serve_arb.snapshot()
    assert snap["ledger"]["process"] == "proc-serve"
    assert snap["ledger"]["peers"] == 1
    assert snap["ledger"]["exchanges"] >= 20


def test_ledger_env_arming_and_facade_exchange(monkeypatch):
    """ACCL_ARBITER_LEDGER arms the registry only on tiers whose engine
    exposes a KV plane; the emulator has none, so the facade stays
    local-only and the public exchange is a clean no-op."""
    from accl_tpu.arbiter import env_ledger

    assert not env_ledger({})
    assert env_ledger({"ACCL_ARBITER_LEDGER": "1"})
    assert not env_ledger({"ACCL_ARBITER_LEDGER": "0"})

    monkeypatch.setenv("ACCL_ARBITER_LEDGER", "1")
    group = emulated_group(2)
    try:
        for a in group:
            assert a._arbiter.ledger is None
            assert a.arbiter_ledger_exchange() is None
    finally:
        _deinit(group)
    # the dist tier's engine DOES expose the plane the facade arms on
    from accl_tpu.backends.dist.engine import DistEngine

    assert hasattr(DistEngine, "arbiter_kv")
