"""Single-interaction dispatch contract (the facade's hostctrl discipline).

The reference issues ONE hostctrl command per collective
(kernels/plugins/hostctrl/hostctrl.cpp:22-63); every extra device
interaction the facade performs is one more host dispatch with the
device idle until it lands.  These
tests pin the TPU-tier analog via the engines' ``device_interactions``
counter (``ACCL.capabilities()``):

* one warm facade collective on the XLA gang fast path = EXACTLY 1
  device interaction (operand staging fused into the program, result
  adopted by pointer swap);
* a batched command queue of N collectives flushes as EXACTLY 1;
* result-side work that does need a program (width-slack adoption) is
  LAZY — deferred past dispatch, materialized on wait().

Runs on the 8-device virtual CPU mesh — no chip needed.
"""

import numpy as np
import pytest

from helpers import run_parallel

from accl_tpu.buffer import DeviceBuffer
from accl_tpu.core import emulated_group, xla_group
from accl_tpu.request import CommandQueue


@pytest.fixture(scope="module")
def g4():
    g = xla_group(4)
    yield g
    for a in g:
        a.deinit()


def _interactions(a) -> int:
    caps = a.capabilities()
    assert isinstance(caps["device_interactions"], int)
    return caps["device_interactions"]


# ---------------------------------------------------------------------------
# one collective == one device interaction
# ---------------------------------------------------------------------------


def test_warm_allreduce_is_one_interaction(g4):
    n = 64
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]
    assert all(isinstance(b, DeviceBuffer) for b in send + recv)

    def work(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(g4, work)  # cold call: compiles, counts once too
    ic0 = _interactions(g4[0])
    run_parallel(g4, work)
    assert _interactions(g4[0]) - ic0 == 1, (
        "one warm gang collective must be exactly one device interaction"
    )
    for r in range(4):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)


@pytest.mark.parametrize("compress", [None, np.float16])
def test_compressed_collective_stays_single_interaction(g4, compress):
    """The wire-compression lanes run INSIDE the collective program (no
    separate cast dispatch), compressed or not."""
    n = 32
    send = [
        a.create_buffer_from(np.linspace(0, r + 1, n).astype(np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.allreduce(send[r], recv[r], n, compress_dtype=compress)

    run_parallel(g4, work)
    ic0 = _interactions(g4[0])
    run_parallel(g4, work)
    assert _interactions(g4[0]) - ic0 == 1


def test_width_slack_operand_fused_into_program(g4):
    """Operands wider than the call count: the slice runs inside the
    collective program (prep fusion), not as a per-rank staging
    dispatch — the call is still one interaction at dispatch time."""
    n, width = 48, 64
    send = []
    for r, a in enumerate(g4):
        b = a.create_buffer(width, np.float32)
        b.data[:] = float(r + 1)
        b.sync_to_device()
        send.append(b)
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(g4, work)
    ic0 = _interactions(g4[0])
    run_parallel(g4, work)
    assert _interactions(g4[0]) - ic0 == 1
    for r in range(4):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)


def test_lazy_result_adoption_defers_writeback(g4):
    """A result buffer WIDER than the output needs a writeback program.
    That program must not run at dispatch (fire-and-forget pays one
    interaction only); it materializes on wait()/data access."""
    n, res_width = 32, 64
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(res_width, np.float32) for a in g4]

    def work_sync(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(g4, work_sync)  # warm (compiles program + writebacks)

    reqs = [None] * 4

    def work_async(a, r):
        reqs[r] = a.allreduce(send[r], recv[r], n, run_async=True)

    ic0 = _interactions(g4[0])
    run_parallel(g4, work_async)
    # completion without materialization: poll the raw done event (NOT
    # test()/wait(), which would trigger the deferred adoption)
    for req in reqs:
        assert req._done.wait(30)
    assert _interactions(g4[0]) - ic0 == 1, (
        "fire-and-forget must pay only the dispatch interaction"
    )
    for req in reqs:
        assert req.wait(30)
        req.check()
    # each rank's deferred writeback ran exactly once at wait()
    assert _interactions(g4[0]) - ic0 == 1 + 4
    for r in range(4):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data[:n], 10.0)


# ---------------------------------------------------------------------------
# batched command queue: N queued calls flush as ONE interaction
# ---------------------------------------------------------------------------


def test_batch_of_n_flushes_as_one_interaction(g4):
    n = 16
    world = len(g4)
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    ar = [a.create_buffer(n, np.float32) for a in g4]
    ag = [a.create_buffer(world * n, np.float32) for a in g4]
    rs = [a.create_buffer(n, np.float32) for a in g4]
    rs_send = [
        a.create_buffer_from(
            np.full(world * n, float(r + 1), np.float32)
        )
        for r, a in enumerate(g4)
    ]

    def work(a, r):
        with a.batch():
            r1 = a.allreduce(send[r], ar[r], n, run_async=True)
            r2 = a.allgather(send[r], ag[r], n, run_async=True)
            r3 = a.reduce_scatter(rs_send[r], rs[r], n, run_async=True)
        for req in (r1, r2, r3):
            assert req.wait(60)
            req.check()

    run_parallel(g4, work)  # cold: compiles the fused batch program
    ic0 = _interactions(g4[0])
    run_parallel(g4, work)
    assert _interactions(g4[0]) - ic0 == 1, (
        "a flushed batch of 3 collectives must be one device interaction"
    )
    for r in range(4):
        ar[r].sync_from_device()
        np.testing.assert_allclose(ar[r].data, 10.0)
        ag[r].sync_from_device()
        np.testing.assert_allclose(
            ag[r].data.reshape(world, n),
            np.broadcast_to(
                np.arange(1.0, 5.0, dtype=np.float32)[:, None], (world, n)
            ),
        )
        rs[r].sync_from_device()
        np.testing.assert_allclose(rs[r].data, 10.0)


def test_batch_auto_flushes_on_wait(g4):
    """Waiting on a queued request flushes the open batch (no explicit
    flush() needed) — the auto-flush contract."""
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.begin_batch()
        try:
            req = a.allreduce(send[r], recv[r], n, run_async=True)
            assert req.wait(60)  # must flush, not deadlock
            req.check()
        finally:
            a.end_batch()

    run_parallel(g4, work)
    for r in range(4):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)


def test_batch_sync_call_flushes_and_completes(g4):
    """A sync (non-async) call inside an open batch flushes the queued
    run and returns completed — callers never stall on their own queue."""
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    r1v = [a.create_buffer(n, np.float32) for a in g4]
    r2v = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.begin_batch()
        try:
            q = a.allreduce(send[r], r1v[r], n, run_async=True)
            a.allreduce(send[r], r2v[r], n)  # sync: flushes both
            assert q.test()
            q.check()
        finally:
            a.end_batch()

    run_parallel(g4, work)
    for r in range(4):
        r1v[r].sync_from_device()
        r2v[r].sync_from_device()
        np.testing.assert_allclose(r1v[r].data, 10.0)
        np.testing.assert_allclose(r2v[r].data, 10.0)


def test_command_queue_drain():
    q = CommandQueue()
    for i in range(5):
        q.push(i)
    assert q.drain() == [0, 1, 2, 3, 4]
    assert len(q) == 0
    assert q.drain() == []


# ---------------------------------------------------------------------------
# counter surface
# ---------------------------------------------------------------------------


def test_capabilities_counter_absent_on_device_free_tier():
    g = emulated_group(2)
    try:
        caps = g[0].capabilities()
        assert caps["device_interactions"] is None
    finally:
        for a in g:
            a.deinit()


def test_gang_dump_rx_buffers_reports_parked_state(g4):
    """The gang tier's rx dump (satellite of the chip-soak leak check):
    a parked unmatched recv shows as a non-IDLE ``rxbuf`` line; a clean
    engine emits none."""
    clean = g4[0].dump_rx_buffers()
    assert "rxbuf" not in clean

    n = 8
    dst = g4[2].create_buffer(n, np.float32)
    req = g4[2].recv(dst, n, src=1, tag=991, run_async=True)
    try:
        dump = g4[2].dump_rx_buffers()
        assert "rxbuf p2p-RECV" in dump and "IDLE" not in dump.split(
            "\n", 1
        )[1]
    finally:
        src = g4[1].create_buffer_from(np.arange(n, dtype=np.float32))
        g4[1].send(src, n, dst=2, tag=991)
        assert req.wait(30)
        req.check()
    assert "rxbuf" not in g4[2].dump_rx_buffers()
    dst.sync_from_device()
    np.testing.assert_array_equal(dst.data, np.arange(n, dtype=np.float32))


# ---------------------------------------------------------------------------
# plan-cache counters (cached per-call dispatch plans, accl_tpu.plans)
# ---------------------------------------------------------------------------


def _plan_stats(a) -> dict:
    pc = a.capabilities()["plan_cache"]
    assert isinstance(pc["hits"], int) and isinstance(pc["misses"], int)
    return pc


#: the module's group (``g4``)
W = 4


def _rank_data(r, elems):
    return r * 1000.0 + np.arange(elems, dtype=np.float32)


def _all(elems):
    return [_rank_data(q, elems) for q in range(W)]


#: op -> (send and recv elements in units of the call's ``count`` n, the
#: call, the result on rank r or None where the rank receives nothing);
#: rank r's send buffer holds ``_rank_data(r, .)``
_WARM_OPS = {
    "allreduce": (
        1, 1, lambda a, s, d, n: a.allreduce(s, d, n),
        lambda r, n: sum(_all(n)),
    ),
    "allgather": (
        1, W, lambda a, s, d, n: a.allgather(s, d, n),
        lambda r, n: np.concatenate(_all(n)),
    ),
    "reduce_scatter": (
        W, 1, lambda a, s, d, n: a.reduce_scatter(s, d, n),
        lambda r, n: sum(_all(n * W))[r * n:(r + 1) * n],
    ),
    "alltoall": (
        W, W, lambda a, s, d, n: a.alltoall(s, d, n),
        lambda r, n: np.concatenate(
            [x[r * n:(r + 1) * n] for x in _all(n * W)]
        ),
    ),
    "bcast": (
        1, 0, lambda a, s, d, n: a.bcast(s, n, root=1),
        lambda r, n: _rank_data(1, n),
    ),
    "scatter": (
        W, 1, lambda a, s, d, n: a.scatter(s, d, n, root=1),
        lambda r, n: _rank_data(1, n * W)[r * n:(r + 1) * n],
    ),
    "gather": (
        1, W, lambda a, s, d, n: a.gather(s, d, n, root=1),
        lambda r, n: np.concatenate(_all(n)) if r == 1 else None,
    ),
    "reduce": (
        1, 1, lambda a, s, d, n: a.reduce(s, d, n, root=1),
        lambda r, n: sum(_all(n)) if r == 1 else None,
    ),
    "barrier": (0, 0, lambda a, s, d, n: a.barrier(), lambda r, n: None),
}


@pytest.mark.parametrize("op", list(_WARM_OPS))
def test_warm_collective_is_one_interaction_and_plan_hit(g4, op):
    """The cached-dispatch contract, counter-asserted both ways, for
    each of the facade's nine collectives: a warm gang collective is
    EXACTLY 1 device interaction AND >= 1 plan-cache hit (zero misses)
    — pool-lookup -> dispatch, nothing re-derived.  The barrier is the
    one whose counts are by design other ones."""
    n = 64
    send_x, recv_x, call, want = _WARM_OPS[op]
    send = [
        a.create_buffer_from(_rank_data(r, n * send_x)) if send_x else None
        for r, a in enumerate(g4)
    ]
    recv = [
        a.create_buffer(n * recv_x, np.float32) if recv_x else None
        for a in g4
    ]

    def work(a, r):
        call(a, send[r], recv[r], n)

    run_parallel(g4, work)  # cold: builds the plan (miss) + template
    run_parallel(g4, work)  # first hit: prepares the program handle
    ic0 = _interactions(g4[0])
    pc0 = _plan_stats(g4[0])
    run_parallel(g4, work)
    pc1 = _plan_stats(g4[0])
    if op == "barrier":
        # by design 0 and 0: on this tier the gang's assembly IS the
        # barrier (engine ``_run_op``), no program is dispatched, and a
        # call without a payload builds its options itself, so the
        # pool is never asked
        assert _interactions(g4[0]) - ic0 == 0
        assert pc1["hits"] == pc0["hits"]
    else:
        assert _interactions(g4[0]) - ic0 == 1
        assert pc1["hits"] - pc0["hits"] >= 1, "warm call must hit the pool"
    assert pc1["misses"] == pc0["misses"], "warm call must not re-plan"
    for r in range(W):
        out = send[r] if op == "bcast" else recv[r]
        expect = want(r, n)
        if expect is not None:
            out.sync_from_device()
            np.testing.assert_array_equal(out.data, expect)


def test_set_tuning_forces_exactly_one_replan(g4):
    """A register write invalidates the pool: the NEXT call re-plans
    (exactly one miss), the one after hits again."""
    n = 32
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(g4, work)
    run_parallel(g4, work)
    for a in g4:  # a write of the DEFAULT value still invalidates
        a.set_tuning("ring_segments", 1)
    pc0 = _plan_stats(g4[0])
    assert pc0["size"] == 0 and pc0["last_invalidation"] == "set_tuning"
    run_parallel(g4, work)
    pc1 = _plan_stats(g4[0])
    assert pc1["misses"] - pc0["misses"] == 1, "exactly one re-plan"
    run_parallel(g4, work)
    pc2 = _plan_stats(g4[0])
    assert pc2["misses"] == pc1["misses"]
    assert pc2["hits"] - pc1["hits"] >= 1


def test_soft_reset_forces_exactly_one_replan(g4):
    """soft_reset is a full flush: pool cleared AND communicator epochs
    bumped, so a stale plan can neither be served nor re-keyed."""
    n = 32
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        a.allreduce(send[r], recv[r], n)

    run_parallel(g4, work)
    run_parallel(g4, work)
    epoch0 = g4[0].comm.epoch
    for a in g4:  # collective by contract: every rank, nothing in flight
        a.soft_reset()
    assert g4[0].comm.epoch != epoch0, "soft_reset must re-epoch comms"
    pc0 = _plan_stats(g4[0])
    assert pc0["size"] == 0
    run_parallel(g4, work)
    pc1 = _plan_stats(g4[0])
    assert pc1["misses"] - pc0["misses"] == 1, "exactly one re-plan"
    run_parallel(g4, work)
    assert _plan_stats(g4[0])["misses"] == pc1["misses"]
    for r in range(4):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)


def test_subcomm_epoch_churn_never_reuses_stale_plan(g4):
    """The PR 2 seqn-epoch lesson applied to plans: a re-created
    same-membership subcommunicator reuses the deterministic comm id but
    carries a fresh epoch, so the first collective on the NEW instance
    must re-plan (one miss), never serve the old instance's plan."""
    n = 16
    sub = [a.create_communicator([0, 1]) for a in g4]
    assert sub[2] is None and sub[3] is None
    assert sub[0].id == sub[1].id

    def work(comms):
        send = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g4[:2])
        ]
        recv = [a.create_buffer(n, np.float32) for a in g4[:2]]

        def body(a, r):
            a.allreduce(send[r], recv[r], n, comm=comms[r])

        run_parallel(g4[:2], body)
        for r in range(2):
            recv[r].sync_from_device()
            np.testing.assert_allclose(recv[r].data, 3.0)

    work(sub)   # plan built for (comm id, epoch A)
    pc0 = _plan_stats(g4[0])
    work(sub)   # same instance: hit
    pc1 = _plan_stats(g4[0])
    assert pc1["hits"] - pc0["hits"] >= 1
    assert pc1["misses"] == pc0["misses"]

    sub2 = [a.create_communicator([0, 1]) for a in g4]
    assert sub2[0].id == sub[0].id, "deterministic id must be reused"
    assert sub2[0].epoch != sub[0].epoch
    pc2 = _plan_stats(g4[0])
    work(sub2)  # new instance: MUST re-plan
    pc3 = _plan_stats(g4[0])
    assert pc3["misses"] - pc2["misses"] == 1, (
        "a re-created same-id subcomm must never reuse the stale plan"
    )


# ---------------------------------------------------------------------------
# overlap plane: the async in-flight window (accl_tpu.overlap)
# ---------------------------------------------------------------------------


def test_back_to_back_window_overlaps_on_emulated_clock():
    """wall < N x the single-call wall, on an emulated clock where the
    comparison is deterministic: the 'device' executes each launched
    call TICK seconds after its launch (a timer thread — async like the
    real device), the host dispatch floor is FLOOR seconds of launch-
    path work.  Serialized discipline pays N x (FLOOR + TICK); the
    window pays ~N x FLOOR + TICK because every launch past the first
    overlaps its predecessors' device time.  Completions must arrive in
    launch order.  (The live-engine variant below asserts the same
    contract structurally — wall-clock comparisons on a shared CPU host
    are noise, the emulated clock is where the timing claim is pinned.)"""
    import threading
    import time

    from accl_tpu.overlap import InflightWindow

    TICK, FLOOR, N = 0.05, 0.01, 6
    single = FLOOR + TICK  # serialized: launch, then block on device

    win = InflightWindow(depth=4)
    done_order = []

    def launch(k):
        time.sleep(FLOOR)  # the host dispatch floor (launch-path work)
        ev = threading.Event()
        timer = threading.Timer(TICK, ev.set)  # the async device
        timer.start()
        win.park(
            "comm0",
            lambda: ev.wait(10),
            lambda overlap_ns, depth, ready_ns, k=k: done_order.append(k),
            lambda exc, k=k: done_order.append(("err", k)),
        )

    t0 = time.perf_counter()
    for k in range(N):
        launch(k)
    assert win.drain(10)
    wall = time.perf_counter() - t0
    assert wall < N * single, (
        f"no overlap on the emulated clock: {N} windowed calls took "
        f"{wall * 1e3:.0f} ms vs {N} x {single * 1e3:.0f} ms serialized"
    )
    assert done_order == list(range(N)), done_order
    stats = win.stats()
    assert stats["completed"] == N and stats["failed"] == 0
    assert stats["max_depth_seen"] >= 2, stats
    assert stats["in_flight"] == 0
    win.stop()


def test_drain_key_fences_inline_completions():
    """``drain_key`` is the per-communicator ordering fence behind
    inline (host-path) completions in ``_execute_calls``: it blocks
    until the key's parked entries completed, returns False past its
    bound (a wedged device call must not wedge the fence), leaves OTHER
    keys alone, and is a no-op on the key's own drainer thread (a
    completion callback re-entering the engine must not wait on
    itself).  ``drain_deadline_s`` is the one policy every drain point
    shares."""
    import threading
    import time

    from accl_tpu.overlap import InflightWindow, drain_deadline_s

    assert drain_deadline_s(30.0) == 120.0
    assert drain_deadline_s(1.0) == 60.0  # the floor

    win = InflightWindow(depth=4)
    gate = threading.Event()
    facts = {}

    def on_ready(*_f):
        # runs on the drainer thread while the entry is still counted:
        # without the re-entry guard this would block its full bound
        t0 = time.perf_counter()
        facts["reentrant"] = win.drain_key("a", 5.0)
        facts["reentrant_s"] = time.perf_counter() - t0

    win.park(
        "a", lambda: gate.wait(10), on_ready,
        lambda exc: facts.setdefault("err", exc),
    )
    assert win.drain_key("b", 0.5)  # other keys are not fenced
    assert not win.drain_key("a", 0.2)  # bounded: wedged entry times out
    gate.set()
    assert win.drain_key("a", 5.0)  # the fence: entry completed first
    assert facts["reentrant"] is True
    assert facts["reentrant_s"] < 1.0, facts
    assert "err" not in facts
    win.stop()


def test_back_to_back_window_overlaps(g4):
    """The live-engine overlap contract, asserted structurally (the
    timing claim lives on the emulated clock above): a window of N
    back-to-back run_async collectives genuinely reaches in-flight
    depth >= 2 (a later launch RETURNED while an earlier call was still
    executing — launch decoupled from completion), completions arrive
    in launch order per rank (the seqn ordering the gang's SPMD
    contract requires), results are bit-correct, and the flight
    recorder carries the overlap facts."""
    N = 6
    # big enough that device execution outlasts the inter-launch gap —
    # depth >= 2 needs launch k+1 to park before call k's done-probe
    # fires, so the device must still be busy when the gang reassembles
    n = 1 << 20
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    run_parallel(g4, lambda a, r: a.allreduce(send[r], recv[r], n))

    order = {r: [] for r in range(len(g4))}

    def burst(a, r):
        reqs = []
        for k in range(N):
            q = a.allreduce(send[r], recv[r], n, run_async=True)
            q.add_done_callback(lambda k=k, r=r: order[r].append(k))
            reqs.append(q)
        for q in reqs:
            assert q.wait(60)
            q.check()

    # max_depth_seen is cumulative, so one genuinely-overlapped burst
    # satisfies it; retry a couple of times in case a loaded host let
    # the drainer win every race in a round
    for _ in range(3):
        for r in order:
            order[r].clear()
        run_parallel(g4, burst)
        stats = g4[0].engine.telemetry_report()["inflight"]
        if stats["max_depth_seen"] >= 2:
            break
    assert stats["max_depth_seen"] >= 2, stats
    assert stats["in_flight"] == 0  # all waits returned: window empty
    assert stats["completed"] == stats["launched"]  # no lost completions
    for r in range(len(g4)):
        assert order[r] == sorted(order[r]), (
            f"rank {r} completions misordered: {order[r]}"
        )
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)
    # the flight recorder carries the overlap facts for windowed calls
    recs = [
        rec for rec in g4[0].telemetry_snapshot()["flight_recorder"]
        if rec["op"] == "allreduce" and rec.get("inflight_depth")
    ]
    assert recs and any(rec["inflight_depth"] >= 2 for rec in recs)


def test_drain_points_actually_drain(g4):
    """flush(), a config write, and soft_reset each leave the window
    EMPTY with every launched request completed — no lost completions."""
    n = 4096
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    recv = [a.create_buffer(n, np.float32) for a in g4]

    def burst(a, r):
        return [
            a.allreduce(send[r], recv[r], n, run_async=True)
            for _ in range(4)
        ]

    # flush() is the explicit drain point
    reqs_per = run_parallel(g4, burst)
    g4[0].flush()
    assert g4[0].engine.telemetry_report()["inflight"]["in_flight"] == 0
    for reqs in reqs_per:
        for q in reqs:
            assert q.done()
            q.check()

    # a config write drains before it applies (here: the window knob
    # itself, re-written at its default depth so the shared fixture's
    # behavior is unchanged)
    reqs_per = run_parallel(g4, burst)
    g4[0].set_inflight_window(4)
    for reqs in reqs_per:
        for q in reqs:
            assert q.done()
            q.check()

    # soft_reset FULLY drains: every in-flight request completes OK
    # before the gang state is abandoned
    reqs_per = run_parallel(g4, burst)
    for a in g4:
        a.soft_reset()
    assert g4[0].engine.telemetry_report()["inflight"]["in_flight"] == 0
    for reqs in reqs_per:
        for q in reqs:
            assert q.done()
            q.check()
    # and the engine still serves afterwards
    run_parallel(g4, lambda a, r: a.allreduce(send[r], recv[r], n))
    for r in range(len(g4)):
        recv[r].sync_from_device()
        np.testing.assert_allclose(recv[r].data, 10.0)


def test_invalid_inflight_window_rejected(g4):
    from accl_tpu.constants import ACCLError

    with pytest.raises(ACCLError):
        g4[0].set_inflight_window(0)
    assert g4[0].capabilities()["inflight_window"] == 4


def test_mid_window_fault_fails_only_the_faulted_channel(fault_plan):
    """A fault mid-window (3rd eager message on the 1→0 channel dropped,
    no retransmit) fails the matching request with RECEIVE_TIMEOUT and
    the flight-recorder tail attached.  Transfers BEFORE the hole
    complete bit-correct; transfers after it on the SAME seqn-ordered
    channel fail too — completing them would reorder past the hole, the
    exact misordering the seqn contract forbids — but every one of them
    COMPLETES (fails fast, never hangs: no lost completions).  The
    untouched 0→1 channel delivers bit-correct throughout, and
    soft_reset recovers the faulted link."""
    from accl_tpu.constants import ACCLError, ErrorCode
    from accl_tpu.core import emulated_group

    g = emulated_group(2)
    a, b = g
    try:
        a.engine.fabric.install_fault_plan(fault_plan(
            dict(action="drop", msg_type="EAGER", src=1, dst=0, nth=3,
                 count=1),
        ))
        a.set_timeout(0.5)
        b.set_timeout(0.5)
        N = 5
        datas = [np.full(32, float(k + 1), np.float32) for k in range(N)]
        sreqs = []
        for k in range(N):
            sb = b.create_buffer_from(datas[k])
            sreqs.append(b.send(sb, 32, dst=0, tag=100 + k, run_async=True))
        rbufs = [a.create_buffer(32, np.float32) for _ in range(N)]
        rreqs = [
            a.recv(rbufs[k], 32, src=1, tag=100 + k, run_async=True)
            for k in range(N)
        ]
        # the isolation window: the reverse (0→1) channel, in flight at
        # the same time, never crosses the fault
        rev_data = np.full(32, 99.0, np.float32)
        rev_send = a.create_buffer_from(rev_data)
        rev_sreq = a.send(rev_send, 32, dst=1, tag=500, run_async=True)
        rev_recv = b.create_buffer(32, np.float32)
        rev_rreq = b.recv(rev_recv, 32, src=0, tag=500, run_async=True)

        for k, q in enumerate(rreqs):
            assert q.wait(10), f"recv {k} never completed (lost!)"
            if k < 2:
                q.check()
                rbufs[k].sync_from_device()
                np.testing.assert_array_equal(rbufs[k].data, datas[k])
            else:
                # k == 2 hit the drop; k > 2 sit behind the hole on the
                # seqn-ordered channel — all fail, none hang
                with pytest.raises(ACCLError) as exc:
                    q.check()
                assert exc.value.code == ErrorCode.RECEIVE_TIMEOUT
                if k == 2:
                    tail = exc.value.details.get("flight_recorder")
                    assert tail, (
                        "failure must ship its flight-recorder tail"
                    )
        for q in sreqs:  # eager sends all completed (fire-and-forget)
            assert q.wait(10)
            q.check()
        assert rev_rreq.wait(10) and rev_sreq.wait(10)
        rev_rreq.check()
        rev_sreq.check()
        rev_recv.sync_from_device()
        np.testing.assert_array_equal(rev_recv.data, rev_data)

        # recovery: soft_reset realigns the seqn counters on both sides;
        # the faulted link serves again
        for x in g:
            x.soft_reset()
        sb = b.create_buffer_from(datas[0])
        rb = a.create_buffer(32, np.float32)
        sq = b.send(sb, 32, dst=0, tag=600, run_async=True)
        rq = a.recv(rb, 32, src=1, tag=600, run_async=True)
        assert rq.wait(10) and sq.wait(10)
        rq.check()
        sq.check()
        rb.sync_from_device()
        np.testing.assert_array_equal(rb.data, datas[0])
    finally:
        for x in g:
            x.deinit()


def test_batch_with_data_dependency_stays_sequentially_correct(g4):
    """A batch position reading an earlier position's RESULT buffer must
    see that result (the fused single-program path would read pre-batch
    bytes, so the planner rejects fusion for dependent chains)."""
    n = 16
    world = len(g4)
    x = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    y = [a.create_buffer(n, np.float32) for a in g4]
    z = [a.create_buffer(world * n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            r1 = a.allreduce(x[r], y[r], n, run_async=True)
            # depends on y: must observe the allreduce's result
            r2 = a.allgather(y[r], z[r], n, run_async=True)
        for req in (r1, r2):
            assert req.wait(60)
            req.check()

    run_parallel(g4, work)
    for r in range(world):
        z[r].sync_from_device()
        np.testing.assert_allclose(z[r].data, 10.0)


def test_nested_batch_contexts_flush_once_at_outer_exit(g4):
    """Inner batch() contexts must not split the outer batch (depth
    counting): everything still dispatches, results correct."""
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    r1v = [a.create_buffer(n, np.float32) for a in g4]
    r2v = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            q1 = a.allreduce(send[r], r1v[r], n, run_async=True)
            with a.batch():  # nested: helper wrapping its own collectives
                q2 = a.allreduce(send[r], r2v[r], n, run_async=True)
            # inner exit must NOT have closed the outer batch
            assert a._pending is not None
        for q in (q1, q2):
            assert q.wait(60)
            q.check()

    run_parallel(g4, work)
    for r in range(4):
        r1v[r].sync_from_device()
        r2v[r].sync_from_device()
        np.testing.assert_allclose(r1v[r].data, 10.0)
        np.testing.assert_allclose(r2v[r].data, 10.0)


def test_segmented_pipelining_gang(g4):
    """Payloads above pipeline_threshold split into ring_segments
    pipelined sub-launches on the gang tier: results stay bit-correct,
    and the flight recorder shows the segment launches (count n/nseg)
    next to the ONE aggregate record covering the full payload."""
    n = 1 << 14
    nseg = 4
    try:
        for a in g4:
            a.set_tuning("ring_segments", nseg)
            a.set_tuning("pipeline_threshold", 8192)  # n*4B is above
        send = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g4)
        ]
        recv = [a.create_buffer(n, np.float32) for a in g4]
        run_parallel(g4, lambda a, r: a.allreduce(send[r], recv[r], n))
        for r in range(len(g4)):
            recv[r].sync_from_device()
            np.testing.assert_allclose(recv[r].data, 10.0)
        recs = [
            rec for rec in g4[0].telemetry_snapshot()["flight_recorder"]
            if rec["op"] == "allreduce"
        ]
        assert len([r for r in recs if r["count"] == n // nseg]) >= nseg
        assert any(r["count"] == n for r in recs)  # the aggregate
        # an async aggregate drains at the flush() drain point like any
        # single call
        reqs = run_parallel(
            g4,
            lambda a, r: a.allreduce(
                send[r], recv[r], n, run_async=True
            ),
        )
        g4[0].flush()
        for q in reqs:
            assert q.done()
            q.check()
    finally:
        for a in g4:
            a.set_tuning("pipeline_threshold", 0)
            a.set_tuning("ring_segments", 1)


def test_segmented_pipelining_emulator():
    """The same split on the emulator tier (bcast + allreduce are the
    eligible ops), segments riding the engine's own schedulers:
    bit-correct, sub-launches visible.  REDUCE must NOT split — its
    per-rank stream-operand overload makes a host-level split
    SPMD-divergent (one rank could split while a streaming peer
    cannot), so the registers leave it whole."""
    from accl_tpu.core import emulated_group

    g = emulated_group(2)
    a, b = g
    n = 2048  # 8 KiB payload over a 1 KiB threshold: 2 segments
    try:
        for x in g:
            x.set_tuning("ring_segments", 2)
            x.set_tuning("pipeline_threshold", 1024)
        data = np.arange(n, dtype=np.float32)

        bufs = [a.create_buffer_from(data.copy()), b.create_buffer(n, np.float32)]
        run_parallel(g, lambda x, r: x.bcast(bufs[r], n, root=0))
        bufs[1].sync_from_device()
        np.testing.assert_array_equal(bufs[1].data, data)

        sa = a.create_buffer_from(data.copy())
        sb = b.create_buffer_from(data.copy())
        ra = a.create_buffer(n, np.float32)
        sends, recvs = [sa, sb], [ra, None]
        run_parallel(
            g,
            lambda x, r: x.reduce(sends[r], recvs[r], n, root=0),
        )
        ra.sync_from_device()
        np.testing.assert_allclose(ra.data, 2.0 * data)

        da = a.create_buffer(n, np.float32)
        db = b.create_buffer(n, np.float32)
        dsts = [da, db]
        run_parallel(
            g, lambda x, r: x.allreduce(sends[r], dsts[r], n)
        )
        for d in dsts:
            d.sync_from_device()
            np.testing.assert_allclose(d.data, 2.0 * data)
        # segment sub-launches recorded next to the aggregates
        recs = a.telemetry_snapshot()["flight_recorder"]
        assert any(
            r["op"] == "allreduce" and r["count"] == n // 2 for r in recs
        )
        assert any(
            r["op"] == "allreduce" and r["count"] == n for r in recs
        )
        # reduce rode the registers UNSPLIT (stream-operand overloads
        # make a per-rank reduce split SPMD-unsafe)
        assert not any(
            r["op"] == "reduce" and r["count"] == n // 2 for r in recs
        )
        assert any(r["op"] == "reduce" and r["count"] == n for r in recs)
    finally:
        for x in g:
            x.deinit()
