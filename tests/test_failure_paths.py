"""Failure surface: timeouts, config validation, error recovery.

Mirrors the reference's error-code/timeout machinery (constants.hpp:355-393,
check_return_value accl.cpp:1210-1234, HOUSEKEEP_TIMEOUT).
"""

import socket as socketlib
import threading

import numpy as np
import pytest

from accl_tpu import ACCLError, ErrorCode, emulated_group, socket_group_member


def _free_addresses(n):
    """Pre-pick n free localhost ports for an in-process socket group."""
    socks, addrs = [], []
    for _ in range(n):
        s = socketlib.socket()
        s.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        addrs.append(f"127.0.0.1:{s.getsockname()[1]}")
    for s in socks:
        s.close()
    return addrs


@pytest.fixture(params=["inproc", "socket"])
def fresh_group2(request):
    """Both emulator transports: the InProc CI tier AND the TCP socket
    tier (in one process), so the socket fabric's timeout/recovery paths
    are exercised by the same failure matrix instead of staying untested."""
    if request.param == "socket":
        last = None
        for _ in range(3):  # a pre-picked port can be re-grabbed: retry
            try:
                addrs = _free_addresses(2)
                g = [socket_group_member(i, addrs) for i in range(2)]
                break
            except OSError as e:
                last = e
        else:
            raise last
    else:
        g = emulated_group(2)
    yield g
    for a in g:
        a.deinit()


def test_recv_timeout_raises(fresh_group2):
    a = fresh_group2[0]
    a.set_timeout(0.2)
    buf = a.create_buffer(10, np.float32)
    with pytest.raises(ACCLError) as exc:
        a.recv(buf, 10, src=1, tag=77)
    assert exc.value.code == ErrorCode.RECEIVE_TIMEOUT


def test_recv_after_timeout_recovers(fresh_group2):
    """A timed-out receive must not poison per-peer sequence matching:
    the inbound counter advances only on match (ref dma_mover.cpp:610)."""
    a, b = fresh_group2
    a.set_timeout(0.2)
    buf = a.create_buffer(10, np.float32)
    with pytest.raises(ACCLError):
        a.recv(buf, 10, src=1, tag=99)
    a.set_timeout(10)

    def sender():
        sb = b.create_buffer_from(np.full(10, 3.0, np.float32))
        b.send(sb, 10, dst=0, tag=1)

    t = threading.Thread(target=sender, daemon=True)
    t.start()
    a.recv(buf, 10, src=1, tag=1)
    t.join(10)
    buf.sync_from_device()
    np.testing.assert_array_equal(buf.data, np.full(10, 3.0, np.float32))


def test_rendezvous_timeout(fresh_group2):
    a = fresh_group2[0]
    a.set_timeout(0.2)
    buf = a.create_buffer_from(np.zeros(64 * 1024, np.float32))
    with pytest.raises(ACCLError) as exc:
        a.send(buf, 64 * 1024, dst=1, tag=5)  # rendezvous; no receiver
    assert exc.value.code == ErrorCode.RENDEZVOUS_TIMEOUT


def test_config_validation(fresh_group2):
    a = fresh_group2[0]
    with pytest.raises(ACCLError):
        a.set_max_eager_size(10**9)
    with pytest.raises(ACCLError):
        a.set_timeout(-1)


def test_request_wait_timeout_leaves_request_unpoisoned():
    """Request.wait(timeout) expiring on an in-flight call returns False,
    leaves status/retcode untouched, and a later wait() adopts the
    deferred result exactly once."""
    import time

    from accl_tpu.request import Request, RequestStatus

    req = Request(op_name="probe")
    req.mark_executing()
    adopted = []
    req.defer_result(lambda: adopted.append(1))

    assert req.wait(0.05) is False
    assert req.status == RequestStatus.EXECUTING  # not poisoned
    assert req.get_retcode() == ErrorCode.OK
    assert adopted == []  # the deferred result must NOT run on a miss
    assert req.wait(0.05) is False  # repeatable while still in flight

    t = threading.Timer(0.2, lambda: req.complete(ErrorCode.OK, 5))
    t.start()
    assert req.wait(5.0) is True
    assert adopted == [1]  # adopted on the first successful wait
    assert req.wait() is True
    req.test()
    req.check()
    assert adopted == [1]  # ... and exactly once
    assert req.get_duration_ns() == 5


def test_request_wait_timeout_on_inflight_engine_call(fresh_group2):
    """The same contract against a real engine call: an expiring wait on a
    not-yet-matched recv does not disturb the call, which then completes
    normally once the sender arrives."""
    a, b = fresh_group2
    buf = a.create_buffer(10, np.float32)
    req = a.recv(buf, 10, src=1, tag=11, run_async=True)
    assert req.wait(0.1) is False  # in flight: no sender yet
    assert req.get_retcode() == ErrorCode.OK

    sb = b.create_buffer_from(np.full(10, 9.0, np.float32))
    b.send(sb, 10, dst=0, tag=11)
    assert req.wait(10.0) is True
    req.check()
    buf.sync_from_device()
    np.testing.assert_array_equal(buf.data, np.full(10, 9.0, np.float32))


def test_socket_dead_peer_send_times_out_fast():
    """Satellite: a socket peer whose process/fabric dies must surface
    SEND_TIMEOUT promptly on later sends — not silently drop them or wait
    out the full call deadline (the fabric.py:222 failure mode)."""
    import time

    addrs = _free_addresses(2)
    g = [socket_group_member(i, addrs) for i in range(2)]
    a, b = g
    try:
        # a real exchange first, so the connection exists
        sb = b.create_buffer_from(np.arange(8, dtype=np.float32))
        t = threading.Thread(
            target=lambda: b.send(sb, 8, dst=0, tag=1), daemon=True
        )
        t.start()
        rb = a.create_buffer(8, np.float32)
        a.recv(rb, 8, src=1, tag=1)
        t.join(10)

        # rank 0 dies (its fabric closes: listener + connections gone)
        a.deinit()
        b.set_timeout(30.0)  # the FULL deadline we must NOT wait out
        t0 = time.monotonic()
        with pytest.raises(ACCLError) as exc:
            # a send may land in the OS buffer of the dead connection
            # until its reset has come back: that takes TIME on a busy
            # machine, not sends (four back to back all landed under
            # six xdist workers), so the sends are spaced, and capped:
            # a fabric that buffers for ever fails here at 64, early
            for i in range(64):
                b.send(sb, 8, dst=0, tag=2 + i)
                time.sleep(0.01)
        elapsed = time.monotonic() - t0
        assert exc.value.code == ErrorCode.SEND_TIMEOUT
        assert elapsed < 10.0, f"dead-peer send took {elapsed:.1f}s"
        # the peer is marked dead in the health map
        assert b.capabilities()["health"][0]["state"] == "dead"
    finally:
        for x in g[1:]:
            x.deinit()


def test_engine_survives_errors(fresh_group2):
    a = fresh_group2[0]
    a.set_timeout(0.2)
    buf = a.create_buffer(10, np.float32)
    for _ in range(3):
        with pytest.raises(ACCLError):
            a.recv(buf, 10, src=1, tag=123)
    src = a.create_buffer_from(np.ones(4, np.float32))
    dst = a.create_buffer(4, np.float32)
    a.copy(src, dst)
    dst.sync_from_device()
    np.testing.assert_array_equal(dst.data, np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# device tiers (VERDICT r2 item 8): gang watchdog timeout + soft-reset
# recovery on the XLA tier; early-exit rank reporting on the dist tier
# ---------------------------------------------------------------------------


def test_xla_gang_timeout_surfaces_watchdog():
    """A gang collective whose peer never submits must surface
    RECEIVE_TIMEOUT via the slot watchdog — the reference's per-call
    deadline (constants.hpp:355-393), not a hang."""
    from accl_tpu.core import xla_group

    g = xla_group(2)
    try:
        a = g[0]
        a.set_timeout(0.3)
        send = a.create_buffer_from(np.ones(16, np.float32))
        recv = a.create_buffer(16, np.float32)
        with pytest.raises(ACCLError) as exc:
            a.allreduce(send, recv, 16)  # rank 1 never calls: gang starves
        assert exc.value.code == ErrorCode.RECEIVE_TIMEOUT
    finally:
        for x in g:
            x.deinit()


def test_xla_gang_recovers_after_soft_reset():
    """soft_reset realigns the gang after a timed-out collective (ref
    accl.cpp:57-89): the failed rank's sequence counter is ahead of the
    absent peer's, and a collective reset restores matching, leaving the
    engine fully usable."""
    import threading

    from accl_tpu.core import xla_group
    from helpers import run_parallel

    g = xla_group(2)
    try:
        a = g[0]
        a.set_timeout(0.3)
        send = a.create_buffer_from(np.ones(16, np.float32))
        recv = a.create_buffer(16, np.float32)
        with pytest.raises(ACCLError):
            a.allreduce(send, recv, 16)  # peer absent: watchdog fires
        a.set_timeout(10)

        # recovery protocol: every rank soft-resets, then work resumes
        for x in g:
            x.soft_reset()

        def work(accl, rank):
            s = accl.create_buffer_from(
                np.full(16, float(rank + 1), np.float32)
            )
            d = accl.create_buffer(16, np.float32)
            accl.allreduce(s, d, 16)
            d.sync_from_device()
            return float(d.data[0])

        assert run_parallel(g, work) == [3.0, 3.0]
    finally:
        for x in g:
            x.deinit()


def test_xla_gang_health_degrades_and_fails_fast():
    """The gang slot watchdog feeds the per-peer health map: an absent
    rank goes suspect -> dead (two strikes), after which collectives
    addressing it fail fast instead of re-burning the watchdog deadline;
    soft_reset clears the verdict."""
    import time

    from accl_tpu.core import xla_group
    from helpers import run_parallel

    g = xla_group(2)
    try:
        a = g[0]
        a.set_timeout(0.3)
        send = a.create_buffer_from(np.ones(16, np.float32))
        recv = a.create_buffer(16, np.float32)
        with pytest.raises(ACCLError) as exc:
            a.allreduce(send, recv, 16)  # strike 1
        assert exc.value.details["peer"] == 1
        assert a.capabilities()["health"][1]["state"] == "suspect"
        with pytest.raises(ACCLError):
            a.allreduce(send, recv, 16)  # strike 2 -> dead
        health = a.capabilities()["health"][1]
        assert health["state"] == "dead" and health["timeouts"] == 2
        assert "health rank 1: dead" in a.dump_communicator()

        a.set_timeout(10)  # a deadline we must NOT wait out
        t0 = time.monotonic()
        with pytest.raises(ACCLError) as exc:
            a.allreduce(send, recv, 16)
        assert time.monotonic() - t0 < 2.0
        assert exc.value.code == ErrorCode.RECEIVE_TIMEOUT
        assert exc.value.details["elapsed_s"] == 0.0  # failed at intake

        # collective recovery: reset clears the health verdict
        for x in g:
            x.soft_reset()
        assert a.capabilities()["health"][1]["state"] == "ok"

        def work(accl, rank):
            s = accl.create_buffer_from(
                np.full(16, float(rank + 1), np.float32)
            )
            d = accl.create_buffer(16, np.float32)
            accl.allreduce(s, d, 16)
            d.sync_from_device()
            return float(d.data[0])

        assert run_parallel(g, work) == [3.0, 3.0]
    finally:
        for x in g:
            x.deinit()


def _early_exit_worker(accl, rank, world):
    """Rank 1 dies before its collective; rank 0 blocks in the gang."""
    import numpy as np

    if rank == 1:
        raise RuntimeError("deliberate rank failure")
    send = accl.create_buffer_from(np.ones(8, np.float32))
    recv = accl.create_buffer(8, np.float32)
    accl.allreduce(send, recv, 8)  # never completes: peer is gone
    return "unreachable"


def test_dist_rank_exit_reported_no_orphans():
    """A dist-tier rank that exits early must be reported per-rank by the
    launcher — and the blocked survivor must be reaped, not orphaned
    (ref: mpirun's per-rank failure reporting)."""
    import multiprocessing
    import time

    from helpers import launch_with_port_retry

    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as exc:
        launch_with_port_retry(
            _early_exit_worker, 2, design="xla_dist", timeout=20.0,
            retry_if=lambda e: "deliberate rank failure" not in str(e),
        )
    msg = str(exc.value)
    assert "rank 1" in msg and "deliberate rank failure" in msg
    assert "rank 0" in msg  # the blocked survivor is reported, not hidden
    assert time.monotonic() - t0 < 60  # bounded by the launcher deadline

    # no orphaned rank processes: the launcher join()/terminate()s every
    # child in its finally, so none of OUR children are still alive
    leftover = [
        p for p in multiprocessing.active_children()
        if p.name != "SyncManager-1"
    ]
    assert leftover == [], [p.name for p in leftover]


def test_subcomm_recv_with_fully_parked_pool():
    """Head-of-line regression (caught by the multi-process soak): a rank
    that is NOT a member of the current subcommunicator op can race ahead
    into the next collective and fill the receiver's ENTIRE eager rx pool
    with parked segments; the subcommunicator segment then waits in the
    inbox with no slot ever becoming free — a deadlock unless the seek
    path can consume straight from the inbox (the native engine's
    overflow-queue match has the same role, ops.cpp seek_rx)."""
    group = emulated_group(3, rx_buffer_count=4)
    a0, a1, a2 = group
    try:
        for a in group:
            a.set_timeout(20.0)
        # rank 2 parks 4 x 4 KiB eager segments at rank 0 (no recv posted):
        # the pool is now 100% occupied by {world comm, src 2} signatures
        filler = a2.create_buffer_from(
            np.arange(4096, dtype=np.float32)  # 16 KiB, eager
        )
        a2.send(filler, 4096, dst=0, tag=7)
        deadline = __import__("time").monotonic() + 10
        while a0.engine.rx_pool.occupancy()[0] < 4:
            if __import__("time").monotonic() > deadline:
                raise AssertionError("filler segments never parked")
            __import__("time").sleep(0.01)

        # subcommunicator op between ranks 0 and 1 must still complete
        comm0 = a0.create_communicator([0, 1])
        comm1 = a1.create_communicator([0, 1])
        assert a2.create_communicator([0, 1]) is None

        payload = np.full(8, 5.0, np.float32)
        err = []

        def sender():
            try:
                sb = a1.create_buffer_from(payload)
                a1.send(sb, 8, dst=0, tag=9, comm=comm1)
            except Exception as e:  # pragma: no cover - surfaced below
                err.append(e)

        t = threading.Thread(target=sender, daemon=True)
        t.start()
        rb = a0.create_buffer(8, np.float32)
        a0.recv(rb, 8, src=1, tag=9, comm=comm0)  # deadlocked before fix
        t.join(10)
        assert not err
        rb.sync_from_device()
        np.testing.assert_array_equal(rb.data, payload)

        # drain the filler; every slot must return to IDLE (no leaks)
        fb = a0.create_buffer(4096, np.float32)
        a0.recv(fb, 4096, src=2, tag=7)
        fb.sync_from_device()
        np.testing.assert_array_equal(
            fb.data, np.arange(4096, dtype=np.float32)
        )
        assert a0.engine.rx_pool.occupancy()[0] == 0
    finally:
        for a in group:
            a.deinit()


# ---------------------------------------------------------------------------
# contract-verifier matrix (accl_tpu.contract): every way two ranks can
# tear the SPMD call sequence must FAIL FAST with CONTRACT_VIOLATION and
# the diverging rank named in ACCLError.details — never hang to the
# engine deadline.  Runs on BOTH emulator transports via fresh_group2
# (InProc: board + wire piggyback; socket: wire piggyback + relay).
# ---------------------------------------------------------------------------


def _drive_contract(group, works, timeout_s=20.0):
    """Run works[rank] on its own thread; returns ({rank: ACCLError},
    elapsed).  interval=1 so the first torn call is also a window
    boundary — detection within ACCL_VERIFY_INTERVAL calls."""
    from accl_tpu import ACCLError as _E

    for a in group:
        a.set_timeout(timeout_s)
        a.set_contract_verify(True, interval=1)
    errs = {}

    def runner(rank):
        try:
            works[rank](group[rank])
        except _E as e:
            errs[rank] = e

    import time as _time

    threads = [
        threading.Thread(
            target=runner, args=(i,), name=f"accl-test-contract{i}",
            daemon=True,
        )
        for i in range(len(group))
    ]
    t0 = _time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(not t.is_alive() for t in threads), "rank thread hung"
    return errs, _time.monotonic() - t0


def _assert_contract_failfast(errs, elapsed, diverging_rank=1):
    """Fail-fast (nowhere near the 20 s deadline), CONTRACT_VIOLATION
    on every failing rank, and the CONFORMING rank 0's report names the
    diverging rank (pairwise blame at world=2 is two-party-symmetric:
    production reads the conforming side's verdict)."""
    assert elapsed < 10, f"not fail-fast: {elapsed:.1f}s"
    assert 0 in errs, "conforming rank never failed (would have hung)"
    for e in errs.values():
        assert e.code == ErrorCode.CONTRACT_VIOLATION, e
    assert errs[0].details["diverging_rank"] == diverging_rank
    assert errs[0].details["contract"]["kind"] == "divergence"
    assert "flight_recorder" in errs[0].details


def test_contract_mismatched_op_order_fails_fast(fresh_group2):
    """rank 0: [allreduce, allreduce]; rank 1: [allgather, allreduce] —
    the op-order tear that classically wedges both ranks until their
    receive deadlines."""

    def work0(a):
        s = a.create_buffer_from(np.ones(8, np.float32))
        d = a.create_buffer(8, np.float32)
        for _ in range(3):
            a.allreduce(s, d, 8)

    def work1(a):
        s = a.create_buffer_from(np.full(8, 2.0, np.float32))
        d = a.create_buffer(8, np.float32)
        r = a.create_buffer(16, np.float32)
        a.allgather(s, r, 8)
        for _ in range(2):
            a.allreduce(s, d, 8)

    errs, elapsed = _drive_contract(fresh_group2, {0: work0, 1: work1})
    _assert_contract_failfast(errs, elapsed)
    # the verdict carries its evidence: the mismatched window plus the
    # (local or relayed) recent-call ring
    assert "window" in errs[0].details["contract"]


def test_contract_mismatched_count_fails_fast(fresh_group2):
    def work0(a):
        s = a.create_buffer_from(np.ones(16, np.float32))
        d = a.create_buffer(16, np.float32)
        for _ in range(3):
            a.allreduce(s, d, 16)

    def work1(a):
        s = a.create_buffer_from(np.full(16, 2.0, np.float32))
        d = a.create_buffer(16, np.float32)
        a.allreduce(s, d, 16)
        a.allreduce(s, d, 8)  # the torn count
        a.allreduce(s, d, 16)

    errs, elapsed = _drive_contract(fresh_group2, {0: work0, 1: work1})
    _assert_contract_failfast(errs, elapsed)


def test_contract_mismatched_root_fails_fast(fresh_group2):
    # both works end in a blocking allreduce: a ROOT's bcast is fire-
    # and-forget on the emulator, so without it rank 0 would complete
    # its whole (conforming) sequence before the verdict can reach it —
    # the trailing collective is where its fail-fast must land
    def work0(a):
        b = a.create_buffer_from(np.ones(8, np.float32))
        d = a.create_buffer(8, np.float32)
        for _ in range(3):
            a.bcast(b, 8, root=0)
        a.allreduce(b, d, 8)

    def work1(a):
        b = a.create_buffer(8, np.float32)
        d = a.create_buffer(8, np.float32)
        a.bcast(b, 8, root=0)
        a.bcast(b, 8, root=1)  # the torn root
        a.bcast(b, 8, root=0)
        a.allreduce(b, d, 8)

    errs, elapsed = _drive_contract(fresh_group2, {0: work0, 1: work1})
    _assert_contract_failfast(errs, elapsed)


def test_contract_subcomm_epoch_skew_fails_fast(fresh_group2):
    """Rank 1 re-creates the subcommunicator (a fresh instance epoch)
    while rank 0 keeps using the original: the begin marker folded into
    rank 1's digest stream diverges it at the next boundary — the skew
    that otherwise surfaces as seqn-dedup silently discarding the fresh
    instance's traffic."""

    def work0(a):
        sub = a.create_communicator([0, 1])
        s = a.create_buffer_from(np.ones(8, np.float32))
        d = a.create_buffer(8, np.float32)
        for _ in range(4):
            a.allreduce(s, d, 8, comm=sub)

    def work1(a):
        sub = a.create_communicator([0, 1])
        s = a.create_buffer_from(np.full(8, 2.0, np.float32))
        d = a.create_buffer(8, np.float32)
        a.allreduce(s, d, 8, comm=sub)
        sub = a.create_communicator([0, 1])  # the skewed re-create
        for _ in range(3):
            a.allreduce(s, d, 8, comm=sub)

    errs, elapsed = _drive_contract(fresh_group2, {0: work0, 1: work1})
    assert elapsed < 10, f"not fail-fast: {elapsed:.1f}s"
    assert errs, "skew never detected"
    for e in errs.values():
        assert e.code == ErrorCode.CONTRACT_VIOLATION
    if 0 in errs:
        assert errs[0].details["diverging_rank"] == 1
