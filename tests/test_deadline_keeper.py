"""The gang engine's one deadline keeper (`backends/xla/engine.py`): a
parked call arms a deadline and starts no thread; nothing is cancelled,
an assembled slot or a matched post is found dead when its entry comes
up.  CPU mesh, short ``timeout_s``; host clocks here are tolerances for
"did it fire at the deadline", never speeds."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from accl_tpu import ACCLError, ErrorCode
from accl_tpu.core import xla_group
from helpers import run_parallel

N = 16
TIMEOUT_S = 0.4
#: a deadline may fire this much late on a loaded CPU host (six xdist
#: workers share it); it never fires early
LATE_S = 1.5
WORLD_COMM = 0  # the facade's world communicator id


@pytest.fixture
def group4():
    g = xla_group(4)
    yield g
    for a in g:
        a.deinit()


def _bufs(a, value=1.0):
    return (
        a.create_buffer_from(np.full(N, value, np.float32)),
        a.create_buffer(N, np.float32),
    )


def _stats(g):
    return g[0].engine.telemetry_report()["gang_deadlines"]


def _timed_failure(fn):
    """Run ``fn`` expecting an ACCLError; return (error, seconds)."""
    t0 = time.monotonic()
    with pytest.raises(ACCLError) as exc:
        fn()
    return exc.value, time.monotonic() - t0


def _park_async(g, rank=0, comm=None):
    """Rank ``rank`` posts one async allreduce nobody joins; return the
    facade's request and the requests parked in the gang's slot, each
    with ``complete`` counted."""
    a = g[rank]
    gang = a.engine.gang
    s, d = _bufs(a)
    kw = {} if comm is None else {"comm": comm}
    req = a.allreduce(s, d, N, run_async=True, **kw)
    deadline = time.monotonic() + 10
    while not gang._slots and time.monotonic() < deadline:
        time.sleep(0.005)
    (slot,) = gang._slots.values()
    counts = []
    for parked in gang._slot_requests(slot):
        count = {"n": 0}
        orig = parked.complete

        def counting(*args, _orig=orig, _count=count, **kwargs):
            _count["n"] += 1
            return _orig(*args, **kwargs)

        parked.complete = counting
        counts.append(count)
    return req, counts, (s, d)


def _stats_when(g, done, timeout=10.0):
    """The counters once ``done(stats)`` holds: the keeper counts an
    expiry AFTER the completion that releases the waiting rank."""
    deadline = time.monotonic() + timeout
    while not done(_stats(g)) and time.monotonic() < deadline:
        time.sleep(0.02)
    return _stats(g)


def _wait_entries_gone(g):
    return _stats_when(g, lambda st: not st["queued"])


def _wait_fired(g, n):
    return _stats_when(g, lambda st: st["fired"] >= n)


def _case_absent_rank(g):
    """(a) ranks 0-2 call, rank 3 never joins: RECEIVE_TIMEOUT with
    today's context keys, one strike for session 3, at the deadline."""
    edges = []
    g[0].engine.gang.add_health_listener(
        lambda session, old, new: edges.append((session, old, new))
    )
    for a in g:
        a.set_timeout(TIMEOUT_S)

    def work(a, rank):
        if rank == 3:
            return None
        s, d = _bufs(a)
        return _timed_failure(lambda: a.allreduce(s, d, N))

    res = run_parallel(g, work)
    for err, _ in res[:3]:
        assert err.code == ErrorCode.RECEIVE_TIMEOUT
        assert err.details["comm"] == WORLD_COMM
        assert err.details["peer"] == 3
        assert err.details["elapsed_s"] == TIMEOUT_S
        assert err.details["op"] == "ALLREDUCE"
    # the slot's deadline runs from the FIRST arrival: every waiter is
    # released by then + LATE_S, and the first waited the whole of it
    waits = [dt for _, dt in res[:3]]
    assert max(waits) >= TIMEOUT_S - 0.02
    assert max(waits) <= TIMEOUT_S + LATE_S
    health = g[0].capabilities()["health"][3]
    assert health["state"] == "suspect" and health["timeouts"] == 1
    assert edges == [(3, "ok", "suspect")]
    st = _wait_fired(g, 1)
    assert st["armed"] == 1 and st["fired"] == 1
    assert st["threads_started"] == 1


def _case_assembled_never_fires(g):
    """(b) slots that assemble leave nothing to fire: their entries are
    dropped when the deadline comes up."""
    for a in g:
        a.set_timeout(TIMEOUT_S)

    def work(a, rank):
        s, d = _bufs(a, rank + 1.0)
        for _ in range(5):
            a.allreduce(s, d, N)
        d.sync_from_device()
        return float(d.data[0])

    assert run_parallel(g, work) == [10.0] * 4
    st = _wait_entries_gone(g)
    assert st == {
        "armed": 5, "fired": 0, "dropped_stale": 5,
        "threads_started": 1, "queued": 0,
    }


def _case_soft_reset(g):
    """(c1) soft_reset completes a parked slot once; the keeper later
    drops the entry without a second completion."""
    g[0].set_timeout(TIMEOUT_S)
    req, counts, _keep = _park_async(g)
    g[0].soft_reset()
    assert req.wait(10)
    assert req.get_retcode() == ErrorCode.RECEIVE_TIMEOUT
    time.sleep(TIMEOUT_S + 0.2)
    st = _wait_entries_gone(g)
    assert [c["n"] for c in counts] == [1]
    assert st["fired"] == 0 and st["dropped_stale"] == 1 == st["armed"]
    assert g[0].capabilities()["health"][3]["timeouts"] == 0


def _case_contract_fail(g):
    """(c2) a contract verdict completes a parked slot once, likewise."""
    g[0].set_timeout(TIMEOUT_S)
    req, counts, _keep = _park_async(g)
    g[0].engine.gang.contract_fail({"comm": WORLD_COMM, "diverging_rank": 2})
    assert req.wait(10)
    time.sleep(TIMEOUT_S + 0.2)
    st = _wait_entries_gone(g)
    assert [c["n"] for c in counts] == [1]
    assert req.get_retcode() == ErrorCode.CONTRACT_VIOLATION
    assert st["fired"] == 0 and st["dropped_stale"] == 1 == st["armed"]


def _case_two_comms(g):
    """(d) two communicators starving at once both expire, one after
    the other on the shared thread."""
    for a in g:
        a.set_timeout(TIMEOUT_S)

    def work(a, rank):
        comm = a.create_communicator([0, 1] if rank < 2 else [2, 3])
        if rank in (1, 3):
            return None  # the absent halves
        s, d = _bufs(a)
        err, dt = _timed_failure(lambda: a.allreduce(s, d, N, comm=comm))
        return err, dt, comm.id

    res = run_parallel(g, work)
    for rank, absent_session in ((0, 1), (2, 3)):
        err, dt, comm_id = res[rank]
        assert err.code == ErrorCode.RECEIVE_TIMEOUT
        assert err.details["comm"] == comm_id
        assert err.details["peer"] == 1  # comm-relative absent member
        assert TIMEOUT_S - 0.02 <= dt <= TIMEOUT_S + LATE_S
        health = g[0].engine.gang.health[absent_session]
        assert health["timeouts"] == 1
    assert res[0][2] != res[2][2]
    st = _wait_fired(g, 2)
    assert st["armed"] == 2 and st["fired"] == 2
    assert st["threads_started"] == 1


def _case_p2p(g):
    """(e) a parked recv / send expires with RECEIVE_TIMEOUT /
    SEND_TIMEOUT and today's ``peer`` (the absent partner)."""
    for a in g:
        a.set_timeout(TIMEOUT_S)

    def work(a, rank):
        if rank == 0:
            buf = a.create_buffer(N, np.float32)
            return _timed_failure(lambda: a.recv(buf, N, src=1, tag=5))
        if rank == 2:
            buf = a.create_buffer_from(np.ones(N, np.float32))
            return _timed_failure(lambda: a.send(buf, N, dst=3, tag=6))
        return None

    res = run_parallel(g, work)
    for rank, code, op, peer in (
        (0, ErrorCode.RECEIVE_TIMEOUT, "RECV", 1),
        (2, ErrorCode.SEND_TIMEOUT, "SEND", 3),
    ):
        err, dt = res[rank]
        assert err.code == code
        assert err.details["op"] == op
        assert err.details["peer"] == peer
        assert err.details["comm"] == WORLD_COMM
        assert TIMEOUT_S - 0.02 <= err.details["elapsed_s"] <= dt
        assert dt <= TIMEOUT_S + LATE_S
    st = _wait_fired(g, 2)
    assert st["armed"] == 2 and st["fired"] == 2
    assert st["threads_started"] == 1
    assert g[0].engine.telemetry_report()["p2p_parked"] == 0


def _case_shorter_timeout(g):
    """(f) a shorter timeout written through the config path is kept by
    the next slot while an older, LATER deadline is still queued: the
    one arm that has to wake the keeper."""
    comms = run_parallel(
        g, lambda a, rank: a.create_communicator(
            [0, 1] if rank < 2 else [2, 3]
        ),
    )
    g[0].set_timeout(30.0)
    old_req, counts, _keep = _park_async(g, 0, comm=comms[0])
    time.sleep(0.1)  # the keeper now sleeps toward the 30 s deadline
    g[2].set_timeout(TIMEOUT_S)
    s, d = _bufs(g[2])
    err, dt = _timed_failure(
        lambda: g[2].allreduce(s, d, N, comm=comms[2])
    )
    assert err.code == ErrorCode.RECEIVE_TIMEOUT
    assert err.details["comm"] == comms[2].id
    assert TIMEOUT_S - 0.02 <= dt <= TIMEOUT_S + LATE_S
    assert not old_req.test() and counts[0]["n"] == 0
    st = _wait_fired(g, 1)
    assert st["armed"] == 2 and st["fired"] == 1 and st["queued"] == 1
    g[0].soft_reset()  # releases the older call
    assert old_req.wait(10) and counts[0]["n"] == 1


@pytest.mark.parametrize("case", [
    _case_absent_rank, _case_assembled_never_fires, _case_soft_reset,
    _case_contract_fail, _case_two_comms, _case_p2p,
    _case_shorter_timeout,
], ids=lambda f: f.__name__[len("_case_"):])
def test_deadline_keeper(group4, case):
    case(group4)


def test_no_thread_is_started_for_a_gang_call(group4):
    """The mechanism engages: 200 blocking allreduces arm 200 deadlines
    on ONE keeper thread, start no other, fire none, and leave at most
    ``_SWEEP_MIN`` stale entries behind."""
    from accl_tpu.backends.xla.engine import _DeadlineKeeper

    g = group4
    bufs = [_bufs(a, rank + 1.0) for rank, a in enumerate(g)]

    def calls(count):
        def work(a, rank):
            s, d = bufs[rank]
            for _ in range(count):
                a.allreduce(s, d, N)
        return work

    run_parallel(g, calls(1))
    threads_after_first = threading.active_count()
    run_parallel(g, calls(199))
    assert threading.active_count() == threads_after_first
    assert not [
        t for t in threading.enumerate() if isinstance(t, threading.Timer)
    ]
    st = _stats(g)
    assert st["threads_started"] == 1
    assert st["armed"] == 200
    assert st["fired"] == 0
    assert st["queued"] <= _DeadlineKeeper._SWEEP_MIN
    assert st["dropped_stale"] + st["queued"] == 200
    bufs[0][1].sync_from_device()
    assert float(bufs[0][1].data[0]) == 10.0


def _until(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    return cond()


class _Parked:
    """A stand-in for a slot or a post: records when it expired."""

    def __init__(self, log, name, live=True, boom=False):
        self.log, self.name, self.live, self.boom = log, name, live, boom

    def expire(self):
        self.log.append((self.name, time.monotonic()))
        if self.boom:
            raise RuntimeError("a done-callback raised")
        return self.live


def _unit_order(keeper, log):
    """Deadlines armed out of order fire in deadline order."""
    now = time.monotonic()
    held = [_Parked(log, name) for name in ("late", "early", "mid")]
    for p, dt in zip(held, (0.30, 0.10, 0.20)):
        keeper.arm(now + dt, p)
    assert _until(lambda: keeper.stats()["fired"] == 3)
    assert [name for name, _ in log] == ["early", "mid", "late"]
    for (_, at), dt in zip(log, (0.10, 0.20, 0.30)):
        assert at - now >= dt
    assert keeper.stats() == {
        "armed": 3, "fired": 3, "dropped_stale": 0,
        "threads_started": 1, "queued": 0,
    }


def _unit_sweep(keeper, log):
    """Dead entries are swept on arm: the heap never holds more than
    ``_SWEEP_MIN`` of them however many calls came and went."""
    far = time.monotonic() + 3600
    worst = 0
    for i in range(1000):
        keeper.arm(far, _Parked(log, i))  # dies at once: nobody holds it
        worst = max(worst, keeper.stats()["queued"])
    assert worst <= keeper._SWEEP_MIN
    keeper_alive = _Parked(log, "alive")
    keeper.arm(far, keeper_alive)
    st = keeper.stats()
    assert st["armed"] == 1001 and st["fired"] == 0 and not log
    assert st["dropped_stale"] + st["queued"] == 1001
    assert st["threads_started"] == 1


def _unit_stale_and_raising(keeper, log):
    """An entry whose owner says "not parked any more" counts as stale,
    and an expiry that raises does not end the thread."""
    now = time.monotonic()
    held = [
        _Parked(log, "stale", live=False),
        _Parked(log, "boom", boom=True),
        _Parked(log, "after"),
    ]
    for p, dt in zip(held, (0.05, 0.10, 0.15)):
        keeper.arm(now + dt, p)
    assert _until(lambda: keeper.stats()["fired"] == 2)
    assert [name for name, _ in log] == ["stale", "boom", "after"]
    st = keeper.stats()
    assert st["fired"] == 2 and st["dropped_stale"] == 1
    assert st["threads_started"] == 1


def _unit_stop_and_restart(keeper, log):
    """``stop`` ends the thread once nothing live is left; a call parked
    at shutdown still gets its deadline, and a later arm starts anew."""
    parked = _Parked(log, "parked-at-stop")
    keeper.arm(time.monotonic() + 0.2, parked)
    thread = keeper._thread
    keeper.stop()
    thread.join(5)
    assert not thread.is_alive()
    assert [name for name, _ in log] == ["parked-at-stop"]
    again = _Parked(log, "again")
    keeper.arm(time.monotonic() + 0.05, again)
    assert _until(lambda: keeper._thread is None)
    assert [name for name, _ in log] == ["parked-at-stop", "again"]
    assert keeper.stats()["threads_started"] == 2


@pytest.mark.parametrize("case", [
    _unit_order, _unit_sweep, _unit_stale_and_raising,
    _unit_stop_and_restart,
], ids=lambda f: f.__name__[len("_unit_"):])
def test_deadline_keeper_unit(case, capsys):
    from accl_tpu.backends.xla.engine import _DeadlineKeeper

    keeper = _DeadlineKeeper()
    try:
        case(keeper, [])
    finally:
        keeper.stop()
    if case is _unit_stale_and_raising:
        assert "a done-callback raised" in capsys.readouterr().err
