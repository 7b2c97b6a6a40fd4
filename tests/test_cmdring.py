"""Command-ring mechanics: a batch becomes one program.

The ring's counter-asserted claim (ISSUE 10 / ROADMAP item 1): a warm
batched window of N eligible collectives costs exactly ONE host refill
interaction — the host encodes slots and rings the doorbell, the
window program decodes and executes the window on device, and the
drainer polls the status word.  These tests pin the mechanics around
that claim: slot encode/decode from the one layout table, wrap-around,
refill underrun (sequencer parks — no spin), oversized/unsupported
fallback to host dispatch, soft_reset teardown realigning seqn, and the
``ring_resident`` telemetry trail.  Runs on the 8-device virtual CPU
mesh; the window has one form, the one the chip runs.
"""

import numpy as np
import pytest

from helpers import run_parallel

from accl_tpu.constants import (
    CMDRING_FIELDS,
    CMDRING_SLOT_WORDS,
    CMDRING_ST_BAD_OP,
    CMDRING_ST_OK,
    CmdOpcode,
    FusedCompute,
    Operation,
    ReduceFunction,
)
from accl_tpu.cmdring import (
    decode_fparam,
    encode_fparam,
    fused_slot_eligible,
    ring_widths,
)
from accl_tpu.core import xla_group
from accl_tpu.ops.cmdring import (
    decode_slot,
    encode_slot,
    encode_window,
)


@pytest.fixture(scope="module")
def g4():
    g = xla_group(4)
    yield g
    for a in g:
        a.deinit()


def _interactions(a) -> int:
    return a.capabilities()["device_interactions"]


def _ring(a):
    return a.engine.gang.cmdring


# ---------------------------------------------------------------------------
# encoder / decoder (the slot-layout contract)
# ---------------------------------------------------------------------------


def test_slot_round_trip():
    words = encode_slot(
        41, CmdOpcode.ALLREDUCE, 1024, dtype=2,
        function=ReduceFunction.MAX, root=3, flags=0, nseg=2,
    )
    assert words.shape == (CMDRING_SLOT_WORDS,)
    d = decode_slot(words)
    assert d["seqn"] == 41
    assert d["opcode"] is CmdOpcode.ALLREDUCE
    assert d["count"] == 1024
    assert d["function"] == int(ReduceFunction.MAX)
    assert d["root"] == 3
    assert d["nseg"] == 2
    # every layout field decodes (the table is the contract)
    assert set(d) == set(CMDRING_FIELDS)


def test_window_nop_padding_and_overflow():
    w = encode_window([encode_slot(0, CmdOpcode.BCAST, 8)], 4)
    assert w.shape == (4, CMDRING_SLOT_WORDS)
    for i in (1, 2, 3):
        assert decode_slot(w[i])["opcode"] is CmdOpcode.NOP
    with pytest.raises(ValueError):
        encode_window([encode_slot(0, CmdOpcode.NOP, 0)] * 3, 2)


def test_decode_rejects_wrong_width():
    with pytest.raises(ValueError):
        decode_slot(np.zeros(CMDRING_SLOT_WORDS + 1, np.int32))


# ---------------------------------------------------------------------------
# the counter-asserted contract: N collectives, ONE refill interaction
# ---------------------------------------------------------------------------


def _window(g4, send, out_ar, out_mx, out_bc, n):
    def work(a, r):
        with a.batch():
            r1 = a.allreduce(send[r], out_ar[r], n, run_async=True)
            r2 = a.allreduce(
                send[r], out_mx[r], n,
                function=ReduceFunction.MAX, run_async=True,
            )
            r3 = a.bcast(out_bc[r], n, root=2, run_async=True)
        reqs = (r1, r2, r3)
        for req in reqs:
            assert req.wait(60)
            req.check()
        return reqs

    return run_parallel(g4, work)


def test_warm_window_is_one_refill_interaction(g4):
    n = 32
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    out_ar = [a.create_buffer(n, np.float32) for a in g4]
    out_mx = [a.create_buffer(n, np.float32) for a in g4]
    out_bc = [
        a.create_buffer_from(np.full(n, 50.0 + r, np.float32))
        for r, a in enumerate(g4)
    ]
    _window(g4, send, out_ar, out_mx, out_bc, n)  # cold: compiles
    for r, a in enumerate(g4):
        out_bc[r].data[:] = 50.0 + r
        out_bc[r].sync_to_device()
    ring0 = _ring(g4[0]).stats()
    ic0 = _interactions(g4[0])
    reqs = _window(g4, send, out_ar, out_mx, out_bc, n)
    ic1 = _interactions(g4[0])
    ring1 = _ring(g4[0]).stats()
    assert ic1 - ic0 == 1, (
        "a warm ring window of 3 collectives must be exactly ONE host "
        "refill interaction"
    )
    assert ring1["refills"] - ring0["refills"] == 1
    assert ring1["doorbells"] - ring0["doorbells"] == 1
    assert ring1["slots"] - ring0["slots"] == 3
    # results: sum, max, root-2 bcast
    for r in range(4):
        out_ar[r].sync_from_device()
        np.testing.assert_allclose(out_ar[r].data, 10.0)
        out_mx[r].sync_from_device()
        np.testing.assert_allclose(out_mx[r].data, 4.0)
        out_bc[r].sync_from_device()
        np.testing.assert_allclose(out_bc[r].data, 52.0)
    # every request carries the ring-resident mark
    for rank_reqs in reqs:
        for req in rank_reqs:
            assert req.ring_resident is True


def test_ring_resident_rides_telemetry(g4):
    tail = g4[0]._telemetry.tail_dicts(3)
    assert tail and all(rec.get("ring_resident") for rec in tail)
    counters = g4[0].telemetry_snapshot()["metrics"]["counters"]
    assert any(
        k.startswith("accl_ring_resident_calls_total") for k in counters
    )
    rep = g4[0].engine.telemetry_report()["cmdring"]
    for key in ("refills", "doorbells", "occupancy", "state", "depth"):
        assert key in rep
    inflight = g4[0].engine.telemetry_report()["inflight"]
    assert inflight["ring_launched"] >= 1


# ---------------------------------------------------------------------------
# wrap-around, underrun parking, soft_reset teardown
# ---------------------------------------------------------------------------


def test_slot_wrap_around(g4):
    ring = _ring(g4[0])
    depth = ring.depth
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.float32) for a in g4]

    def window(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[r], n, run_async=True)
                for _ in range(3)
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()

    wraps0 = ring.stats()["wraps"]
    rounds = depth // 3 + 2  # head must cross the ring boundary
    for _ in range(rounds):
        run_parallel(g4, window)
    st = ring.stats()
    assert st["wraps"] > wraps0, "head never wrapped the ring"
    comm_id = g4[0]._world.id
    session = ring._sessions[comm_id]
    assert session.seqn >= rounds * 3  # seqn stays monotone across wraps
    assert session.ring.shape == (depth, CMDRING_SLOT_WORDS)
    for r in range(4):
        out[r].sync_from_device()
        np.testing.assert_allclose(out[r].data, 10.0)


def test_refill_underrun_parks_sequencer(g4):
    """Host slower than the sequencer: when the last in-flight window
    drains, the sequencer parks on the doorbell — no window in flight,
    no spin — and the next refill re-arms it."""
    import time

    ring = _ring(g4[0])
    deadline = time.monotonic() + 30
    while not ring.parked:
        assert time.monotonic() < deadline, "sequencer never parked"
        time.sleep(0.01)
    st = ring.stats()
    assert st["state"] == "parked"
    assert st["doorbells"] == st["refills"]  # one doorbell per refill,
    # none fired while parked (the no-spin contract)


def test_soft_reset_parks_and_realigns_seqn(g4):
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.float32) for a in g4]

    def window(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[r], n, run_async=True)
                for _ in range(2)
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()

    run_parallel(g4, window)
    ring = _ring(g4[0])
    comm_id = g4[0]._world.id
    assert ring._sessions[comm_id].seqn > 0
    resets0 = ring.stats()["resets"]

    run_parallel(g4, lambda a, r: a.soft_reset())
    st = ring.stats()
    assert st["resets"] > resets0
    assert st["state"] == "parked"
    assert comm_id not in ring._sessions  # teardown: session abandoned

    run_parallel(g4, window)  # the ring re-arms after the reset
    assert ring._sessions[comm_id].seqn == 2  # realigned at 0, then 2
    for r in range(4):
        out[r].sync_from_device()
        np.testing.assert_allclose(out[r].data, 10.0)


# ---------------------------------------------------------------------------
# fallbacks: oversized payloads + unsupported ops stay on host dispatch
# ---------------------------------------------------------------------------


def test_oversized_payload_falls_back_to_host_dispatch(g4):
    ring = _ring(g4[0])
    n = 64
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.float32) for a in g4]

    def window(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[r], n, run_async=True)
                for _ in range(2)
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()
        return reqs

    saved = ring.max_bytes
    ring.max_bytes = n * 4 - 1  # every payload is now oversized
    try:
        over0 = ring.stats()["fallbacks"].get("oversized", 0)
        slots0 = ring.stats()["slots"]
        reqs = run_parallel(g4, window)
        st = ring.stats()
        assert st["fallbacks"].get("oversized", 0) > over0
        assert st["slots"] == slots0  # nothing executed ring-resident
        for rank_reqs in reqs:
            for req in rank_reqs:
                assert req.ring_resident is None
        for r in range(4):
            out[r].sync_from_device()
            np.testing.assert_allclose(out[r].data, 10.0)
    finally:
        ring.max_bytes = saved


def test_unsupported_op_falls_back(g4):
    """A batch containing a rooted reduce (no ring opcode — the rooted
    trees stay host-dispatch) falls back whole — and still fuses to one
    interaction on the legacy path."""
    ring = _ring(g4[0])
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    ar = [a.create_buffer(n, np.float32) for a in g4]
    rd = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            r1 = a.allreduce(send[r], ar[r], n, run_async=True)
            r2 = a.reduce(send[r], rd[r], n, root=0, run_async=True)
        for req in (r1, r2):
            assert req.wait(60)
            req.check()

    run_parallel(g4, work)  # cold
    un0 = ring.stats()["fallbacks"].get("unsupported_op", 0)
    ic0 = _interactions(g4[0])
    run_parallel(g4, work)
    assert _interactions(g4[0]) - ic0 == 1  # fused batch still 1
    assert ring.stats()["fallbacks"].get("unsupported_op", 0) > un0
    for r in range(4):
        ar[r].sync_from_device()
        np.testing.assert_allclose(ar[r].data, 10.0)
    rd[0].sync_from_device()
    np.testing.assert_allclose(rd[0].data, 10.0)


# ---------------------------------------------------------------------------
# eager mode: single warm calls ride one-slot windows
# ---------------------------------------------------------------------------


def test_eager_mode_routes_single_calls(monkeypatch):
    monkeypatch.setenv("ACCL_CMDRING", "eager")
    g = xla_group(2)
    try:
        ring = _ring(g[0])
        assert ring.eager
        n = 16
        send = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g)
        ]
        out = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            return a.allreduce(send[r], out[r], n, run_async=True)

        reqs = run_parallel(g, work)
        for req in reqs:
            assert req.wait(60)
            req.check()
        # warm pass: one refill per call (a one-slot window)
        refills0 = ring.stats()["refills"]
        ic0 = _interactions(g[0])
        reqs = run_parallel(g, work)
        for req in reqs:
            assert req.wait(60)
            req.check()
        assert _interactions(g[0]) - ic0 == 1
        assert ring.stats()["refills"] - refills0 == 1
        assert all(req.ring_resident for req in reqs)
        for r in range(2):
            out[r].sync_from_device()
            np.testing.assert_allclose(out[r].data, 3.0)
    finally:
        for a in g:
            a.deinit()


def test_disabled_ring_stays_off(monkeypatch):
    monkeypatch.setenv("ACCL_CMDRING", "0")
    g = xla_group(2)
    try:
        ring = _ring(g[0])
        assert not ring.enabled
        n = 16
        send = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g)
        ]
        out = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            with a.batch():
                req = a.allreduce(send[r], out[r], n, run_async=True)
            assert req.wait(60)
            req.check()
            return req

        reqs = run_parallel(g, work)
        assert ring.stats()["refills"] == 0
        assert all(req.ring_resident is None for req in reqs)
        for r in range(2):
            out[r].sync_from_device()
            np.testing.assert_allclose(out[r].data, 3.0)
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# a mixed-dtype window falls back whole
# ---------------------------------------------------------------------------


def test_mixed_dtype_window_falls_back(g4):
    """The pallas lowering packs a window into ONE buffer, so a mixed-
    dtype window must fall back whole (on every lowering — the slot
    schema is lowering-agnostic) instead of silently promoting."""
    ring = _ring(g4[0])
    n = 16
    send_f = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    send_i = [
        a.create_buffer_from(np.full(n, r + 1, np.int32))
        for r, a in enumerate(g4)
    ]
    out_f = [a.create_buffer(n, np.float32) for a in g4]
    out_i = [a.create_buffer(n, np.int32) for a in g4]

    def work(a, r):
        with a.batch():
            r1 = a.allreduce(send_f[r], out_f[r], n, run_async=True)
            r2 = a.allreduce(send_i[r], out_i[r], n, run_async=True)
        for req in (r1, r2):
            assert req.wait(60)
            req.check()

    mixed0 = ring.stats()["fallbacks"].get("mixed_dtype", 0)
    run_parallel(g4, work)
    assert ring.stats()["fallbacks"].get("mixed_dtype", 0) > mixed0
    for r in range(4):
        out_f[r].sync_from_device()
        np.testing.assert_allclose(out_f[r].data, 10.0)
        out_i[r].sync_from_device()
        np.testing.assert_array_equal(out_i[r].data, 10)


# ---------------------------------------------------------------------------
# full opcode space, mixed windows, pipelined streams
# ---------------------------------------------------------------------------


def test_mixed_opcode_window_rides_ring(g4):
    """The tentpole's opcode growth: ONE warm batched window mixing
    allreduce, reduce-scatter, allgather, alltoall, barrier and a
    compressed allreduce executes ring-resident — one refill
    interaction, zero unsupported_op/compressed fallbacks — and every
    result matches the host-computed reference."""
    ring = _ring(g4[0])
    n = 16
    world = 4
    base = [
        np.arange(n, dtype=np.float32) + 8.0 * (r + 1)
        for r in range(world)
    ]
    wide = [
        np.arange(world * n, dtype=np.float32) * 0.5 + 100.0 * (r + 1)
        for r in range(world)
    ]
    send = [a.create_buffer_from(base[r]) for r, a in enumerate(g4)]
    send_w = [a.create_buffer_from(wide[r]) for r, a in enumerate(g4)]
    ar = [a.create_buffer(n, np.float32) for a in g4]
    car = [a.create_buffer(n, np.float32) for a in g4]
    rs = [a.create_buffer(n, np.float32) for a in g4]
    ag = [a.create_buffer(world * n, np.float32) for a in g4]
    a2a = [a.create_buffer(world * n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], ar[r], n, run_async=True),
                a.reduce_scatter(send_w[r], rs[r], n, run_async=True),
                a.allgather(send[r], ag[r], n, run_async=True),
                a.barrier(run_async=True),
                a.alltoall(send_w[r], a2a[r], n, run_async=True),
                a.allreduce(
                    send[r], car[r], n, compress_dtype=np.float16,
                    run_async=True,
                ),
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()
        return reqs

    run_parallel(g4, work)  # cold: compiles the program
    st0 = ring.stats()
    ic0 = _interactions(g4[0])
    reqs = run_parallel(g4, work)
    st1 = ring.stats()
    assert _interactions(g4[0]) - ic0 == 1, (
        "a warm mixed window of 6 collectives must be ONE refill "
        "interaction"
    )
    assert st1["slots"] - st0["slots"] == 6
    # the acceptance gate: the grown opcode space leaves nothing behind
    for reason in ("unsupported_op", "compressed", "mixed_dtype"):
        assert st1["fallbacks"].get(reason, 0) == st0["fallbacks"].get(
            reason, 0
        ), f"mixed warm window still falls back with {reason}"
    for rank_reqs in reqs:
        for req in rank_reqs:
            assert req.ring_resident is True
    # per-opcode residency evidence
    for opname in (
        "ALLREDUCE", "REDUCE_SCATTER", "ALLGATHER", "ALLTOALL", "BARRIER",
    ):
        assert st1["ops"].get(opname, 0) > 0, f"{opname} never rode"
    # references
    ar_ref = np.sum(base, axis=0)
    stack = np.stack(wide)  # (world, world*n)
    rs_ref = stack.sum(axis=0).reshape(world, n)
    ag_ref = np.concatenate(base)
    a2a_ref = stack.reshape(world, world, n).transpose(1, 0, 2).reshape(
        world, world * n
    )
    f16 = np.float16
    car_ref = np.sum(
        [b.astype(f16).astype(np.float32) for b in base], axis=0
    )
    for r in range(world):
        ar[r].sync_from_device()
        np.testing.assert_allclose(ar[r].data, ar_ref)
        rs[r].sync_from_device()
        np.testing.assert_allclose(rs[r].data, rs_ref[r])
        ag[r].sync_from_device()
        np.testing.assert_allclose(ag[r].data, ag_ref)
        a2a[r].sync_from_device()
        np.testing.assert_allclose(a2a[r].data, a2a_ref[r])
        car[r].sync_from_device()
        np.testing.assert_allclose(car[r].data, car_ref)


def test_pipelined_stream_of_mixed_windows_one_interaction_each(g4):
    """A warm stream of K windows dispatched back-to-back without a
    drain (``_dispatch_pending``), each mixing allreduce, reduce-scatter,
    allgather and alltoall: every window is one refill, one program and
    one host interaction — K in all — and every rank's requests complete
    in issue order."""
    ring = _ring(g4[0])
    n, world, K = 16, 4, 5
    base = [
        np.arange(n, dtype=np.float32) + 8.0 * (r + 1)
        for r in range(world)
    ]
    wide = [
        np.arange(world * n, dtype=np.float32) * 0.5 + 100.0 * (r + 1)
        for r in range(world)
    ]
    send = [a.create_buffer_from(base[r]) for r, a in enumerate(g4)]
    send_w = [a.create_buffer_from(wide[r]) for r, a in enumerate(g4)]
    ar = [a.create_buffer(n, np.float32) for a in g4]
    rs = [a.create_buffer(n, np.float32) for a in g4]
    ag = [a.create_buffer(world * n, np.float32) for a in g4]
    a2a = [a.create_buffer(world * n, np.float32) for a in g4]
    order = {r: [] for r in range(world)}

    def stream(a, r):
        """_dispatch_pending launches each window without draining
        (batch exit would drain the in-flight window and serialize the
        stream), so the host genuinely runs ahead of the device."""
        reqs = []
        a.begin_batch()
        try:
            for k in range(K):
                window = [
                    a.allreduce(send[r], ar[r], n, run_async=True),
                    a.reduce_scatter(send_w[r], rs[r], n, run_async=True),
                    a.allgather(send[r], ag[r], n, run_async=True),
                    a.alltoall(send_w[r], a2a[r], n, run_async=True),
                ]
                for i, req in enumerate(window):
                    req.add_done_callback(
                        lambda k=k, i=i: order[r].append((k, i))
                    )
                reqs.extend(window)
                a._dispatch_pending()  # launch, do NOT drain
        finally:
            a.end_batch()  # the one drain for the whole stream
        for req in reqs:
            assert req.wait(60)
            req.check()
        return reqs

    run_parallel(g4, stream)  # cold: compiles the window program
    for r in range(world):
        order[r].clear()
    st0 = ring.stats()
    ic0 = _interactions(g4[0])
    reqs = run_parallel(g4, stream)
    st1 = ring.stats()
    assert _interactions(g4[0]) - ic0 == K, (
        "a warm pipelined stream of K windows must cost K host "
        "interactions: one program launch a window, nothing else"
    )
    assert st1["refills"] - st0["refills"] == K
    assert st1["doorbells"] - st0["doorbells"] == K
    assert st1["dispatches"] - st0["dispatches"] == K
    assert st1["slots"] - st0["slots"] == 4 * K
    assert st1["fallbacks"] == st0["fallbacks"]
    issue_order = [(k, i) for k in range(K) for i in range(4)]
    for r in range(world):
        assert order[r] == issue_order, f"rank {r} completed out of order"
    for rank_reqs in reqs:
        for req in rank_reqs:
            assert req.ring_resident is True
    stack = np.stack(wide)
    rs_ref = stack.sum(axis=0).reshape(world, n)
    a2a_ref = stack.reshape(world, world, n).transpose(1, 0, 2).reshape(
        world, world * n
    )
    for r in range(world):
        ar[r].sync_from_device()
        np.testing.assert_allclose(ar[r].data, np.sum(base, axis=0))
        rs[r].sync_from_device()
        np.testing.assert_allclose(rs[r].data, rs_ref[r])
        ag[r].sync_from_device()
        np.testing.assert_allclose(ag[r].data, np.concatenate(base))
        a2a[r].sync_from_device()
        np.testing.assert_allclose(a2a[r].data, a2a_ref[r])


@pytest.mark.parametrize("result_width", ["exact", "wider"])
def test_successive_windows_into_one_buffer_land_newest_last(
    g4, result_width
):
    """K pipelined windows all write the SAME result buffer with
    different values: what a read finds afterwards is the last window's
    — whether the buffer adopts by pointer swap (exact width) or by a
    deferred store into a wider root (which must layer in issue
    order)."""
    n, world, K = 32, 4, 4
    sends = [
        [
            a.create_buffer_from(
                np.full(n, float((k + 1) * (r + 1)), np.float32)
            )
            for r, a in enumerate(g4)
        ]
        for k in range(K)
    ]
    width = n if result_width == "exact" else 2 * n
    out = [
        a.create_buffer_from(np.full(width, -1.0, np.float32))
        for a in g4
    ]

    def stream(a, r):
        reqs = []
        a.begin_batch()
        try:
            for k in range(K):
                reqs.append(
                    a.allreduce(sends[k][r], out[r], n, run_async=True)
                )
                a._dispatch_pending()
        finally:
            a.end_batch()
        for req in reqs:
            assert req.wait(60)
            req.check()

    for _ in range(2):  # cold, then warm
        run_parallel(g4, stream)
        for r in range(world):
            out[r].sync_from_device()
            np.testing.assert_array_equal(out[r].data[:n], 10.0 * K)
            # the tail of a wider buffer is not the windows' to touch
            np.testing.assert_array_equal(out[r].data[n:], -1.0)


def test_sendrecv_pair_rides_ring_slots():
    """Matched SEND/RECV pairs on a world-2 gang ride ring slots (one
    slot per pair, root=src / peer=dst), in both orientations inside
    one window, beside a collective slot."""
    g = xla_group(2)
    try:
        ring = _ring(g[0])
        n = 16
        payload = [
            np.arange(n, dtype=np.float32) + 1000.0 * (r + 1)
            for r in range(2)
        ]
        send = [a.create_buffer_from(payload[r]) for r, a in enumerate(g)]
        got = [a.create_buffer(n, np.float32) for a in g]
        arr_in = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g)
        ]
        arr_out = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            peer = 1 - r
            with a.batch():
                if r == 0:
                    r1 = a.send(send[r], n, dst=peer, tag=7,
                                run_async=True)
                    r2 = a.recv(got[r], n, src=peer, tag=9,
                                run_async=True)
                else:
                    r1 = a.recv(got[r], n, src=peer, tag=7,
                                run_async=True)
                    r2 = a.send(send[r], n, dst=peer, tag=9,
                                run_async=True)
                r3 = a.allreduce(arr_in[r], arr_out[r], n, run_async=True)
            for req in (r1, r2, r3):
                assert req.wait(60)
                req.check()
            return (r1, r2, r3)

        run_parallel(g, work)  # cold
        st0 = ring.stats()
        ic0 = _interactions(g[0])
        reqs = run_parallel(g, work)
        st1 = ring.stats()
        assert _interactions(g[0]) - ic0 == 1
        assert st1["slots"] - st0["slots"] == 3
        assert (
            st1["ops"].get("SEND", 0) + st1["ops"].get("RECV", 0)
            > st0["ops"].get("SEND", 0) + st0["ops"].get("RECV", 0)
        )
        assert st1["fallbacks"].get("p2p_unpaired", 0) == st0[
            "fallbacks"
        ].get("p2p_unpaired", 0)
        for rank_reqs in reqs:
            for req in rank_reqs:
                assert req.ring_resident is True
        got[1].sync_from_device()
        np.testing.assert_array_equal(got[1].data, payload[0])
        got[0].sync_from_device()
        np.testing.assert_array_equal(got[0].data, payload[1])
        for r in range(2):
            arr_out[r].sync_from_device()
            np.testing.assert_allclose(arr_out[r].data, 3.0)
    finally:
        for a in g:
            a.deinit()


def test_torn_p2p_collective_position_fails_fast():
    """A batch position mixing a SEND with a collective (a genuine SPMD
    divergence) must fail promptly with INVALID_OPERATION on both
    ranks — never feed the collective call into the p2p channel as a
    phantom recv (which would wedge until timeout and leave a stray
    post able to steal a later real send)."""
    import time as _time

    from accl_tpu.constants import ACCLError

    g = xla_group(2)
    try:
        n = 16
        send = [
            a.create_buffer_from(np.full(n, float(r + 1), np.float32))
            for r, a in enumerate(g)
        ]
        out = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            with a.batch():
                if r == 0:
                    req = a.send(send[r], n, dst=1, tag=3, run_async=True)
                else:
                    req = a.allreduce(send[r], out[r], n, run_async=True)
            assert req.wait(60)
            try:
                req.check()
                return None
            except ACCLError as e:
                return e

        t0 = _time.monotonic()
        errs = run_parallel(g, work)
        assert _time.monotonic() - t0 < 20, "torn position hung"
        assert all(e is not None for e in errs), (
            "a torn p2p/collective position must fail on both ranks"
        )
    finally:
        for a in g:
            a.deinit()


def test_batched_cross_exchange_falls_back_to_channel():
    """The classic world-2 cross exchange — both ranks batch
    ``[send, recv]`` so positions hold {SEND,SEND} then {RECV,RECV} —
    cannot pair within a slot; it must fall back (counted
    p2p_unpaired) and still complete correctly through the shared
    tag-matched channel (pairing ACROSS positions)."""
    g = xla_group(2)
    try:
        ring = _ring(g[0])
        n = 16
        payload = [
            np.arange(n, dtype=np.float32) + 100.0 * (r + 1)
            for r in range(2)
        ]
        send = [a.create_buffer_from(payload[r]) for r, a in enumerate(g)]
        got = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            peer = 1 - r
            with a.batch():
                r1 = a.send(send[r], n, dst=peer, tag=5, run_async=True)
                r2 = a.recv(got[r], n, src=peer, tag=5, run_async=True)
            for req in (r1, r2):
                assert req.wait(60)
                req.check()

        un0 = ring.stats()["fallbacks"].get("p2p_unpaired", 0)
        run_parallel(g, work)
        assert ring.stats()["fallbacks"].get("p2p_unpaired", 0) > un0
        got[0].sync_from_device()
        np.testing.assert_array_equal(got[0].data, payload[1])
        got[1].sync_from_device()
        np.testing.assert_array_equal(got[1].data, payload[0])
    finally:
        for a in g:
            a.deinit()


def test_batched_compressed_pair_routes_to_channel():
    """A compressed SEND/RECV pair in a batch is NOT a ring slot (the
    wire-cast lanes stay on the channel): it must re-route and deliver
    with the unbatched path's compress-on-send semantics (values round
    through the wire dtype)."""
    g = xla_group(2)
    try:
        n = 16
        vals = np.arange(n, dtype=np.float32) + 0.1  # rounds in f16
        send = [a.create_buffer_from(vals.copy()) for a in g]
        got = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            peer = 1 - r
            with a.batch():
                if r == 0:
                    req = a.send(send[r], n, dst=peer, tag=11,
                                 compress_dtype=np.float16,
                                 run_async=True)
                else:
                    req = a.recv(got[r], n, src=peer, tag=11,
                                 compress_dtype=np.float16,
                                 run_async=True)
            assert req.wait(60)
            req.check()
            return req

        reqs = run_parallel(g, work)
        got[1].sync_from_device()
        np.testing.assert_array_equal(
            got[1].data, vals.astype(np.float16).astype(np.float32)
        )
        # never ring-resident: the pair rode the channel
        assert all(r.ring_resident is None for r in reqs)
    finally:
        for a in g:
            a.deinit()


def test_barrier_in_window_orders_slots(g4):
    """A BARRIER slot inside a window: the window completes with every
    slot OK and the device status words carry the slots' seqns in
    monotone encode order (the sequencer executed them in slot order —
    the ordering the barrier pins)."""
    ring = _ring(g4[0])
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    o1 = [a.create_buffer(n, np.float32) for a in g4]
    o2 = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            r1 = a.allreduce(send[r], o1[r], n, run_async=True)
            rb = a.barrier(run_async=True)
            r2 = a.bcast(o2[r] if r != 2 else send[r], n, root=2,
                         run_async=True)
        for req in (r1, rb, r2):
            assert req.wait(60)
            req.check()

    # bcast's device form is in-place (op0 is res): stage operand for
    # the root, result buffers elsewhere
    def work2(a, r):
        with a.batch():
            r1 = a.allreduce(send[r], o1[r], n, run_async=True)
            rb = a.barrier(run_async=True)
            r2 = a.allreduce(
                send[r], o2[r], n, function=ReduceFunction.MAX,
                run_async=True,
            )
        for req in (r1, rb, r2):
            assert req.wait(60)
            req.check()

    run_parallel(g4, work2)  # cold
    run_parallel(g4, work2)
    comm_id = g4[0]._world.id
    sv = ring.last_status(comm_id)
    assert sv is not None and len(sv) >= 3
    seqns = [int(s) for s in sv[:3, 0]]
    assert seqns == sorted(seqns), "slots executed out of encode order"
    assert all(int(c) == 1 for c in sv[:3, 1])  # CMDRING_ST_OK
    for r in range(4):
        o1[r].sync_from_device()
        np.testing.assert_allclose(o1[r].data, 10.0)
        o2[r].sync_from_device()
        np.testing.assert_allclose(o2[r].data, 4.0)


def test_window_replay_status_deterministic(g4):
    """The same encoded window replays to identical device status
    words (seqn-relative): determinism of the decode loop's status
    path across runs of one session."""
    ring = _ring(g4[0])
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.float32) for a in g4]
    wide = [
        a.create_buffer_from(np.ones(4 * n, np.float32))
        for a in g4
    ]
    rs = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[r], n, run_async=True),
                a.reduce_scatter(wide[r], rs[r], n, run_async=True),
                a.barrier(run_async=True),
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()

    comm_id = g4[0]._world.id
    run_parallel(g4, work)
    sv1 = ring.last_status(comm_id)
    run_parallel(g4, work)
    sv2 = ring.last_status(comm_id)
    assert sv1 is not None and sv2 is not None
    # retcodes identical; seqns advance by exactly the window length
    np.testing.assert_array_equal(sv1[:, 1], sv2[:, 1])
    np.testing.assert_array_equal(sv2[:, 0] - sv1[:, 0], 3)


def test_wraparound_and_soft_reset_under_mixed_windows(g4):
    """Ring wrap-around and soft_reset teardown under the grown opcode
    mix: heads wrap with mixed windows in the ring, reset realigns
    seqn at 0, and the session re-arms cleanly after."""
    ring = _ring(g4[0])
    depth = ring.depth
    n = 16
    send = [
        a.create_buffer_from(np.full(n, float(r + 1), np.float32))
        for r, a in enumerate(g4)
    ]
    wide = [a.create_buffer_from(np.ones(4 * n, np.float32)) for a in g4]
    out = [a.create_buffer(n, np.float32) for a in g4]
    rs = [a.create_buffer(n, np.float32) for a in g4]
    ag = [a.create_buffer(4 * n, np.float32) for a in g4]

    def window(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[r], n, run_async=True),
                a.reduce_scatter(wide[r], rs[r], n, run_async=True),
                a.allgather(send[r], ag[r], n, run_async=True),
            ]
        for req in reqs:
            assert req.wait(60)
            req.check()

    wraps0 = ring.stats()["wraps"]
    # 3-slot windows from wherever earlier tests left the head: 3 and
    # the depth share no factor, so `depth` rounds start once at every
    # residue and one of them must straddle the ring boundary
    assert depth % 3
    rounds = depth
    for _ in range(rounds):
        run_parallel(g4, window)
    st = ring.stats()
    assert st["wraps"] > wraps0, "head never wrapped under mixed windows"
    comm_id = g4[0]._world.id
    assert ring._sessions[comm_id].seqn >= rounds * 3

    resets0 = st["resets"]
    run_parallel(g4, lambda a, r: a.soft_reset())
    st = ring.stats()
    assert st["resets"] > resets0
    assert comm_id not in ring._sessions  # teardown: session abandoned

    run_parallel(g4, window)  # the ring re-arms after the reset
    assert ring._sessions[comm_id].seqn == 3  # realigned at 0, then 3
    for r in range(4):
        out[r].sync_from_device()
        np.testing.assert_allclose(out[r].data, 10.0)
        rs[r].sync_from_device()
        np.testing.assert_allclose(rs[r].data, 4.0)


def test_f16_window_rides_ring_bit_accurate():
    """The f16 satellite: f16 windows ride the ring (no host-dispatch
    fallback) and the sequencer's fold is bit-accurate against the
    host path on exactly-representable values (integer-valued f16
    sums are exact in every association order, so any correct path
    must agree BITWISE)."""
    g = xla_group(2)
    try:
        ring = _ring(g[0])
        n = 64
        vals = [
            np.arange(n, dtype=np.float16) + (r + 1)
            for r in range(2)
        ]
        send = [a.create_buffer_from(vals[r]) for r, a in enumerate(g)]
        out = [a.create_buffer(n, np.float16) for a in g]

        def ring_work(a, r):
            with a.batch():
                reqs = [
                    a.allreduce(send[r], out[r], n, run_async=True)
                    for _ in range(2)
                ]
            for req in reqs:
                assert req.wait(60)
                req.check()
            return reqs

        run_parallel(g, ring_work)  # cold
        st0 = ring.stats()
        reqs = run_parallel(g, ring_work)
        st1 = ring.stats()
        assert st1["slots"] - st0["slots"] == 2, "f16 window fell back"
        for reason in ("mosaic_dtype", "mixed_dtype", "unsupported_op"):
            assert st1["fallbacks"].get(reason, 0) == st0[
                "fallbacks"
            ].get(reason, 0)
        for rank_reqs in reqs:
            for req in rank_reqs:
                assert req.ring_resident is True
        ref = (vals[0] + vals[1]).astype(np.float16)  # exact: integers
        for r in range(2):
            out[r].sync_from_device()
            np.testing.assert_array_equal(out[r].data, ref)
        # host path (ring off) agrees bitwise
        host_out = [a.create_buffer(n, np.float16) for a in g]
        saved = ring.enabled
        ring.enabled = False
        try:
            def host_work(a, r):
                req = a.allreduce(send[r], host_out[r], n, run_async=True)
                assert req.wait(60)
                req.check()

            run_parallel(g, host_work)
        finally:
            ring.enabled = saved
        for r in range(2):
            host_out[r].sync_from_device()
            np.testing.assert_array_equal(host_out[r].data, ref)
    finally:
        for a in g:
            a.deinit()

# ---------------------------------------------------------------------------
# fused compute slots: kernel-initiated collectives (the accl_hls analog)
# ---------------------------------------------------------------------------


def test_fused_slot_codec_round_trip():
    """Fused opcodes ride the same 11-word slot with the epilogue
    scalar in the Q16.16 fparam word — exact for the power-of-two
    alphas/lrs/scales that dominate training."""
    for fuse, opcode in (
        (FusedCompute.MATMUL_RS, CmdOpcode.FUSED_MATMUL_RS),
        (FusedCompute.APPLY, CmdOpcode.FUSED_APPLY),
        (FusedCompute.ATTN_HOP, CmdOpcode.FUSED_ATTN_HOP),
    ):
        words = encode_slot(
            7, opcode, 64, dtype=2, root=1, nseg=1, peer=1,
            fparam=encode_fparam(0.125),
        )
        d = decode_slot(words)
        assert d["opcode"] is opcode, fuse
        assert decode_fparam(d["fparam"]) == 0.125  # exact: power of two
    # Q16.16 exactness + clamp behavior
    for exact in (1.0, -1.0, 0.5, 2.0, 0.0078125, -0.25):
        assert decode_fparam(encode_fparam(exact)) == exact
    assert abs(decode_fparam(encode_fparam(0.1)) - 0.1) < 1e-4
    assert encode_fparam(1e9) == 2 ** 31 - 1  # clamped, never wraps
    assert encode_fparam(-1e9) == -(2 ** 31)


def test_ring_widths_fused_geometry():
    """The width RELATIONS that classify fused slots: APPLY packs the
    param shard behind the grads (in == out*(size+1)); ATTN_HOP packs
    q behind kv (in == 2*out); MATMUL_RS keeps the plain RS geometry."""
    assert ring_widths(
        Operation.REDUCE_SCATTER, 8, 4, fuse=FusedCompute.MATMUL_RS
    ) == (32, 8)
    assert ring_widths(
        Operation.ALLREDUCE, 8, 4, fuse=FusedCompute.APPLY
    ) == (40, 8)
    assert ring_widths(
        Operation.ALLREDUCE, 8, 4, fuse=FusedCompute.ATTN_HOP
    ) == (16, 8)


def test_fused_eligibility_reasons():
    """The ONE fused-eligibility predicate and its counted reasons —
    the planner refuses exactly what the lowerings cannot sequence."""
    f32 = np.float32
    ok = fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 40, f32
    )
    assert ok is None
    assert fused_slot_eligible(
        99, Operation.ALLREDUCE, 4, 8, 40, f32
    ) == "unknown_fuse"
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.REDUCE_SCATTER, 4, 8, 40, f32
    ) == "fused_base_op"
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 1, 8, 16, f32
    ) == "fused_world_too_small"
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 40, np.int32
    ) == "fused_dtype"
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 32, f32
    ) == "fused_operand_width"
    assert fused_slot_eligible(
        FusedCompute.APPLY, Operation.ALLREDUCE, 4, 8, 40, f32,
        compressed=True,
    ) == "fused_compressed"


def test_fused_warm_window_counter_asserted(g4):
    """THE tentpole counter-assert: a warm window mixing all three
    fused opcodes is exactly ONE host refill interaction, every slot
    ring-resident with zero fused fallbacks, and the epilogues compute
    on-device: scaled reduce-scatter of GEMM partials, optimizer
    apply-on-arrival, and the ring-attention hop partial."""
    ring = _ring(g4[0])
    world, n, lr, scale = 4, 16, 0.25, 0.5
    parts = [
        np.arange(world * n, dtype=np.float32) + 10.0 * r
        for r in range(world)
    ]
    grads = [
        np.arange(world * n, dtype=np.float32) * 0.1 + r
        for r in range(world)
    ]
    params = [np.full(n, 100.0 + r, np.float32) for r in range(world)]
    kv = [np.arange(n, dtype=np.float32) + 5.0 * r for r in range(world)]
    q = [np.arange(n, dtype=np.float32) * 0.5 + r for r in range(world)]
    mm_send = [a.create_buffer_from(parts[r]) for r, a in enumerate(g4)]
    mm_out = [a.create_buffer(n, np.float32) for a in g4]
    ap_send = [
        a.create_buffer_from(np.concatenate([grads[r], params[r]]))
        for r, a in enumerate(g4)
    ]
    ap_out = [a.create_buffer(n, np.float32) for a in g4]
    hp_send = [
        a.create_buffer_from(np.concatenate([kv[r], q[r]]))
        for r, a in enumerate(g4)
    ]
    hp_out = [a.create_buffer(n, np.float32) for a in g4]

    def work(a, r):
        with a.batch():
            r1 = a.fused_matmul_reduce_scatter(
                mm_send[r], mm_out[r], n, scale=scale, run_async=True
            )
            r2 = a.fused_apply(
                ap_send[r], ap_out[r], n, lr=lr, run_async=True
            )
            r3 = a.fused_attn_hop(
                hp_send[r], hp_out[r], hop=1, count=n, scale=2.0,
                run_async=True,
            )
        reqs = (r1, r2, r3)
        for req in reqs:
            assert req.wait(60)
            req.check()
        return reqs

    run_parallel(g4, work)  # cold: compiles the fused window program
    st0 = ring.stats()
    ic0 = _interactions(g4[0])
    reqs = run_parallel(g4, work)
    st1 = ring.stats()
    assert _interactions(g4[0]) - ic0 == 1, (
        "a warm fused window of 3 compute slots must be exactly ONE "
        "host refill interaction — compute never re-enters the host"
    )
    assert st1["refills"] - st0["refills"] == 1
    assert st1["slots"] - st0["slots"] == 3
    for op in ("FUSED_MATMUL_RS", "FUSED_APPLY", "FUSED_ATTN_HOP"):
        assert st1["ops"].get(op, 0) - st0["ops"].get(op, 0) == 1, op
    for reason in ("unsupported_op", "compressed", "fused_decomposed"):
        assert st1["fallbacks"].get(reason, 0) == (
            st0["fallbacks"].get(reason, 0)
        ), reason
    for rank_reqs in reqs:
        for req in rank_reqs:
            assert req.ring_resident is True
    mm_ref = scale * np.sum(parts, axis=0).reshape(world, n)
    gsum = np.sum(grads, axis=0).reshape(world, n)
    for r in range(world):
        mm_out[r].sync_from_device()
        np.testing.assert_allclose(mm_out[r].data, mm_ref[r], rtol=1e-6)
        ap_out[r].sync_from_device()
        np.testing.assert_allclose(
            ap_out[r].data, params[r] - lr * gsum[r], rtol=1e-6
        )
        hp_out[r].sync_from_device()
        np.testing.assert_allclose(
            hp_out[r].data, 2.0 * q[r] * kv[(r - 1) % world], rtol=1e-6
        )


def test_fused_ineligible_decomposes_counted(g4):
    """A fused call the ring cannot sequence (int operand) NEVER runs
    the plain base op: it decomposes on host with a counted
    ``fused_decomposed`` fallback and bit-exact epilogue semantics."""
    ring = _ring(g4[0])
    world, n = 4, 8
    grads = [
        (np.arange(world * n) + r).astype(np.int32) for r in range(world)
    ]
    params = [np.full(n, 1000 * (r + 1), np.int32) for r in range(world)]
    send = [
        a.create_buffer_from(np.concatenate([grads[r], params[r]]))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.int32) for a in g4]

    def work(a, r):
        with a.batch():
            req = a.fused_apply(send[r], out[r], n, lr=2.0, run_async=True)
        assert req.wait(60)
        req.check()
        return req

    slots0 = ring.stats()["slots"]
    dec0 = ring.stats()["fallbacks"].get("fused_decomposed", 0)
    reqs = run_parallel(g4, work)
    st = ring.stats()
    assert st["fallbacks"].get("fused_decomposed", 0) > dec0
    assert st["slots"] == slots0  # nothing rode the ring
    for req in reqs:
        assert req.ring_resident is None
    gsum = np.sum(np.stack(grads), axis=0).reshape(world, n)
    for r in range(world):
        out[r].sync_from_device()
        np.testing.assert_array_equal(
            out[r].data, params[r] - 2 * gsum[r]
        )  # exact: integer arithmetic, lr=2.0 exact in Q16.16


# ---------------------------------------------------------------------------
# one form: nothing selects, so nothing that used to select is read
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name,value", [
    ("ACCL_CMDRING_LOWERING", "pallas"),
    ("ACCL_CMDRING_RUN_WINDOWS", "3"),
    ("ACCL_CMDRING_LINGER_MS", "900"),
])
def test_retired_environment_names_change_nothing(monkeypatch, name, value):
    """The three names that chose between window forms are read by
    nothing: with one set, a fresh gang reports the same ring and lowers
    the same window program, and a window still rides it."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from accl_tpu.cmdring import WindowShape
    from accl_tpu.ops import cmdring as devring
    from accl_tpu.ops.driver import AXIS, _mesh_key

    def observe():
        devring._windows_program.cache_clear()
        g = xla_group(2)
        try:
            ring = _ring(g[0])
            n = 16
            send = [
                a.create_buffer_from(np.full(n, r + 1.0, np.float32))
                for r, a in enumerate(g)
            ]
            out = [a.create_buffer(n, np.float32) for a in g]

            def window(a, r):
                with a.batch():
                    reqs = [
                        a.allreduce(send[r], out[r], n, run_async=True),
                        a.bcast(send[r], n, root=1, run_async=True),
                    ]
                for q in reqs:
                    assert q.wait(60)
                    q.check()

            run_parallel(g, window)
            st = ring.stats()
            out[0].sync_from_device()
            np.testing.assert_array_equal(out[0].data, 3.0)
            mesh = g[0].engine.gang.submesh(g[0].comm)
            shape = WindowShape(2, [n, n], [n, n], [None, None], np.float32)
            sh = NamedSharding(mesh, PartitionSpec(AXIS))
            args = [jax.ShapeDtypeStruct(
                (2 * 2, CMDRING_SLOT_WORDS), np.int32, sharding=sh
            )] + [
                jax.ShapeDtypeStruct((2 * n,), np.float32, sharding=sh)
            ] * 2
            text = devring._windows_program(
                _mesh_key(mesh), shape.key(), 1
            ).lower(*args).as_text()
        finally:
            for a in g:
                a.deinit()
        for volatile in ("windows", "window_latency_sum_us",
                         "window_latency_log2_us"):
            st.pop(volatile)
        return st, text

    monkeypatch.delenv(name, raising=False)
    plain = observe()
    monkeypatch.setenv(name, value)
    assert observe() == plain
    assert plain[0]["dispatches"] == plain[0]["refills"] == 1
    assert "all_gather" in plain[1]


# ---------------------------------------------------------------------------
# chaos: fused windows fail fast, recover via soft_reset — never hang
# ---------------------------------------------------------------------------


def _fused_apply_buffers(g4, world=4, n=8):
    grads = [
        np.arange(world * n, dtype=np.float32) + r for r in range(world)
    ]
    params = [np.full(n, 50.0 + r, np.float32) for r in range(world)]
    send = [
        a.create_buffer_from(np.concatenate([grads[r], params[r]]))
        for r, a in enumerate(g4)
    ]
    out = [a.create_buffer(n, np.float32) for a in g4]
    ref = [
        params[r] - 0.5 * np.sum(grads, axis=0).reshape(world, n)[r]
        for r in range(world)
    ]
    return send, out, ref


def _drive_fused(g4, send, out, n=8):
    """One fused_apply window per rank; returns {rank: ACCLError}."""
    import threading
    import time as _time

    from accl_tpu import ACCLError

    errs = {}

    def runner(a, r):
        try:
            with a.batch():
                req = a.fused_apply(
                    send[r], out[r], n, lr=0.5, run_async=True
                )
            assert req.wait(60)
            req.check()
        except ACCLError as e:
            errs[r] = e

    threads = [
        threading.Thread(
            target=runner, args=(a, i), name=f"accl-fused-rank{i}",
            daemon=True,
        )
        for i, a in enumerate(g4)
    ]
    t0 = _time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert all(not t.is_alive() for t in threads), "fused window hung"
    return errs, _time.monotonic() - t0


@pytest.mark.chaos
def test_chaos_corrupt_fused_window_fails_fast_soft_reset_recovers(g4):
    """A corrupt fault mid-fused-window poisons the refill's opcode
    word: the sequencer reports BAD_OP and the slot's requests fail
    INVALID_OPERATION fast — with the flight-recorder tail — never a
    hang; soft_reset then recovers the ring for a clean fused window."""
    from accl_tpu import ErrorCode, FaultPlan, FaultRule
    from accl_tpu import contract as contract_mod

    ring = _ring(g4[0])
    n = 8
    send, out, ref = _fused_apply_buffers(g4, n=n)
    _drive_fused(g4, send, out, n=n)  # cold: compile before the chaos
    contract_mod.install_fault_plan(FaultPlan(
        rules=[FaultRule(
            action="corrupt", msg_type="RING", nth=1, count=1,
        )],
        seed=11,
    ))
    try:
        errs, elapsed = _drive_fused(g4, send, out, n=n)
        assert elapsed < 15, "corrupted fused window took the slow path"
        assert errs, "poisoned fused window completed without error"
        for e in errs.values():
            assert e.code == ErrorCode.INVALID_OPERATION
            assert "flight_recorder" in e.details
        assert ring.stats()["chaos_faults"].get("corrupt", 0) >= 1
    finally:
        contract_mod.install_fault_plan(None)
    run_parallel(g4, lambda a, r: a.soft_reset())
    errs, _ = _drive_fused(g4, send, out, n=n)
    assert not errs, f"fused window failed after soft_reset: {errs}"
    for r in range(4):
        out[r].sync_from_device()
        np.testing.assert_allclose(out[r].data, ref[r], rtol=1e-6)


@pytest.mark.chaos
def test_chaos_delay_fused_window_bounded_and_correct(g4):
    """A delay fault on the fused refill is BOUNDED (the ring clamps
    the injected sleep) and the window still completes bit-correct —
    delay perturbs timing, never results."""
    from accl_tpu import FaultPlan, FaultRule
    from accl_tpu import contract as contract_mod

    ring = _ring(g4[0])
    n = 8
    send, out, ref = _fused_apply_buffers(g4, n=n)
    _drive_fused(g4, send, out, n=n)  # cold
    delays0 = ring.stats()["chaos_faults"].get("delay", 0)
    contract_mod.install_fault_plan(FaultPlan(
        rules=[FaultRule(
            action="delay", msg_type="RING", nth=1, count=1,
            delay_s=0.3,
        )],
        seed=12,
    ))
    try:
        errs, elapsed = _drive_fused(g4, send, out, n=n)
    finally:
        contract_mod.install_fault_plan(None)
    assert not errs, f"delayed fused window failed: {errs}"
    assert elapsed < 15
    assert ring.stats()["chaos_faults"].get("delay", 0) > delays0
    for r in range(4):
        out[r].sync_from_device()
        np.testing.assert_allclose(out[r].data, ref[r], rtol=1e-6)


# ---------------------------------------------------------------------------
# model zoo opt-in: the fuse-hint helpers ride real training shapes
# ---------------------------------------------------------------------------


def test_model_zoo_fused_helpers_ride_ring(g4):
    """transformer.fused_optimizer_step and
    ring_attention.fused_hop_partial opt model code into fused slots
    through the facade — warm steps stay at the refill count with the
    documented epilogue numerics."""
    from accl_tpu.models.ring_attention import fused_hop_partial
    from accl_tpu.models.transformer import fused_optimizer_step

    ring = _ring(g4[0])
    world, n, lr = 4, 16, 0.125
    buckets = 2
    grads = [
        [
            np.arange(world * n, dtype=np.float32) * 0.01 + b + r
            for b in range(buckets)
        ]
        for r in range(world)
    ]
    params = [
        [np.full(n, 10.0 * (b + 1) + r, np.float32) for b in range(buckets)]
        for r in range(world)
    ]

    def opt_step(a, r):
        return fused_optimizer_step(a, grads[r], params[r], lr=lr)

    run_parallel(g4, opt_step)  # cold
    st0 = ring.stats()
    ic0 = _interactions(g4[0])
    outs = run_parallel(g4, opt_step)
    st1 = ring.stats()
    assert _interactions(g4[0]) - ic0 == 1  # all buckets, one refill
    assert st1["refills"] - st0["refills"] == 1
    assert st1["ops"].get("FUSED_APPLY", 0) - st0["ops"].get(
        "FUSED_APPLY", 0
    ) == buckets
    for r in range(world):
        gsum = np.sum(
            [grads[rr] for rr in range(world)], axis=0
        )  # (buckets, world*n)
        for b in range(buckets):
            ref = params[r][b] - lr * gsum[b].reshape(world, n)[r]
            np.testing.assert_allclose(outs[r][b], ref, rtol=1e-6)

    kv = [np.arange(n, dtype=np.float32) + r for r in range(world)]
    q = [np.arange(n, dtype=np.float32) * 0.25 + r for r in range(world)]

    def hop(a, r):
        return fused_hop_partial(a, kv[r], q[r], hop=1, scale=4.0)

    run_parallel(g4, hop)  # cold
    st0 = ring.stats()
    outs = run_parallel(g4, hop)
    st1 = ring.stats()
    assert st1["ops"].get("FUSED_ATTN_HOP", 0) - st0["ops"].get(
        "FUSED_ATTN_HOP", 0
    ) == 1
    for r in range(world):
        np.testing.assert_allclose(
            outs[r], 4.0 * q[r] * kv[(r - 1) % world], rtol=1e-6
        )


# ---------------------------------------------------------------------------
# a warm window's control words make no trip of their own: slot words the
# chips already hold are not put again, the status copy is asked for at
# launch.  Bytes and results, never a time.
# ---------------------------------------------------------------------------


def _slot_counts(ring):
    st = ring.stats()
    return st["slot_hits"], st["slot_puts"]


def _ar_window(g, send, out, n, function=ReduceFunction.SUM, slots=3):
    """One window of ``slots`` allreduces a rank; waits and checks."""
    def work(a, r):
        with a.batch():
            reqs = [
                a.allreduce(send[r], out[k][r], n, function=function,
                            run_async=True)
                for k in range(slots)
            ]
        for q in reqs:
            assert q.wait(60)
            q.check()

    run_parallel(g, work)


def _ar_buffers(g, n, slots=3):
    vals = [np.arange(n, dtype=np.float32) + 7.0 * r for r in range(len(g))]
    # a copy: the buffer wraps the array it is made from
    send = [a.create_buffer_from(vals[r].copy()) for r, a in enumerate(g)]
    out = [[a.create_buffer(n, np.float32) for a in g] for _ in range(slots)]
    return vals, send, out


def test_identical_warm_windows_put_their_slot_words_once(g4):
    """K windows of the same slots: one put, K - 1 hits; every result is
    numpy's on THAT window's operands; the status words carry the seqns
    the host encoded (the device echoes them window-relative, the host
    puts the window's base back) and advance by the window's length."""
    ring = _ring(g4[0])
    comm_id = g4[0]._world.id
    n, slots, K = 24, 3, 5
    vals, send, out = _ar_buffers(g4, n, slots)
    ring._kept_slots.clear()
    hits0, puts0 = _slot_counts(ring)
    disp0 = ring.stats()["dispatches"]
    prev = None
    for w in range(K):
        for r in range(4):  # new operand BYTES, the same slot words
            send[r].data[:] = vals[r] + w
            send[r].sync_to_device()
        _ar_window(g4, send, out, n, slots=slots)
        ref = np.sum([v + w for v in vals], axis=0)
        for k in range(slots):
            for r in range(4):
                out[k][r].sync_from_device()
                np.testing.assert_array_equal(out[k][r].data, ref)
        status = ring.last_status(comm_id)
        logged = ring.window_log(1)[0]["slots"]
        assert [s["seqn"] for s in logged] == list(status[:, 0])
        assert [s["retcode"] for s in logged] == [CMDRING_ST_OK] * slots
        np.testing.assert_array_equal(np.diff(status[:, 0]), 1)
        if prev is not None:
            np.testing.assert_array_equal(status[:, 0] - prev[:, 0], slots)
        prev = status
    hits1, puts1 = _slot_counts(ring)
    assert (puts1 - puts0, hits1 - hits0) == (1, K - 1)
    assert ring.stats()["dispatches"] - disp0 == K
    # what the chips hold is window-relative: 0 .. n-1, whatever the base
    (kept,) = ring._kept_slots._kept.values()
    words = np.asarray(kept.addressable_shards[0].data)
    assert list(words[:, CMDRING_FIELDS["seqn"]]) == list(range(slots))


def _differs_in_function(g):
    n = 16
    vals, send, out = _ar_buffers(g, n)

    def check():
        for o in out[0]:
            o.sync_from_device()
            np.testing.assert_array_equal(o.data, np.max(vals, axis=0))

    return (
        lambda: _ar_window(g, send, out, n, ReduceFunction.SUM),
        lambda: _ar_window(g, send, out, n, ReduceFunction.MAX),
        check,
    )


def _differs_in_count(g):
    wide, n = 16, 8
    vals, send, out = _ar_buffers(g, wide, slots=1)

    def check():
        for o in out[0]:
            o.sync_from_device()
            np.testing.assert_array_equal(
                o.data[:n], np.sum(vals, axis=0)[:n]
            )

    return (
        lambda: _ar_window(g, send, out, wide, slots=1),
        lambda: _ar_window(g, send, out, n, slots=1),
        check,
    )


def _differs_in_root(g):
    n = 16
    vals = [np.full(n, 30.0 + r, np.float32) for r in range(len(g))]
    bufs = [a.create_buffer_from(vals[r]) for r, a in enumerate(g)]

    def window(root):
        def work(a, r):
            bufs[r].data[:] = vals[r]
            bufs[r].sync_to_device()
            with a.batch():
                q = a.bcast(bufs[r], n, root=root, run_async=True)
            assert q.wait(60)
            q.check()

        return lambda: run_parallel(g, work)

    def check():
        for b in bufs:
            b.sync_from_device()
            np.testing.assert_array_equal(b.data, vals[3])

    return window(1), window(3), check


def _differs_in_peer(g):
    """A pair slot's ``root`` (src) and ``peer`` (dst) swap."""
    n = 16
    vals = [np.arange(n, dtype=np.float32) + 100.0 * (r + 1)
            for r in range(2)]
    send = [a.create_buffer_from(vals[r]) for r, a in enumerate(g)]
    got = [a.create_buffer(n, np.float32) for a in g]

    def window(src):
        def work(a, r):
            with a.batch():
                if r == src:
                    q = a.send(send[r], n, dst=1 - r, tag=5, run_async=True)
                else:
                    q = a.recv(got[r], n, src=1 - r, tag=5, run_async=True)
            assert q.wait(60)
            q.check()

        return lambda: run_parallel(g, work)

    def check():
        got[0].sync_from_device()
        np.testing.assert_array_equal(got[0].data, vals[1])

    return window(0), window(1), check


def _differs_in_fparam(g):
    n = 8
    send, out, _ = _fused_apply_buffers(g, n=n)
    grads = np.sum(
        [np.arange(4 * n, dtype=np.float32) + r for r in range(4)], axis=0
    ).reshape(4, n)

    def window(lr):
        def work(a, r):
            with a.batch():
                q = a.fused_apply(send[r], out[r], n, lr=lr, run_async=True)
            assert q.wait(60)
            q.check()

        return lambda: run_parallel(g, work)

    def check():
        for r in range(4):
            out[r].sync_from_device()
            np.testing.assert_allclose(
                out[r].data, (50.0 + r) - 0.25 * grads[r], rtol=1e-6
            )

    return window(0.5), window(0.25), check


def _differs_in_flags(g):
    """A compressed lane's stochastic-rounding seed rides ``flags`` and
    advances a call: the same operands, another seed, other roundings."""
    n = 512
    rng = np.random.default_rng(5)
    vals = [rng.standard_normal(n).astype(np.float32) for _ in g]
    send = [a.create_buffer_from(vals[r]) for r, a in enumerate(g)]
    out = [a.create_buffer(n, np.float32) for a in g]
    seen = []

    def window():
        def work(a, r):
            with a.batch():
                q = a.allreduce(send[r], out[r], n, compress_dtype="int8",
                                run_async=True)
            assert q.wait(60)
            q.check()

        run_parallel(g, work)
        out[0].sync_from_device()
        seen.append(out[0].data.copy())

    def check():
        ref = np.sum(vals, axis=0)
        assert np.max(np.abs(seen[-1] - ref)) < 0.25  # the int8 lane's
        # a stale hit would round by the OLD seed: bit for bit the old sum
        assert not np.array_equal(seen[-1], seen[-2])

    return window, window, check


@pytest.mark.parametrize("field,world,differ", [
    ("function", 4, _differs_in_function),
    ("count", 4, _differs_in_count),
    ("root", 4, _differs_in_root),
    ("peer", 2, _differs_in_peer),
    ("fparam", 4, _differs_in_fparam),
    ("flags", 4, _differs_in_flags),
])
def test_no_false_hit_on_a_window_that_differs_in_one_field(
    field, world, differ
):
    """A warm window, then one whose slot words differ in ``field``
    alone: it is a put, and its result is the NEW window's.  (A count
    is also a width, so through the facade it changes the window's
    shape too; ``test_kept_slots_key_is_every_word_but_seqn`` holds the
    words alone.)"""
    g = xla_group(world)
    try:
        ring = _ring(g[0])
        first, second, check = differ(g)
        first()
        if field != "flags":  # a churning seed misses every window
            hits0, _ = _slot_counts(ring)
            first()
            assert _slot_counts(ring)[0] - hits0 == 1
        hits0, puts0 = _slot_counts(ring)
        second()
        hits1, puts1 = _slot_counts(ring)
        assert (puts1 - puts0, hits1 - hits0) == (1, 0)
        assert ring.stats()["fallbacks"] == {}
        check()
    finally:
        for a in g:
            a.deinit()


def _mesh4():
    from accl_tpu.ops.driver import make_mesh

    return make_mesh(4)


def _rows(base=0, **fields):
    rows = np.stack([
        encode_slot(base + k, CmdOpcode.ALLREDUCE, 64,
                    function=ReduceFunction.SUM)
        for k in range(3)
    ])
    for name, value in fields.items():
        rows[1, CMDRING_FIELDS[name]] = value
    return rows


@pytest.mark.parametrize(
    "field", [f for f in CMDRING_FIELDS if f != "seqn"]
)
def test_kept_slots_key_is_every_word_but_seqn(field):
    """``seqn`` alone may differ between a window and the kept words
    that serve it; any other word makes another key, and what is put is
    the NEW words."""
    from accl_tpu.ops.cmdring import KeptSlots

    mesh, kept = _mesh4(), KeptSlots()
    first = kept.on_device(_rows(), mesh)
    assert kept.on_device(_rows(base=40), mesh) is first  # seqn: a hit
    assert (kept.hits, kept.puts) == (1, 1)
    other = _rows(base=43, **{field: 5})
    dev = kept.on_device(other, mesh)
    assert dev is not first and (kept.hits, kept.puts) == (1, 2)
    want = other.copy()
    want[:, CMDRING_FIELDS["seqn"]] = np.arange(3)
    for shard in dev.addressable_shards:  # every chip holds the words
        np.testing.assert_array_equal(np.asarray(shard.data), want)
    assert kept.on_device(_rows(base=7), mesh) is first  # still kept


def test_kept_slots_are_bounded_and_drop_the_least_recently_sent():
    from accl_tpu.ops import cmdring as devring

    mesh, kept = _mesh4(), devring.KeptSlots()
    extra = 5
    for i in range(devring.KEPT_WINDOWS + extra):
        kept.on_device(_rows(count=100 + i), mesh)
        kept.on_device(_rows(count=100), mesh)  # window 0 stays in use
    assert len(kept) == devring.KEPT_WINDOWS
    puts = kept.puts
    kept.on_device(_rows(count=100), mesh)          # kept: used all along
    kept.on_device(_rows(count=100 + devring.KEPT_WINDOWS + extra - 1), mesh)
    assert kept.puts == puts
    kept.on_device(_rows(count=101), mesh)          # the oldest: gone
    assert kept.puts == puts + 1 and len(kept) == devring.KEPT_WINDOWS


@pytest.mark.parametrize("how", ["reset", "soft_reset"])
def test_reset_forgets_the_kept_slot_words(g4, how):
    ring = _ring(g4[0])
    n = 16
    vals, send, out = _ar_buffers(g4, n)
    _ar_window(g4, send, out, n)
    hits0, _ = _slot_counts(ring)
    _ar_window(g4, send, out, n)
    assert _slot_counts(ring)[0] - hits0 == 1 and len(ring._kept_slots) > 0
    if how == "reset":
        ring.reset()
    else:
        run_parallel(g4, lambda a, r: a.soft_reset())
    assert len(ring._kept_slots) == 0
    hits0, puts0 = _slot_counts(ring)
    _ar_window(g4, send, out, n)
    assert _slot_counts(ring) == (hits0, puts0 + 1)
    for o in out[0]:
        o.sync_from_device()
        np.testing.assert_array_equal(o.data, np.sum(vals, axis=0))


@pytest.mark.chaos
def test_chaos_corrupt_after_warm_up_is_a_put_then_a_hit(g4):
    """The poisoned opcode is another content: that window is put and
    judged BAD_OP on the device (its first slot INVALID_OPERATION, fast);
    the next clean window finds its own words still kept, and is OK."""
    from accl_tpu import ACCLError, ErrorCode, FaultPlan, FaultRule
    from accl_tpu import contract as contract_mod

    ring = _ring(g4[0])
    comm_id = g4[0]._world.id
    n = 16
    vals, send, out = _ar_buffers(g4, n, slots=2)
    _ar_window(g4, send, out, n, slots=2)
    _ar_window(g4, send, out, n, slots=2)
    hits0, puts0 = _slot_counts(ring)
    contract_mod.install_fault_plan(FaultPlan(
        rules=[FaultRule(action="corrupt", msg_type="RING", nth=1, count=1)],
        seed=11,
    ))
    try:
        with pytest.raises(ACCLError) as err:
            _ar_window(g4, send, out, n, slots=2)
        assert err.value.code == ErrorCode.INVALID_OPERATION
    finally:
        contract_mod.install_fault_plan(None)
    assert _slot_counts(ring) == (hits0, puts0 + 1)
    status = ring.last_status(comm_id)
    # the device's own words: BAD_OP on the poisoned slot alone
    assert list(status[:, 1]) == [CMDRING_ST_BAD_OP, CMDRING_ST_OK]
    _ar_window(g4, send, out, n, slots=2)
    assert _slot_counts(ring) == (hits0 + 1, puts0 + 1)
    assert list(ring.last_status(comm_id)[:, 1]) == [CMDRING_ST_OK] * 2
    for k in range(2):
        for o in out[k]:
            o.sync_from_device()
            np.testing.assert_array_equal(o.data, np.sum(vals, axis=0))


def test_the_waiter_reads_the_shard_whose_copy_was_asked_for(
    g4, monkeypatch
):
    """``run_windows`` asks for ONE status shard's copy to the host and
    hands on THAT array; the drainer reads that object, once a window
    (``shard.data`` makes a new array each time, and a new array has no
    copy under way)."""
    from jax._src import array as jarray

    from accl_tpu.ops import cmdring as devring

    asked, read = [], []
    ask = jarray.ArrayImpl.copy_to_host_async
    view = devring.status_view

    def recording_ask(self):
        asked.append(self)
        return ask(self)

    def recording_view(shard):
        read.append(shard)
        assert shard.shape == (3, 2) and len(shard.sharding.device_set) == 1
        return view(shard)

    n = 16
    vals, send, out = _ar_buffers(g4, n)
    _ar_window(g4, send, out, n)  # warm: nothing else moves to the host
    monkeypatch.setattr(jarray.ArrayImpl, "copy_to_host_async", recording_ask)
    monkeypatch.setattr(devring, "status_view", recording_view)
    for _ in range(2):
        _ar_window(g4, send, out, n)
    assert len(read) == 2 == len(asked)
    assert all(r is a for r, a in zip(read, asked))


#: sha256 of the lowered text (543 lines) of the window the sweep's
#: ``--rehearse`` issues (eight slots of 2,048 bytes, float32, the
#: four-device CPU mesh), as PR 29 read it and as the parent of PR 51
#: (fd6a099) lowers it: the host stopped sending words the chips hold
#: and asks for the status sooner; the program is the one it was
REHEARSAL_WINDOW_TEXT = (
    "2dd482c5a4747056e6c28a4cd896a9f1a9a1a6d32824a89f7c3da7f8d58ba5e1"
)


def test_window_program_text_is_the_parents():
    import hashlib

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from accl_tpu.cmdring import WindowShape
    from accl_tpu.ops import cmdring as devring
    from accl_tpu.ops.driver import AXIS, _mesh_key

    world, n = 4, 2048 // 4
    ops = [Operation.ALLREDUCE, Operation.ALLREDUCE,
           Operation.REDUCE_SCATTER, Operation.ALLGATHER] * 2
    # the sweep's counts: a whole buffer reduced, a rank's share
    # scattered or gathered
    widths = [ring_widths(op, n if op == Operation.ALLREDUCE
                          else n // world, world) for op in ops]
    shape = WindowShape(
        len(ops), [w[0] for w in widths], [w[1] for w in widths],
        [None] * len(ops), np.float32,
    )
    mesh = _mesh4()
    sh = NamedSharding(mesh, PartitionSpec(AXIS))
    args = [jax.ShapeDtypeStruct(
        (world * len(ops), CMDRING_SLOT_WORDS), np.int32, sharding=sh
    )] + [
        # raw committed shards: every send buffer is a whole slot wide,
        # the allgather's too (the program slices it)
        jax.ShapeDtypeStruct((world * n,), np.float32, sharding=sh)
    ] * len(ops)
    text = devring._windows_program(
        _mesh_key(mesh), shape.key(), 1
    ).lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == REHEARSAL_WINDOW_TEXT
