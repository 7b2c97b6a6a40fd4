"""The ACCL facade over the XLA gang backend: the same MPI-like programs
that run on the emulator tier execute as shard_map programs over the device
mesh — the tier-equivalence contract of SURVEY.md §4.
"""

import numpy as np
import pytest

from accl_tpu.compat import has_interpret_params

from helpers import run_parallel

from accl_tpu import ReduceFunction
from accl_tpu.core import xla_group


def test_xla_allreduce(gang4, rng):
    count = 1000
    chunks = [rng.standard_normal(count).astype(np.float32) for _ in gang4]
    expected = np.sum(chunks, axis=0)

    def work(accl, rank):
        send = accl.create_buffer_from(chunks[rank])
        recv = accl.create_buffer(count, np.float32)
        accl.allreduce(send, recv, count)
        recv.sync_from_device()
        return recv.data.copy()

    for got in run_parallel(gang4, work):
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


def test_xla_allreduce_max(gang4, rng):
    count = 500
    chunks = [rng.standard_normal(count).astype(np.float32) for _ in gang4]
    expected = np.max(chunks, axis=0)

    def work(accl, rank):
        send = accl.create_buffer_from(chunks[rank])
        recv = accl.create_buffer(count, np.float32)
        accl.allreduce(send, recv, count, function=ReduceFunction.MAX)
        recv.sync_from_device()
        return recv.data.copy()

    for got in run_parallel(gang4, work):
        np.testing.assert_array_equal(got, expected)


@pytest.mark.parametrize("root", [0, 2])
def test_xla_bcast(gang4, rng, root):
    count = 700
    data = rng.standard_normal(count).astype(np.float32)

    def work(accl, rank):
        buf = (
            accl.create_buffer_from(data)
            if rank == root
            else accl.create_buffer(count, np.float32)
        )
        accl.bcast(buf, count, root=root)
        buf.sync_from_device()
        return buf.data.copy()

    for got in run_parallel(gang4, work):
        np.testing.assert_array_equal(got, data)


def test_xla_scatter_gather(gang4, rng):
    size = len(gang4)
    count = 64
    data = rng.standard_normal(size * count).astype(np.float32)

    def work(accl, rank):
        send = accl.create_buffer_from(data) if rank == 0 else None
        recv = accl.create_buffer(count, np.float32)
        accl.scatter(send, recv, count, root=0)
        recv.sync_from_device()
        got_chunk = recv.data.copy()
        # round-trip: gather the chunks back to rank 3
        gbuf = accl.create_buffer(size * count, np.float32) if rank == 3 else None
        accl.gather(recv, gbuf, count, root=3)
        if rank == 3:
            gbuf.sync_from_device()
            return got_chunk, gbuf.data.copy()
        return got_chunk, None

    res = run_parallel(gang4, work)
    for r, (chunk, _) in enumerate(res):
        np.testing.assert_array_equal(chunk, data[r * count : (r + 1) * count])
    np.testing.assert_array_equal(res[3][1], data)


def test_xla_allgather(gang4, rng):
    size = len(gang4)
    count = 50
    chunks = [rng.standard_normal(count).astype(np.float32) for _ in gang4]

    def work(accl, rank):
        send = accl.create_buffer_from(chunks[rank])
        recv = accl.create_buffer(size * count, np.float32)
        accl.allgather(send, recv, count)
        recv.sync_from_device()
        return recv.data.copy()

    for got in run_parallel(gang4, work):
        np.testing.assert_array_equal(got, np.concatenate(chunks))


def test_xla_reduce_scatter(gang4, rng):
    size = len(gang4)
    count = 32
    full = [rng.standard_normal(size * count).astype(np.float32) for _ in gang4]
    expected = np.sum(full, axis=0)

    def work(accl, rank):
        send = accl.create_buffer_from(full[rank])
        recv = accl.create_buffer(count, np.float32)
        accl.reduce_scatter(send, recv, count)
        recv.sync_from_device()
        return recv.data.copy()

    res = run_parallel(gang4, work)
    for r, got in enumerate(res):
        np.testing.assert_allclose(
            got, expected[r * count : (r + 1) * count], rtol=1e-5, atol=1e-6
        )


def test_xla_alltoall(gang4, rng):
    size = len(gang4)
    count = 16
    mats = [rng.standard_normal(size * count).astype(np.float32) for _ in gang4]

    def work(accl, rank):
        send = accl.create_buffer_from(mats[rank])
        recv = accl.create_buffer(size * count, np.float32)
        accl.alltoall(send, recv, count)
        recv.sync_from_device()
        return recv.data.copy()

    res = run_parallel(gang4, work)
    for r, got in enumerate(res):
        expected = np.concatenate(
            [mats[p][r * count : (r + 1) * count] for p in range(size)]
        )
        np.testing.assert_array_equal(got, expected)


def test_xla_sendrecv(gang4, rng):
    data = rng.standard_normal(333).astype(np.float32)

    def work(accl, rank):
        if rank == 1:
            buf = accl.create_buffer_from(data)
            accl.send(buf, 333, dst=2, tag=4)
            return None
        if rank == 2:
            buf = accl.create_buffer(333, np.float32)
            accl.recv(buf, 333, src=1, tag=4)
            buf.sync_from_device()
            return buf.data.copy()
        return None

    res = run_parallel(gang4, work)
    np.testing.assert_array_equal(res[2], data)


def test_xla_sendrecv_durations_measured(gang4, rng):
    """p2p requests report measured post->delivery wall-clock ns, never
    the old duration_ns=1 sentinel (ref bench.cpp:25-31 is literally a
    get_duration read on send/recv; the sentinel made a committed sweep
    claim 2 MiB in 1 ns)."""
    n = 1 << 18  # 1 MiB of f32: delivery alone is safely over a microsecond

    def work(accl, rank):
        if rank == 0:
            buf = accl.create_buffer_from(np.ones(n, np.float32))
            req = accl.send(buf, n, dst=1, tag=9, run_async=True)
        elif rank == 1:
            buf = accl.create_buffer(n, np.float32)
            req = accl.recv(buf, n, src=0, tag=9, run_async=True)
        else:
            return None
        assert req.wait(60)
        req.check()
        return req.get_duration_ns()

    res = run_parallel(gang4, work)
    for ns in (res[0], res[1]):
        assert 1_000 <= ns < 60 * 10**9, f"implausible p2p duration {ns} ns"


def test_xla_stream_put(gang4, rng):
    data = rng.standard_normal(64).astype(np.float32)

    def work(accl, rank):
        if rank == 0:
            buf = accl.create_buffer_from(data)
            accl.stream_put(buf, 64, dst=3, stream_id=5)
            return None
        if rank == 3:
            return accl.stream_pop(64, np.float32, stream_id=5)
        return None

    res = run_parallel(gang4, work)
    np.testing.assert_array_equal(res[3], data)


def test_xla_compressed_allreduce(gang4, rng):
    count = 512
    chunks = [rng.standard_normal(count).astype(np.float32) for _ in gang4]
    expected = np.sum(chunks, axis=0)

    def work(accl, rank):
        send = accl.create_buffer_from(chunks[rank])
        recv = accl.create_buffer(count, np.float32)
        accl.allreduce(send, recv, count, compress_dtype=np.float16)
        recv.sync_from_device()
        return recv.data.copy()

    for got in run_parallel(gang4, work):
        np.testing.assert_allclose(got, expected, rtol=5e-2, atol=5e-2)


def test_xla_reduce(gang4, rng):
    count = 128
    chunks = [rng.standard_normal(count).astype(np.float32) for _ in gang4]

    def work(accl, rank):
        send = accl.create_buffer_from(chunks[rank])
        recv = accl.create_buffer(count, np.float32) if rank == 1 else None
        accl.reduce(send, recv, count, root=1)
        if rank == 1:
            recv.sync_from_device()
            return recv.data.copy()
        return None

    res = run_parallel(gang4, work)
    np.testing.assert_allclose(res[1], np.sum(chunks, axis=0), rtol=1e-5, atol=1e-6)


def test_xla_barrier_and_copy(gang4, rng):
    def work(accl, rank):
        src = accl.create_buffer_from(np.full(8, rank, np.float32))
        dst = accl.create_buffer(8, np.float32)
        accl.copy(src, dst)
        accl.barrier()
        dst.sync_from_device()
        return dst.data[0]

    res = run_parallel(gang4, work)
    assert res == [0.0, 1.0, 2.0, 3.0]


def test_xla_send_from_stream(gang4, rng):
    """OP0_STREAM send: operand pulled from the local stream port, then a
    normal tag-matched transfer (regression: was misrouted as stream_put)."""
    data = rng.standard_normal(32).astype(np.float32)

    def work(accl, rank):
        if rank == 0:
            accl.stream_push(data, stream_id=2)
            accl.send(None, 32, dst=1, tag=21, from_stream=True, stream_id=2)
            return None
        if rank == 1:
            buf = accl.create_buffer(32, np.float32)
            accl.recv(buf, 32, src=0, tag=21)
            buf.sync_from_device()
            return buf.data.copy()
        return None

    res = run_parallel(gang4, work)
    np.testing.assert_array_equal(res[1], data)


def test_xla_recv_to_stream(gang4, rng):
    """RES_STREAM recv: matched payload lands in the local stream port
    (regression: DummyBuffer deref deadlocked both ranks)."""
    data = rng.standard_normal(48).astype(np.float32)

    def work(accl, rank):
        if rank == 2:
            buf = accl.create_buffer_from(data)
            accl.send(buf, 48, dst=3, tag=22)
            return None
        if rank == 3:
            accl.recv(None, 48, src=2, tag=22, to_stream=True, stream_id=9)
            return accl.stream_pop(48, np.float32, stream_id=9)
        return None

    res = run_parallel(gang4, work)
    np.testing.assert_array_equal(res[3], data)


def test_xla_stream_put_subcommunicator(gang4, rng):
    """stream_put with a comm-relative dst must reach the right WORLD rank
    (regression: delivered to the sender's own port)."""
    data = rng.standard_normal(16).astype(np.float32)

    def work(accl, rank):
        comm = accl.create_communicator([1, 2])
        if comm is None:
            return None
        if comm.local_rank == 0:  # world rank 1
            buf = accl.create_buffer_from(data)
            accl.stream_put(buf, 16, dst=1, stream_id=11, comm=comm)
            return "sent"
        return accl.stream_pop(16, np.float32, stream_id=11)  # world rank 2

    res = run_parallel(gang4, work)
    assert res[1] == "sent"
    np.testing.assert_array_equal(res[2], data)


def test_xla_mismatched_gang_call_errors(rng):
    """Ranks disagreeing on count at the same gang slot must error, not
    silently truncate."""
    from accl_tpu import ACCLError
    from accl_tpu.core import xla_group

    g = xla_group(2)
    try:
        errors = []

        def work(accl, rank):
            n = 50 if rank == 0 else 100
            send = accl.create_buffer_from(np.ones(n, np.float32))
            recv = accl.create_buffer(n, np.float32)
            try:
                accl.allreduce(send, recv, n)
            except ACCLError as e:
                errors.append(e)

        run_parallel(g, work)
        assert len(errors) == 2
    finally:
        for a in g:
            a.deinit()


def test_xla_watchdog_threads_bounded(rng):
    """Completed collectives must not leave timer threads lingering
    (regression: one leaked 30s Timer per non-final submit)."""
    import threading as _t

    from accl_tpu.core import xla_group

    g = xla_group(2)
    try:
        def work(accl, rank):
            for _ in range(50):
                s = accl.create_buffer_from(np.ones(16, np.float32))
                d = accl.create_buffer(16, np.float32)
                accl.allreduce(s, d, 16)

        before = _t.active_count()
        run_parallel(g, work)
        import time as _time

        _time.sleep(0.3)
        after = _t.active_count()
        assert after - before < 10, f"lingering threads: {after - before}"
    finally:
        for a in g:
            a.deinit()


@pytest.mark.parametrize("algo", ["ring", "pallas_ring"])
def test_xla_allreduce_algorithm_tuning(algo, rng):
    """The gang's algorithm-selection tuning register (the reference's
    runtime flat-vs-tree threshold surface, accl.cpp:1198-1208) switches
    the allreduce lowering: explicit ppermute ring or the Pallas
    remote-DMA ring kernel — same MPI-facade semantics either way."""
    if algo.startswith("pallas") and not has_interpret_params():
        pytest.skip("pallas lowering off-chip needs the Pallas interpreter")
    g = xla_group(4)
    try:
        g[0].engine.gang.tuning.update(
            {"allreduce_algorithm": algo, "ring_segments": 2}
        )
        count = 2 * 8 * 128
        chunks = [rng.standard_normal(count).astype(np.float32) for _ in g]
        expected = np.sum(chunks, axis=0)

        def work(accl, rank):
            send = accl.create_buffer_from(chunks[rank])
            recv = accl.create_buffer(count, np.float32)
            accl.allreduce(send, recv, count)
            recv.sync_from_device()
            return recv.data.copy()

        for got in run_parallel(g, work):
            np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)
    finally:
        for a in g:
            a.deinit()


def test_xla_allreduce_compressed_pallas_ring(rng):
    """ETH_COMPRESSED + pallas_ring tuning: the compression lanes execute
    inside the kernel (wire narrowed to bf16, f32 accumulation)."""
    if not has_interpret_params():
        pytest.skip("pallas lowering off-chip needs the Pallas interpreter")
    g = xla_group(4)
    try:
        g[0].engine.gang.tuning.update({"allreduce_algorithm": "pallas_ring"})
        count = 8 * 128
        chunks = [rng.standard_normal(count).astype(np.float32) for _ in g]
        expected = np.sum(chunks, axis=0)

        def work(accl, rank):
            send = accl.create_buffer_from(chunks[rank])
            recv = accl.create_buffer(count, np.float32)
            accl.allreduce(send, recv, count, compress_dtype=np.float16)
            recv.sync_from_device()
            return recv.data.copy()

        for got in run_parallel(g, work):
            np.testing.assert_allclose(got, expected, rtol=3e-2, atol=3e-2)
            assert not np.array_equal(got, expected)  # wire was narrowed
    finally:
        for a in g:
            a.deinit()
