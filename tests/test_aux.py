"""Auxiliary subsystems: topology bootstrap, launcher, timing/logging,
device-kernel example, debug dumps — SURVEY.md §2.7/§2.5/§5 parity.
"""

import numpy as np
import pytest

from helpers import run_parallel


def test_generate_ranks_synthetic():
    from accl_tpu.parallel import Design, generate_ranks

    ranks = generate_ranks(Design.SOCKET, 4, base_port=48000)
    assert [r.address for r in ranks] == [
        f"127.0.0.1:{48000 + i}" for i in range(4)
    ]
    assert [r.session for r in ranks] == [0, 1, 2, 3]


def test_generate_ranks_json(tmp_path):
    import json

    from accl_tpu.parallel import Design, generate_ranks

    path = tmp_path / "cluster.json"
    path.write_text(
        json.dumps(
            [
                {"address": "10.0.0.1:5000", "max_segment_size": 2048},
                {"address": "10.0.0.2:5000", "session": 7},
            ]
        )
    )
    ranks = generate_ranks(Design.SOCKET, 2, json_path=str(path))
    assert ranks[0].address == "10.0.0.1:5000"
    assert ranks[0].max_segment_size == 2048
    assert ranks[1].session == 7


def test_bootstrap_inproc():
    from accl_tpu.parallel import Design, bootstrap

    group = bootstrap(Design.INPROC, 2)
    try:
        a, b = group
        import threading

        def sender():
            buf = b.create_buffer_from(np.full(8, 5.0, np.float32))
            b.send(buf, 8, dst=0, tag=1)

        t = threading.Thread(target=sender)
        t.start()
        buf = a.create_buffer(8, np.float32)
        a.recv(buf, 8, src=1, tag=1)
        t.join(10)
        buf.sync_from_device()
        np.testing.assert_array_equal(buf.data, np.full(8, 5.0, np.float32))
    finally:
        for x in group:
            x.deinit()


def test_mesh_from_topology():
    from accl_tpu.parallel import mesh_from_topology

    mesh = mesh_from_topology({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}


def test_device_memory_report():
    from accl_tpu.parallel import device_memory_report

    report = device_memory_report()
    assert len(report) >= 8
    assert all("platform" in e for e in report)


def test_timer():
    import time

    from accl_tpu.utils import Timer

    with Timer() as t:
        time.sleep(0.01)
    assert 8_000 < t.elapsed_us() < 1_000_000


def test_log_levels(capsys):
    from accl_tpu.utils import Log, LogLevel

    log = Log("test", level=LogLevel.INFO)
    log.info("visible")
    log.trace("hidden")
    err = capsys.readouterr().err
    assert "visible" in err and "hidden" not in err


def test_vadd_put_example(group2, rng):
    """The device-kernel-initiated flow (ref vadd_put.cpp demo)."""
    from accl_tpu.examples.vadd_put import vadd_put, vadd_put_streamed

    data = rng.standard_normal(64).astype(np.float32)

    def work(accl, rank):
        if rank == 0:
            vadd_put(accl, data, dst=1, stream_id=3)
            return None
        buf = accl.create_buffer(64, np.float32)
        accl.recv(buf, 64, src=0, tag=3)
        buf.sync_from_device()
        return buf.data.copy()

    res = run_parallel(group2, work)
    np.testing.assert_allclose(res[1], data + 1.0, rtol=1e-6)

    def work2(accl, rank):
        if rank == 0:
            vadd_put_streamed(accl, data, dst=1, stream_id=4)
            return None
        return accl.stream_pop(64, np.float32, stream_id=4)

    res = run_parallel(group2, work2)
    np.testing.assert_allclose(res[1], data + 1.0, rtol=1e-6)


def test_debug_dumps(group2):
    a = group2[0]
    rx = a.dump_rx_buffers()
    assert "rxbuf[0]" in rx
    comm = a.dump_communicator()
    assert "size=2" in comm and "rank 0" in comm


def test_launcher_multiprocess():
    """The mpirun-analog: N OS processes over the socket fabric.  Ports
    are randomized with retries: a fixed port flakes under parallel test
    runs (TIME_WAIT / contention)."""
    from helpers import launch_with_port_retry
    from tests_launch_target import allreduce_main  # see module below

    results = launch_with_port_retry(allreduce_main, 2)
    assert results == [3.0, 3.0]


def test_stress_short(group2):
    """Short randomized stress pass (the reference's stress.cpp loop,
    test/host/xrt/src/stress.cpp:24) against the shared 2-rank fixture —
    integrity-checked send/recv pairs and mixed collectives."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "stress.py"
    )
    spec = importlib.util.spec_from_file_location("stress", path)
    stress_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(stress_mod)
    stress_mod.stress(group2, iters=40, max_count=512, report_every=0)


def test_multihost_singleprocess_bootstrap():
    """Single-process path of the multi-host bootstrap (the degenerate
    'cluster of one', like running the reference's fixtures without
    mpirun)."""
    from accl_tpu.parallel import bootstrap_multihost

    ctx = bootstrap_multihost()
    assert ctx.is_coordinator and ctx.num_processes == 1
    assert len(ctx.global_devices()) >= 1


def test_hybrid_mesh_layout():
    """DCN x ICI mesh layout on the virtual device pool: outer axis =
    'slices', inner axes stay within a slice."""
    import jax

    from accl_tpu.parallel import dp_over_dcn_mesh, hybrid_mesh

    mesh = hybrid_mesh("dcn", {"x": 4})
    assert mesh.axis_names == ("dcn", "x")
    assert mesh.devices.shape == (len(jax.devices()) // 4, 4)

    sub = hybrid_mesh("dcn", {"x": 2}, devices=jax.devices()[:4])
    assert sub.devices.shape == (2, 2)

    mesh2 = dp_over_dcn_mesh(tp=2)
    assert mesh2.axis_names == ("dp", "tp")
    assert mesh2.devices.shape == (len(jax.devices()) // 2, 2)


def test_hybrid_mesh_runs_two_level_collective():
    """A two-level program: psum over ICI axis then over the DCN axis —
    the dp-gradient-over-DCN pattern."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from accl_tpu.parallel import hybrid_mesh

    mesh = hybrid_mesh("dcn", {"x": 4})
    n = mesh.devices.size

    def body(v):
        local = jax.lax.psum(v, "x")     # intra-slice: ICI
        return jax.lax.psum(local, "dcn")  # cross-slice: DCN

    fn = jax.jit(
        shard_map(
            body, mesh=mesh, in_specs=P(("dcn", "x")), out_specs=P(),
            check_vma=False,
        )
    )
    out = np.asarray(fn(jnp.ones((n,), jnp.float32)))
    np.testing.assert_allclose(out, float(n))


def test_profiler_trace_capture(tmp_path):
    """utils.profiling.trace captures an xprof trace of facade calls (the
    per-call span role of the reference's device perf counter, §5)."""
    import os

    import numpy as np

    from accl_tpu import utils
    from accl_tpu.core import xla_group

    logdir = str(tmp_path / "trace")
    g = xla_group(2)
    try:
        bufs = [
            (a.create_buffer_from(np.full(64, float(r), np.float32)),
             a.create_buffer(64, np.float32))
            for r, a in enumerate(g)
        ]
        with utils.trace(logdir):
            with utils.annotate("test-span"):
                from helpers import run_parallel

                run_parallel(
                    g, lambda a, r: a.allreduce(bufs[r][0], bufs[r][1], 64)
                )
    finally:
        for a in g:
            a.deinit()
    captured = [
        os.path.join(root, f)
        for root, _, files in os.walk(logdir)
        for f in files
    ]
    assert captured, "trace produced no files"


def test_device_memory_profile():
    from accl_tpu import utils

    blob = utils.device_memory_profile()
    assert isinstance(blob, bytes) and len(blob) > 0


def test_capabilities_report(group2):
    """The parse_hwid role: a runtime capability report per handle."""
    caps = group2[0].capabilities()
    assert caps["world_size"] == 2
    assert "SUM" in caps["arithmetic"] and "MAX" in caps["arithmetic"]
    assert any("FLOAT16" in w for w in caps["wire_compression"])
    assert any("FLOAT8" in w for w in caps["wire_compression"])
    assert caps["streams"] and caps["rendezvous"]
    assert isinstance(caps["device_tier"], bool)
    assert caps["platform"] == "cpu"


def test_flagship_train_step_on_hybrid_mesh():
    """The dp x tp train step runs unchanged on a DCN-aware hybrid mesh
    (dp crossing hosts, tp inside a slice) and matches the plain-mesh
    step — the multi-host training layout is a device-ordering concern,
    not a program change."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from accl_tpu.models import (
        TransformerConfig, init_params, make_sharded_train_step,
    )
    from accl_tpu.parallel import hybrid_mesh

    cfg = TransformerConfig(
        vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32, max_seq=16,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)

    plain = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    s1, sh1 = make_sharded_train_step(cfg, plain, lr=0.05)
    p1, l1 = s1(sh1(params), toks, tgts)

    hyb = hybrid_mesh("dp", {"tp": 2}, devices=jax.devices()[:8])
    assert hyb.axis_names == ("dp", "tp")
    s2, sh2 = make_sharded_train_step(cfg, hyb, lr=0.05)
    p2, l2 = s2(sh2(params), toks, tgts)

    assert float(l2) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )
