"""Runtime tuning-register surface (VERDICT item 5).

Role model: the reference host writes flat-vs-tree thresholds into the
firmware's exchange-memory registers at runtime
(``driver/xrt/src/accl.cpp:1198-1208``, registers
``ccl_offload_control.h:86-90``).  Here the facade's ``set_tuning`` routes
a SET_TUNING config op to whichever engine backs the rank: the Python
emulator's tuning table, the native C++ engine's atomics, or the XLA
gang's algorithm-selection registers.
"""

import numpy as np
import pytest

from accl_tpu.compat import has_interpret_params

from helpers import run_parallel

from accl_tpu.constants import (
    ACCLError,
    ConfigFunction,
    ErrorCode,
    TuningKey,
)
from accl_tpu.tuning import REGISTER_DEFAULTS


def _restore_defaults(group):
    """Put every register a test may have flipped back to stock.  Runs
    as a fixture FINALIZER so an assertion failure mid-test can no
    longer leak `max_eager_size=4` / flipped thresholds into sibling
    tests sharing the module-scoped group."""
    for a in group:
        a.set_max_eager_size(REGISTER_DEFAULTS["max_eager_size"])
        for name, val in REGISTER_DEFAULTS.items():
            if name != "max_eager_size":
                a.set_tuning(name, val)


@pytest.fixture
def tuned2(group2):
    yield group2
    _restore_defaults(group2)


@pytest.fixture
def tuned4(group4):
    yield group4
    _restore_defaults(group4)


# ---------------------------------------------------------------------------
# engine tiers (emulator + native C++): flat-vs-tree threshold flips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("flat", [True, False])
def test_bcast_flat_vs_tree_at_runtime(tuned4, rng, flat):
    """BCAST_FLAT_TREE_MAX_RANKS flipped through the facade selects the
    flat fan-out (threshold >= size) or the binomial tree (threshold 0);
    both must deliver root data everywhere.  Restoration is the tuned4
    finalizer's job — a mid-test assertion failure must not leak the
    flipped registers into sibling tests."""
    group4 = tuned4
    n = 64
    # rendezvous path so the tree algorithm actually engages
    for a in group4:
        a.set_max_eager_size(4)
        a.set_tuning(TuningKey.BCAST_FLAT_TREE_MAX_RANKS, 99 if flat else 0)
    data = rng.standard_normal(n).astype(np.float32)
    bufs = [a.create_buffer(n, np.float32) for a in group4]
    np.copyto(bufs[1].host_view(), data)
    bufs[1].sync_to_device()

    run_parallel(group4, lambda a, r: a.bcast(bufs[r], n, root=1))
    for r in range(4):
        bufs[r].sync_from_device()
        np.testing.assert_allclose(bufs[r].host_view(), data, rtol=1e-6)


@pytest.mark.parametrize("flat", [True, False])
def test_reduce_flat_vs_tree_at_runtime(tuned4, rng, flat):
    group4 = tuned4
    n = 64
    for a in group4:
        a.set_max_eager_size(4)
        a.set_tuning(TuningKey.REDUCE_FLAT_TREE_MAX_RANKS, 99 if flat else 0)
        a.set_tuning(
            TuningKey.REDUCE_FLAT_TREE_MAX_COUNT, 1 << 30 if flat else 0
        )
    rows = [rng.standard_normal(n).astype(np.float32) for _ in range(4)]
    sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(group4)]
    rb = [a.create_buffer(n, np.float32) for a in group4]

    run_parallel(
        group4,
        lambda a, r: a.reduce(sb[r], rb[r] if r == 2 else None, n, root=2),
    )
    rb[2].sync_from_device()
    np.testing.assert_allclose(
        rb[2].host_view(), np.sum(rows, axis=0), rtol=1e-4, atol=1e-5
    )


def test_gather_fanin_register(tuned4, rng):
    """Gather's fan-in throttle register is writable and gather stays
    correct with a fan-in of 1 (fully serialized) vs wide."""
    group4 = tuned4
    n = 16
    for fanin in (1, 8):
        for a in group4:
            a.set_tuning(TuningKey.GATHER_FLAT_TREE_MAX_FANIN, fanin)
            a.set_tuning(TuningKey.GATHER_FLAT_TREE_MAX_COUNT, 0)
        rows = [rng.standard_normal(n).astype(np.float32) for _ in range(4)]
        sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(group4)]
        rb0 = group4[0].create_buffer(4 * n, np.float32)

        run_parallel(
            group4,
            lambda a, r: a.gather(
                sb[r], rb0 if r == 0 else None, n, root=0
            ),
        )
        rb0.sync_from_device()
        np.testing.assert_allclose(
            rb0.host_view(), np.concatenate(rows), rtol=1e-6
        )


def test_tuning_register_state_visible(tuned2):
    """Emulator-tier registers are readable back from the engine table."""
    a = tuned2[0]
    if not hasattr(a.engine, "tuning"):
        pytest.skip("native engine state not host-readable")
    a.set_tuning("bcast_flat_tree_max_ranks", 7)
    assert a.engine.tuning["bcast_flat_tree_max_ranks"] == 7
    a.set_tuning(TuningKey.BCAST_FLAT_TREE_MAX_RANKS, 3)
    assert a.engine.tuning["bcast_flat_tree_max_ranks"] == 3


def test_tuning_invalid_inputs(group2):
    a = group2[0]
    with pytest.raises(ValueError, match="unknown tuning key"):
        a.set_tuning("no_such_register", 1)
    with pytest.raises(ValueError):
        a.set_tuning(99, 1)
    with pytest.raises(ValueError, match="unknown algorithm"):
        a.set_tuning(TuningKey.ALLREDUCE_ALGORITHM, "not_an_algorithm")
    with pytest.raises(ACCLError) as ei:
        a.set_tuning(TuningKey.GATHER_FLAT_TREE_MAX_FANIN, -1)
    assert ei.value.code == ErrorCode.CONFIG_ERROR


# ---------------------------------------------------------------------------
# device tier: allreduce algorithm selection through the facade
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "algo", ["ring", "pallas_ring", "pallas_ring_bidir", "xla"]
)
def test_xla_allreduce_algorithm_via_facade(algo, rng):
    if algo.startswith("pallas") and not has_interpret_params():
        pytest.skip("pallas lowering off-chip needs the Pallas interpreter")
    from accl_tpu.core import xla_group

    g = xla_group(4)
    try:
        n = 32
        for a in g:
            a.set_tuning(TuningKey.ALLREDUCE_ALGORITHM, algo)
            a.set_tuning(TuningKey.RING_SEGMENTS, 2)
        assert g[0].engine.gang.tuning["allreduce_algorithm"] == algo
        assert g[0].engine.gang.tuning["ring_segments"] == 2
        rows = [rng.standard_normal(n).astype(np.float32) for _ in range(4)]
        sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(g)]
        rb = [a.create_buffer(n, np.float32) for a in g]
        run_parallel(g, lambda a, r: a.allreduce(sb[r], rb[r], n))
        for r in range(4):
            rb[r].sync_from_device()
            np.testing.assert_allclose(
                rb[r].host_view(), np.sum(rows, axis=0), rtol=1e-4, atol=1e-5
            )
    finally:
        for a in g:
            a.deinit()


@pytest.mark.parametrize("algo", ["xla", "pallas_ring"])
def test_xla_rooted_algorithms_via_facade(algo, rng):
    """bcast/reduce/scatter/gather flip between the XLA lowering and the
    rooted Pallas ring-relay kernels through the tuning registers."""
    if algo.startswith("pallas") and not has_interpret_params():
        pytest.skip("pallas lowering off-chip needs the Pallas interpreter")
    from accl_tpu.core import xla_group

    g = xla_group(4)
    try:
        n = 64
        for a in g:
            for key in (
                TuningKey.BCAST_ALGORITHM,
                TuningKey.REDUCE_ALGORITHM,
                TuningKey.SCATTER_ALGORITHM,
                TuningKey.GATHER_ALGORITHM,
            ):
                a.set_tuning(key, algo)
            a.set_tuning(TuningKey.RING_SEGMENTS, 2)
        rows = [rng.standard_normal(n).astype(np.float32) for _ in range(4)]
        big = rng.standard_normal(4 * n).astype(np.float32)
        # snapshot expectations up front: buffers ALIAS the arrays they
        # wrap, so sync_from_device overwrites rows[r]
        expect_sum = np.sum(rows, axis=0)
        expect_cat = np.concatenate(rows)
        expect_b = rows[3].copy()
        sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(g)]
        bb = [a.create_buffer_from(rows[r].copy()) for r, a in enumerate(g)]
        rb = [a.create_buffer(n, np.float32) for a in g]
        gb2 = g[2].create_buffer(4 * n, np.float32)
        scat_src = g[1].create_buffer_from(big)
        scat_dst = [a.create_buffer(n, np.float32) for a in g]

        def work(a, r):
            a.bcast(bb[r], n, root=3)
            a.reduce(sb[r], rb[r] if r == 1 else None, n, root=1)
            a.gather(sb[r], gb2 if r == 2 else None, n, root=2)
            a.scatter(
                scat_src if r == 1 else None, scat_dst[r], n, root=1
            )

        run_parallel(g, work)
        for r in range(4):
            bb[r].sync_from_device()
            np.testing.assert_allclose(bb[r].host_view(), expect_b, rtol=1e-6)
            scat_dst[r].sync_from_device()
            np.testing.assert_allclose(
                scat_dst[r].host_view(), big[r * n : (r + 1) * n], rtol=1e-6
            )
        rb[1].sync_from_device()
        np.testing.assert_allclose(
            rb[1].host_view(), expect_sum, rtol=1e-4, atol=1e-5
        )
        gb2.sync_from_device()
        np.testing.assert_allclose(gb2.host_view(), expect_cat, rtol=1e-6)
    finally:
        for a in g:
            a.deinit()


def test_rooted_algorithm_rejects_ppermute_ring(group2):
    """RING is an allreduce-only lowering: rooted registers reject it."""
    with pytest.raises(ACCLError) as ei:
        group2[0].set_tuning(TuningKey.BCAST_ALGORITHM, "ring")
    assert ei.value.code == ErrorCode.CONFIG_ERROR


def test_xla_invalid_algorithm_value_errors():
    from accl_tpu.core import xla_group

    g = xla_group(2)
    try:
        with pytest.raises(ACCLError) as ei:
            # direct config op with an out-of-range algorithm value
            g[0]._config(
                ConfigFunction.SET_TUNING,
                42.0,
                key=int(TuningKey.ALLREDUCE_ALGORITHM),
            )
        assert ei.value.code == ErrorCode.CONFIG_ERROR
    finally:
        for a in g:
            a.deinit()
