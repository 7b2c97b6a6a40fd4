"""The XLA collective layer over an 8-device virtual mesh.

Validates that every reference collective has a working XLA-native lowering
(the TPU fast path) and that the explicit ring pipelines match — the
equivalence the reference establishes between its emulator tier and
hardware tier (SURVEY.md §4).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu import ReduceFunction
from accl_tpu.ops import (
    make_mesh,
    run_allgather,
    run_allreduce,
    run_alltoall,
    run_bcast,
    run_gather,
    run_reduce,
    run_reduce_scatter,
    run_ring_allreduce,
    run_scatter,
)
from accl_tpu.ops.driver import run_compressed_allreduce

P = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= P, "conftest must force 8 cpu devices"
    return make_mesh(P)


@pytest.fixture
def stacked(rng):
    return rng.standard_normal((P, 256)).astype(np.float32)


def test_allreduce_sum(mesh, stacked):
    out = np.asarray(run_allreduce(stacked, mesh))
    expected = stacked.sum(axis=0)
    for r in range(P):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5)


def test_allreduce_max(mesh, stacked):
    out = np.asarray(run_allreduce(stacked, mesh, ReduceFunction.MAX))
    expected = stacked.max(axis=0)
    for r in range(P):
        np.testing.assert_allclose(out[r], expected, rtol=1e-6)


@pytest.mark.parametrize("nseg", [1, 4])
def test_ring_allreduce_matches_xla(mesh, stacked, nseg):
    out = np.asarray(run_ring_allreduce(stacked, mesh, num_segments=nseg))
    expected = stacked.sum(axis=0)
    for r in range(P):
        np.testing.assert_allclose(out[r], expected, rtol=1e-4, atol=1e-5)


def test_ring_allreduce_non_divisible(mesh, rng):
    """Count not divisible by world size exercises the tail/padding path
    (ref allreduce tail handling c:1900-1912)."""
    stacked = rng.standard_normal((P, 1001)).astype(np.float32)
    out = np.asarray(run_ring_allreduce(stacked, mesh))
    for r in range(P):
        np.testing.assert_allclose(out[r], stacked.sum(axis=0), rtol=1e-4, atol=1e-5)


def test_ring_allreduce_max(mesh, stacked):
    out = np.asarray(run_ring_allreduce(stacked, mesh, ReduceFunction.MAX))
    for r in range(P):
        np.testing.assert_allclose(out[r], stacked.max(axis=0), rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3, 7])
def test_bcast(mesh, stacked, root):
    out = np.asarray(run_bcast(stacked, mesh, root=root))
    for r in range(P):
        np.testing.assert_array_equal(out[r], stacked[root])


@pytest.mark.parametrize("root", [0, 5])
def test_reduce(mesh, stacked, root):
    out = np.asarray(run_reduce(stacked, mesh, root=root))
    np.testing.assert_allclose(out[root], stacked.sum(axis=0), rtol=1e-5)
    for r in range(P):
        if r != root:
            np.testing.assert_array_equal(out[r], np.zeros(256, np.float32))


def test_reduce_scatter(mesh, stacked):
    out = np.asarray(run_reduce_scatter(stacked, mesh))
    expected = stacked.sum(axis=0)
    block = 256 // P
    for r in range(P):
        np.testing.assert_allclose(
            out[r][:block], expected[r * block : (r + 1) * block], rtol=1e-5
        )


def test_allgather(mesh, rng):
    blocks = rng.standard_normal((P, 32)).astype(np.float32)
    out = np.asarray(run_allgather(blocks, mesh))
    expected = blocks.reshape(-1)
    for r in range(P):
        np.testing.assert_array_equal(out[r], expected)


@pytest.mark.parametrize("root", [0, 2])
def test_scatter(mesh, rng, root):
    full = rng.standard_normal((P, P * 16)).astype(np.float32)
    out = np.asarray(run_scatter(full, mesh, root=root))
    for r in range(P):
        np.testing.assert_array_equal(out[r], full[root][r * 16 : (r + 1) * 16])


@pytest.mark.parametrize("root", [0, 6])
def test_gather(mesh, rng, root):
    blocks = rng.standard_normal((P, 16)).astype(np.float32)
    out = np.asarray(run_gather(blocks, mesh, root=root))
    np.testing.assert_array_equal(out[root], blocks.reshape(-1))


def test_alltoall(mesh, rng):
    count = 8
    mats = rng.standard_normal((P, P * count)).astype(np.float32)
    out = np.asarray(run_alltoall(mats, mesh))
    for r in range(P):
        expected = np.concatenate(
            [mats[p][r * count : (r + 1) * count] for p in range(P)]
        )
        np.testing.assert_array_equal(out[r], expected)


def test_compressed_allreduce(mesh, stacked):
    """bf16 wire compression: the TPU-native ETH_COMPRESSED analog."""
    out = np.asarray(run_compressed_allreduce(stacked, mesh))
    expected = stacked.sum(axis=0)
    for r in range(P):
        np.testing.assert_allclose(out[r], expected, rtol=5e-2, atol=5e-2)


def test_sendrecv_shift(mesh, stacked):
    """SPMD point-to-point: ring shift via collective-permute."""
    from functools import partial

    from accl_tpu.ops import collectives
    from jax.sharding import PartitionSpec
    from jax import shard_map

    fn = jax.jit(
        shard_map(
            lambda x: collectives.sendrecv(x[0], "ranks", 1)[None],
            mesh=mesh,
            in_specs=(PartitionSpec("ranks"),),
            out_specs=PartitionSpec("ranks"),
            check_vma=False,
        )
    )
    out = np.asarray(fn(jnp.asarray(stacked)))
    for r in range(P):
        np.testing.assert_array_equal(out[r], stacked[(r - 1) % P])


# ---------------------------------------------------------------------------
# overlap primitives (ring-scheduled matmul + reduction)
# ---------------------------------------------------------------------------


def _smap_overlap(fn, mesh):
    from jax.sharding import PartitionSpec as PS

    from jax import shard_map

    return jax.jit(
        shard_map(
            fn, mesh=mesh, in_specs=(PS("ranks"), PS("ranks")),
            out_specs=PS("ranks"), check_vma=False,
        )
    )


def test_matmul_reduce_scatter_exact(mesh):
    """Ring-scheduled fused matmul+reduce_scatter == matmul then
    reduce_scatter (the decomposition only reorders a sum)."""
    from accl_tpu.ops import overlap

    size = P
    B, K, N = 4, 16, 32  # K_local = K per rank (already sharded)
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((size, B, K)).astype(np.float32)
    ws = rng.standard_normal((size, K, N)).astype(np.float32)

    full = np.einsum("rbk,rkn->bn", xs, ws)  # summed over ranks
    blk = N // size

    fn = _smap_overlap(
        lambda x, w: overlap.matmul_reduce_scatter(x[0], w[0], "ranks")[None],
        mesh,
    )
    out = np.asarray(fn(jnp.asarray(xs), jnp.asarray(ws)))
    for r in range(size):
        np.testing.assert_allclose(
            out[r], full[:, r * blk : (r + 1) * blk], rtol=2e-4, atol=2e-4
        )


def test_matmul_allreduce_exact(mesh):
    from accl_tpu.ops import overlap

    size = P
    B, K, N = 2, 8, 16
    rng = np.random.default_rng(1)
    xs = rng.standard_normal((size, B, K)).astype(np.float32)
    ws = rng.standard_normal((size, K, N)).astype(np.float32)
    full = np.einsum("rbk,rkn->bn", xs, ws)

    fn = _smap_overlap(
        lambda x, w: overlap.matmul_allreduce(x[0], w[0], "ranks")[None],
        mesh,
    )
    out = np.asarray(fn(jnp.asarray(xs), jnp.asarray(ws)))
    for r in range(size):
        np.testing.assert_allclose(out[r], full, rtol=2e-4, atol=2e-4)


def test_matmul_reduce_scatter_rejects_ragged(mesh):
    from accl_tpu.ops import overlap

    with pytest.raises(ValueError, match="divide"):
        _smap_overlap(
            lambda x, w: overlap.matmul_reduce_scatter(
                x[0], w[0], "ranks"
            )[None],
            mesh,
        )(jnp.ones((P, 2, 4)), jnp.ones((P, 4, 12)))  # 12 % 8 != 0


def test_matmul_allreduce_replicated_outspec(mesh):
    """The fused TP-layer exit under check_vma=True with a REPLICATED
    out_spec: the invariant allgather makes the replication claim
    provable, the exact scenario row-parallel layers need."""
    from jax.sharding import PartitionSpec as PS

    from jax import shard_map

    from accl_tpu.ops import overlap

    tp = P
    B, K, N = 3, 8, 32
    rng = np.random.default_rng(7)
    x = rng.standard_normal((B, tp * K)).astype(np.float32)
    w = rng.standard_normal((tp * K, N)).astype(np.float32)

    fused = jax.jit(
        shard_map(
            lambda xl, wl: overlap.matmul_allreduce(xl, wl, "ranks"),
            mesh=mesh,
            in_specs=(PS(None, "ranks"), PS("ranks", None)),
            out_specs=PS(None, None),  # replicated: demands invariance
        )
    )
    out = np.asarray(fused(jnp.asarray(x), jnp.asarray(w)))
    np.testing.assert_allclose(out, x @ w, rtol=2e-4, atol=2e-3)


def test_allgather_invariant_fallback(mesh, monkeypatch):
    """The older-jax fallback (psum of scattered slices) must stay
    semantically identical to the private ``all_gather_invariant`` op —
    a jax upgrade that drops the private symbol silently reroutes
    zero.py / seq-parallel exits through this path (ADVICE r2)."""
    from jax.sharding import PartitionSpec as PS

    from jax import shard_map

    from accl_tpu.ops import collectives

    monkeypatch.setattr(collectives, "_ag_invariant", None)

    rng = np.random.default_rng(11)
    blocks = rng.standard_normal((P, 16)).astype(np.float32)

    gathered = jax.jit(
        shard_map(
            lambda x: collectives.allgather_invariant(x, "ranks"),
            mesh=mesh,
            in_specs=(PS("ranks"),),
            out_specs=PS(None),  # replicated output: demands invariance
        )
    )(jnp.asarray(blocks).reshape(-1))
    np.testing.assert_allclose(
        np.asarray(gathered), blocks.reshape(-1), rtol=1e-6
    )

    # non-tiled form stacks the blocks along a fresh leading axis
    stacked = jax.jit(
        shard_map(
            lambda x: collectives.allgather_invariant(
                x, "ranks", tiled=False
            ),
            mesh=mesh,
            in_specs=(PS("ranks"),),
            out_specs=PS(None, None),
        )
    )(jnp.asarray(blocks).reshape(-1))
    np.testing.assert_allclose(np.asarray(stacked), blocks, rtol=1e-6)


def test_allgather_invariant_private_op_still_present():
    """Pin the fast path: every jax this repo supports (>= 0.5) ships
    ``jax._src.lax.parallel.all_gather_invariant``; if a future bump
    drops it we want a loud test failure, not a silent 2x-wire-bytes
    reroute through the fallback."""
    from accl_tpu.ops import collectives

    major, minor = (int(p) for p in jax.__version__.split(".")[:2])
    if (major, minor) >= (0, 5):
        assert collectives._ag_invariant is not None, (
            f"jax {jax.__version__} no longer exports all_gather_invariant; "
            "re-point collectives._ag_invariant or promote the fallback"
        )


def test_reduce_scatter_non_divisible_non_sum_raises(mesh):
    """Non-SUM reduce_scatter with an indivisible axis must raise, not
    silently truncate (ADVICE r2)."""
    from jax.sharding import PartitionSpec as PS

    from jax import shard_map

    from accl_tpu.ops import collectives

    with pytest.raises(ValueError, match="not\\s+divisible"):
        jax.jit(
            shard_map(
                lambda x: collectives.reduce_scatter(
                    x, "ranks", function=ReduceFunction.MAX, tiled=True
                ),
                mesh=mesh,
                in_specs=(PS(None),),
                out_specs=PS("ranks"),
            )
        )(jnp.ones((P * 3 + 1,), jnp.float32))


def test_reduce_scatter_non_sum_untiled_matches_sum(mesh):
    """tiled=False must squeeze the scatter dimension identically for the
    SUM (psum_scatter) and composed non-SUM paths."""
    from jax.sharding import PartitionSpec as PS

    from jax import shard_map

    from accl_tpu.ops import collectives

    rng = np.random.default_rng(5)
    x = rng.standard_normal((P, 16)).astype(np.float32)

    def run(fn):
        return np.asarray(
            jax.jit(
                shard_map(
                    lambda v: collectives.reduce_scatter(
                        v, "ranks", function=fn, tiled=False
                    ),
                    mesh=mesh,
                    in_specs=(PS(None, None),),
                    out_specs=PS("ranks"),
                )
            )(jnp.asarray(x))
        )

    got_sum = run(ReduceFunction.SUM)
    got_max = run(ReduceFunction.MAX)
    # each rank returns its squeezed (16,) row; global output is (P*16,)
    assert got_sum.shape == (P * 16,)
    assert got_max.shape == (P * 16,)
    # each rank r holds row r of the (replicated-input) reduction, squeezed
    np.testing.assert_allclose(got_sum, (x * P).reshape(-1), rtol=1e-5)
    np.testing.assert_allclose(got_max, x.reshape(-1), rtol=1e-6)

    with pytest.raises(ValueError, match="tiled=False"):
        run_bad = shard_map(
            lambda v: collectives.reduce_scatter(
                v, "ranks", function=ReduceFunction.MAX, tiled=False
            ),
            mesh=mesh,
            in_specs=(PS(None, None),),
            out_specs=PS("ranks"),
        )
        jax.jit(run_bad)(jnp.ones((P * 3, 16), jnp.float32))
