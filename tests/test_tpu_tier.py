"""Real-chip tier (opt-in: ``ACCL_TPU_TIER=1 python -m pytest tests/``).

The reference runs ONE suite against emulator, RTL sim, AND hardware
(``test/host/xrt/include/utility.hpp:29-51`` ``--hardware``; AXIS3x packs
3 ranks on one board so collectives run without a cluster,
``INSTALL.md:44``).  Our single-chip analog: the MPI facade at world=1 on
HBM-resident DeviceBuffers through the XLA gang backend, plus the Pallas
kernel suite Mosaic-compiled (selected via the ``pallas`` marker by
conftest in this mode; multi-device kernels self-skip on one chip).

Everything here also passes on the CPU host platform — handy for
developing the tier itself — but its purpose is chip execution:
DeviceBuffer paths, compiled kernels, and the gang backend are otherwise
only chip-exercised by ``chip_smoke.py`` and ``perfbench``.
"""

import numpy as np
import pytest

import jax

from accl_tpu import ACCLError, ErrorCode
from accl_tpu.buffer import DeviceBuffer
from accl_tpu.constants import ReduceFunction, TuningKey
from accl_tpu.core import xla_group

pytestmark = pytest.mark.tpu


@pytest.fixture(scope="module")
def accl():
    """One rank handle over the gang backend on the local device."""
    g = xla_group(1)
    yield g[0]
    for a in g:
        a.deinit()


@pytest.fixture
def rng():
    return np.random.default_rng(99)


# ---------------------------------------------------------------------------
# DeviceBuffer paths on the chip's HBM
# ---------------------------------------------------------------------------


def test_device_buffer_roundtrip(accl, rng):
    data = rng.standard_normal(4096).astype(np.float32)
    buf = accl.create_buffer_from(data)
    assert isinstance(buf, DeviceBuffer)
    buf.sync_from_device()
    np.testing.assert_array_equal(buf.data, data)


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float16])
def test_device_buffer_dtypes(accl, rng, dtype):
    data = (
        rng.standard_normal(512).astype(dtype)
        if np.dtype(dtype).kind == "f"
        else rng.integers(-50, 50, 512).astype(dtype)
    )
    buf = accl.create_buffer_from(data)
    buf.sync_from_device()
    np.testing.assert_array_equal(buf.data, data)


def test_device_buffer_slice_writeback(accl, rng):
    data = rng.standard_normal(1024).astype(np.float32)
    buf = accl.create_buffer_from(data)
    part = buf.slice(256, 768)
    part.sync_from_device()
    np.testing.assert_array_equal(part.data, data[256:768])
    # write through the slice, read back through the parent
    part.data[:] = 7.0
    part.sync_to_device()
    buf.sync_from_device()
    np.testing.assert_array_equal(buf.data[256:768], np.full(512, 7.0))
    np.testing.assert_array_equal(buf.data[:256], data[:256])


def test_host_only_buffer(accl, rng):
    buf = accl.create_buffer(64, np.float32, host_only=True)
    assert buf.is_host_only
    buf.data[:] = 5.0
    np.testing.assert_array_equal(buf.data, np.full(64, 5.0, np.float32))


# ---------------------------------------------------------------------------
# facade primitives at world=1 (copy / combine / collectives-as-identity)
# ---------------------------------------------------------------------------


def test_copy(accl, rng):
    data = rng.standard_normal(2048).astype(np.float32)
    src = accl.create_buffer_from(data)
    dst = accl.create_buffer(2048, np.float32)
    accl.copy(src, dst)
    dst.sync_from_device()
    np.testing.assert_array_equal(dst.data, data)


@pytest.mark.parametrize(
    "function", [ReduceFunction.SUM, ReduceFunction.MAX]
)
def test_combine(accl, rng, function):
    a = rng.standard_normal(1024).astype(np.float32)
    b = rng.standard_normal(1024).astype(np.float32)
    ba = accl.create_buffer_from(a)
    bb = accl.create_buffer_from(b)
    out = accl.create_buffer(1024, np.float32)
    accl.combine(function, ba, bb, out)
    out.sync_from_device()
    expect = a + b if function == ReduceFunction.SUM else np.maximum(a, b)
    np.testing.assert_allclose(out.data, expect, rtol=1e-6)


@pytest.mark.parametrize(
    "op", ["allreduce", "bcast", "allgather", "reduce", "alltoall"]
)
@pytest.mark.parametrize("count", [1, 1024, 3000])
def test_world1_collectives_identity(accl, rng, op, count):
    """World-1 collectives are identities, but they still build, compile,
    and run real gang programs against HBM shards — the single-board
    philosophy of the reference's AXIS3x tier."""
    data = rng.standard_normal(count).astype(np.float32)
    send = accl.create_buffer_from(data)
    if op == "bcast":
        recv = send  # in-place form: no second HBM allocation needed
        accl.bcast(recv, count, root=0)
    elif op == "allreduce":
        recv = accl.create_buffer(count, np.float32)
        accl.allreduce(send, recv, count)
    elif op == "allgather":
        recv = accl.create_buffer(count, np.float32)
        accl.allgather(send, recv, count)
    elif op == "reduce":
        recv = accl.create_buffer(count, np.float32)
        accl.reduce(send, recv, count, root=0)
    else:
        recv = accl.create_buffer(count, np.float32)
        accl.alltoall(send, recv, count)
    recv.sync_from_device()
    np.testing.assert_allclose(recv.data[:count], data, rtol=1e-6)


def test_world1_allreduce_zero_host_copies(accl, rng):
    """The gang data path must stay on-device: no host transfers between
    buffer creation and readback (transfer-guard enforced)."""
    data = rng.standard_normal(4096).astype(np.float32)
    send = accl.create_buffer_from(data)
    recv = accl.create_buffer(4096, np.float32)
    with jax.transfer_guard("disallow"):
        accl.allreduce(send, recv, 4096)
    recv.sync_from_device()
    np.testing.assert_allclose(recv.data, data, rtol=1e-6)


def test_compressed_allreduce_world1(accl, rng):
    data = rng.standard_normal(2000).astype(np.float32)
    send = accl.create_buffer_from(data)
    recv = accl.create_buffer(2000, np.float32)
    accl.allreduce(send, recv, 2000, compress_dtype=np.float16)
    recv.sync_from_device()
    np.testing.assert_allclose(recv.data, data, rtol=1e-3, atol=1e-3)


def test_async_request_surface(accl, rng):
    data = rng.standard_normal(256).astype(np.float32)
    send = accl.create_buffer_from(data)
    recv = accl.create_buffer(256, np.float32)
    req = accl.allreduce(send, recv, 256, run_async=True)
    assert req.wait(30)
    req.check()
    assert req.get_duration_ns() >= 0
    recv.sync_from_device()
    np.testing.assert_allclose(recv.data, data, rtol=1e-6)


# ---------------------------------------------------------------------------
# stream ports on the chip tier
# ---------------------------------------------------------------------------


def test_stream_copy_variants(accl, rng):
    data = rng.standard_normal(32).astype(np.float32)
    accl.stream_push(data, stream_id=3)
    buf = accl.create_buffer(32, np.float32)
    accl.copy_from_stream(buf, 32, stream_id=3)
    buf.sync_from_device()
    np.testing.assert_allclose(buf.host_view(), data, rtol=1e-6)

    buf2 = accl.create_buffer_from(data * 2.0)
    accl.copy_to_stream(buf2, 32, stream_id=4)
    out = accl.stream_pop(32, np.float32, stream_id=4)
    np.testing.assert_allclose(out, data * 2.0, rtol=1e-6)

    accl.stream_push(data * 3.0, stream_id=5)
    accl.copy_from_to_stream(np.float32, 32, stream_id=5)
    out = accl.stream_pop(32, np.float32, stream_id=5)
    np.testing.assert_allclose(out, data * 3.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# config + error surface on the chip tier
# ---------------------------------------------------------------------------


def test_config_surface(accl):
    accl.set_timeout(30)
    accl.set_max_eager_size(64 * 1024)
    with pytest.raises(ACCLError) as exc:
        accl.set_timeout(-1)
    assert exc.value.code == ErrorCode.CONFIG_ERROR
    with pytest.raises(ACCLError):
        accl.set_max_eager_size(10**9)


def test_tuning_registers(accl, rng):
    data = rng.standard_normal(1024).astype(np.float32)
    send = accl.create_buffer_from(data)
    recv = accl.create_buffer(1024, np.float32)
    try:
        for algo in ("xla", "ring"):
            accl.set_tuning(TuningKey.ALLREDUCE_ALGORITHM, algo)
            accl.allreduce(send, recv, 1024)
            recv.sync_from_device()
            np.testing.assert_allclose(recv.data, data, rtol=1e-5)
    finally:
        accl.set_tuning(TuningKey.ALLREDUCE_ALGORITHM, "xla")
    with pytest.raises(ValueError):
        accl.set_tuning(TuningKey.ALLREDUCE_ALGORITHM, "bogus")


def test_invalid_rank_error(accl, rng):
    buf = accl.create_buffer_from(rng.standard_normal(16).astype(np.float32))
    with pytest.raises(ACCLError) as exc:
        accl.bcast(buf, 16, root=5)  # world=1: rank 5 does not exist
    assert exc.value.code == ErrorCode.INVALID_RANK


def test_soft_reset_leaves_engine_usable(accl, rng):
    accl.soft_reset()
    data = rng.standard_normal(128).astype(np.float32)
    send = accl.create_buffer_from(data)
    recv = accl.create_buffer(128, np.float32)
    accl.allreduce(send, recv, 128)
    recv.sync_from_device()
    np.testing.assert_allclose(recv.data, data, rtol=1e-6)


def test_capabilities_report(accl):
    caps = accl.capabilities()
    assert caps["world_size"] == 1
    assert caps["device_tier"] is True  # the gang backend IS the chip tier
    assert "wire_compression" in caps and "arithmetic" in caps


def test_dumps(accl):
    assert "rank 0" in accl.dump_communicator()
    # the gang tier's rx dump is real now (parked slots / p2p posts /
    # stream depths); an idle engine must report clean — no occupied
    # ``rxbuf`` line for the soak's leak filter to trip on
    dump = accl.dump_rx_buffers()
    assert "XLA gang rx state" in dump
    assert "rxbuf" not in dump


# ---------------------------------------------------------------------------
# the windowed flash kernels at their own tile size (Mosaic-compiled here)
# ---------------------------------------------------------------------------

#: the window against the kernels' 512-row tile at T=1500 (three tiles,
#: the last one ragged): inside a tile, a whole number of tiles, not a
#: whole number, and wider than the sequence
_WINDOWS = {"under_a_block": 200, "two_blocks": 1024, "ragged": 700,
            "past_t": 4096}


@pytest.mark.parametrize("window", list(_WINDOWS))
def test_flash_attention_window_at_the_kernels_tile_size(window):
    """``flash_attention(window=)`` with the tile the train cells run (512),
    heads of 128, GQA and a ragged T, forward and gradients against the
    masked naive form; a window the sequence does not reach equals plain
    causal attention bit for bit.  tests/test_pallas.py has the same cases
    at small tiles; this tier compiles them."""
    import jax.numpy as jnp

    from accl_tpu.compat import has_interpret_params
    from accl_tpu.ops import pallas as pk

    if jax.default_backend() != "tpu" and not has_interpret_params():
        pytest.skip("no Pallas TPU interpreter here")
    W = _WINDOWS[window]
    B, H, Hkv, T, D = 1, 4, 2, 1500, 128
    r = np.random.default_rng(41)
    q = jnp.asarray(r.standard_normal((B, H, T, D)), jnp.float32)
    k, v = (
        jnp.asarray(r.standard_normal((B, Hkv, T, D)), jnp.float32)
        for _ in range(2)
    )
    w = jnp.asarray(r.standard_normal((B, H, T, D)), jnp.float32)

    def naive(q, k, v):
        k2, v2 = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k2) / np.sqrt(D)
        dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
        s = jnp.where((dist >= 0) & (dist < W), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v2)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out * w).sum(), out
        return jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)

    (_, got), grads = run(lambda q, k, v: pk.flash_attention(q, k, v, window=W))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = run(naive)
    atol = 5e-4 if jax.default_backend() == "tpu" else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=atol)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=20 * atol)
    if W >= T:
        (_, causal), causal_grads = run(
            lambda q, k, v: pk.flash_attention(q, k, v)
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(causal))
        for a, b in zip(grads, causal_grads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pk.flash_tile_pairs(T, 512, W) == {200: 5, 1024: 6, 700: 6,
                                               4096: 6}[W]


# ---------------------------------------------------------------------------
# the latent mixer's core at the kernels' tile size (Mosaic-compiled here)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 700])
def test_flash_attention_mla_widths_at_the_kernels_tile_size(window):
    """``flash_attention`` as DeepSeek-V2's core calls it: q and k heads of
    128 + 64 columns, the 64 rope columns of k ONE head, v and the output
    128 wide, the caller's scale, tiles of 512 over a ragged T: forward and
    all five gradients against the naive form."""
    import jax.numpy as jnp

    from accl_tpu.compat import has_interpret_params
    from accl_tpu.ops import pallas as pk

    if jax.default_backend() != "tpu" and not has_interpret_params():
        pytest.skip("no Pallas TPU interpreter here")
    B, H, T, D, Dr, Dv, scale = 1, 4, 1100, 128, 64, 128, 0.11472
    r = np.random.default_rng(43)
    n = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    args = (n(B, H, T, D), n(B, H, T, D), n(B, H, T, Dv), n(B, H, T, Dr),
            n(B, 1, T, Dr))
    w = n(B, H, T, Dv)

    def naive(q, k, v, qr, kr):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        s = s + jnp.einsum("bhqd,bkd->bhqk", qr, kr[:, 0])
        dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
        mask = dist >= 0 if window is None else (dist >= 0) & (dist < window)
        s = jnp.where(mask, s * scale, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def run(fn):
        def loss(*a):
            out = fn(*a)
            return (out * w).sum(), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    (_, got), grads = run(lambda q, k, v, qr, kr: pk.flash_attention(
        q, k, v, window=window, scale=scale, q_rope=qr, k_rope=kr))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = run(naive)
    atol = 5e-4 if jax.default_backend() == "tpu" else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=atol)
    for a, b in zip(grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=20 * atol)
