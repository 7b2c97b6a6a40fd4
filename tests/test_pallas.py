"""Pallas kernel tier tests.

The reference validates its HLS dataplane by compiling the same kernel
sources for x86 and driving them through the emulator harness
(test/model/emulator/cclo_emu.cpp); here the same role is played by the
Pallas TPU **interpreter**: the identical kernel code that compiles via
Mosaic on a real chip executes interpreted on the virtual CPU mesh —
including the inter-chip remote DMAs of the ring collectives, and
optionally under the interpreter's vector-clock race detector (an aux
capability the reference lacks entirely, SURVEY.md §5).
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.pallas import tpu as pltpu

from accl_tpu.compat import has_interpret_params, interpret_params_reason
from accl_tpu.constants import ReduceFunction
from accl_tpu.ops import pallas as pk

pytestmark = [
    pytest.mark.pallas,
    # off-chip these kernels need the Pallas TPU interpreter; where its
    # probe fails the whole suite skips LOUDLY with the probe's reason
    # (the compat loud-skip convention)
    pytest.mark.skipif(
        jax.default_backend() != "tpu" and not has_interpret_params(),
        reason=f"Pallas interpret tier unavailable: "
               f"{interpret_params_reason()}",
    ),
]

# Gradient-comparison atol: on real silicon the HIGHEST-precision kernels
# still disagree with XLA's autodiff by ~1e-4 absolute (different exp
# approximation + accumulation order; measured max 1.6e-4, mean 3e-6 on
# v5e) — while the interpreter tier is exact and keeps the tight bound
# as a regression guard.
_GRAD_ATOL = 5e-4 if jax.default_backend() == "tpu" else 2e-5


def _mesh(n):
    devs = jax.devices()[:n]
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(devs), ("x",))


def _interpreter_only():
    """Tests that force ``pltpu.InterpretParams`` belong to the off-chip
    tier: the interpreter is how the CPU mesh runs these kernels (and
    its race detector is a CPU-side tool); on the chip the same kernels
    run compiled, which is what the chip tier is there to exercise.
    (Interpreting ON a TPU backend is untried on the attached chip.)"""
    if jax.default_backend() == "tpu":
        pytest.skip("interpreter tier runs off-chip")


# ---------------------------------------------------------------------------
# combine (reduce_ops plugin)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize(
    "function", [ReduceFunction.SUM, ReduceFunction.MAX]
)
def test_combine(dtype, function):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-50, 50, size=777), dtype)
    b = jnp.asarray(rng.integers(-50, 50, size=777), dtype)
    out = pk.combine(a, b, function)
    expect = (
        np.asarray(a) + np.asarray(b)
        if function == ReduceFunction.SUM
        else np.maximum(np.asarray(a), np.asarray(b))
    )
    np.testing.assert_allclose(np.asarray(out), expect)


def test_combine_fused_output_cast():
    a = jnp.linspace(0, 1, 300, dtype=jnp.float32)
    b = jnp.linspace(1, 0, 300, dtype=jnp.float32)
    out = pk.combine(a, b, out_dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray((a + b).astype(jnp.bfloat16), np.float32),
    )


def test_combine_rejects_mismatch():
    with pytest.raises(ValueError):
        pk.combine(jnp.zeros(4), jnp.zeros(5))


@pytest.mark.parametrize(
    "function", [ReduceFunction.SUM, ReduceFunction.MAX]
)
def test_combine_accumulate(function):
    """In-place form: result aliases the first operand's storage (donated);
    values match the out-of-place combine."""
    rng = np.random.default_rng(3)
    a_np = rng.standard_normal(1111).astype(np.float32)
    b_np = rng.standard_normal(1111).astype(np.float32)
    out = pk.combine(
        jnp.asarray(a_np), jnp.asarray(b_np), function, accumulate=True
    )
    expect = (
        a_np + b_np
        if function == ReduceFunction.SUM
        else np.maximum(a_np, b_np)
    )
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_combine_accumulate_rejects_cast():
    with pytest.raises(ValueError):
        pk.combine(
            jnp.zeros(8, jnp.float32),
            jnp.zeros(8, jnp.float32),
            out_dtype=jnp.bfloat16,
            accumulate=True,
        )


# ---------------------------------------------------------------------------
# compression (hp_compression plugin)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float16, jnp.bfloat16])
def test_cast_roundtrip(dtype):
    x = jnp.asarray(np.random.default_rng(1).normal(size=500), jnp.float32)
    narrow = pk.cast(x, dtype)
    np.testing.assert_array_equal(
        np.asarray(narrow), np.asarray(x.astype(dtype))
    )
    widened = pk.cast(narrow, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(widened), np.asarray(narrow.astype(jnp.float32))
    )


def test_cast_f16_compiled_mode_rides_xla():
    """Compiled-mode (interpret=False) f16 casts must never reach Mosaic:
    the TPU mosaic dialect has no f16 (the v5e compile rejects it).  The
    guard short-circuits to XLA's convert before any Pallas
    lowering, so this is assertable on every backend."""
    x = jnp.asarray(np.random.default_rng(3).normal(size=300), jnp.float32)
    narrow = pk.cast(x, jnp.float16, interpret=False)
    np.testing.assert_array_equal(
        np.asarray(narrow), np.asarray(x.astype(jnp.float16))
    )
    widened = pk.cast(narrow, jnp.float32, interpret=False)
    np.testing.assert_array_equal(
        np.asarray(widened), np.asarray(narrow.astype(jnp.float32))
    )
    # combine reroutes the same way (fp16 is a reduce_ops lane dtype)
    a = jnp.asarray([1.5, 2.25, -3.0], jnp.float16)
    b = jnp.asarray([0.5, 0.75, 1.0], jnp.float16)
    out = pk.combine(a, b, interpret=False)
    assert out.dtype == jnp.float16
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a + b))
    # ring kernels reject instead (remote-DMA kernels have no XLA reroute)
    from accl_tpu.ops.pallas._common import mosaic_rejects

    assert mosaic_rejects(False, jnp.float16)
    assert mosaic_rejects(False, jnp.float32, "float16")
    assert not mosaic_rejects(False, jnp.float32, None)
    assert not mosaic_rejects(pltpu.InterpretParams(), jnp.float16)
    # mixed q/k/v dtypes can no longer smuggle f16 past the q-dtype guard
    q = jnp.zeros((1, 1, 8, 32), jnp.bfloat16)
    kv = jnp.zeros((1, 1, 8, 32), jnp.float16)
    with pytest.raises(ValueError, match="dtypes must match"):
        pk.flash_attention(q, kv, kv)
    with pytest.raises(ValueError, match="use bfloat16"):
        pk.flash_attention(
            kv, kv, kv, interpret=False
        )


def test_stochastic_round_unbiased():
    # a value strictly between two bf16 neighbors must round both ways —
    # requires real hardware PRNG: the interpreter stubs prng_random_bits
    # to zeros (rounding degenerates to truncation there).
    if jax.default_backend() != "tpu":
        pytest.skip("hardware PRNG required (interpreter stubs it to 0)")
    x = jnp.full((2048,), 1.0 + 2.0**-9, jnp.float32)
    out = pk.cast(x, jnp.bfloat16, stochastic=True, seed=11)
    vals = np.unique(np.asarray(out, np.float32))
    assert len(vals) == 2, vals
    mean = float(np.mean(np.asarray(out, np.float32)))
    assert abs(mean - (1.0 + 2.0**-9)) < 2.0**-11


def test_stochastic_round_interpreter_truncates():
    """Under the interpreter the random bits are zeros: stochastic rounding
    must reduce to truncation toward zero of the low mantissa bits."""
    _interpreter_only()
    x = jnp.asarray([1.0 + 2.0**-9, -1.0 - 2.0**-9, 2.5], jnp.float32)
    out = pk.cast(
        x, jnp.bfloat16, stochastic=True, seed=0,
        interpret=pltpu.InterpretParams(),
    )
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), [1.0, -1.0, 2.5]
    )


def test_stochastic_round_arg_validation():
    with pytest.raises(ValueError):
        pk.cast(jnp.zeros(8, jnp.float32), jnp.float16, stochastic=True)


def test_int8_roundtrip():
    x = jnp.asarray(np.random.default_rng(2).normal(size=900), jnp.float32)
    values, scales, n = pk.quantize_int8(x)
    assert values.dtype == jnp.int8
    back = pk.dequantize_int8(values, scales, n, x.shape)
    tol = float(jnp.max(jnp.abs(x))) / 120
    np.testing.assert_allclose(np.asarray(back), np.asarray(x), atol=tol)


# ---------------------------------------------------------------------------
# ring collectives (segmented ring over remote DMA)
# ---------------------------------------------------------------------------

_RING_N = 4 * 2 * 8 * 128  # exact packing for size=4, segments<=2


@pytest.mark.parametrize("num_segments", [1, 2])
@pytest.mark.parametrize(
    "function", [ReduceFunction.SUM, ReduceFunction.MAX]
)
def test_ring_allreduce(num_segments, function):
    mesh = _mesh(4)
    data = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, _RING_N)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_allreduce(
                x[0], "x", function, num_segments
            )[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    expect = (
        np.asarray(data).sum(0)
        if function == ReduceFunction.SUM
        else np.asarray(data).max(0)
    )
    for r in range(4):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-5)


def test_ring_allreduce_ragged_padding():
    """Sizes that don't pack evenly are padded and sliced back."""
    mesh = _mesh(4)
    n = 1000
    data = jnp.asarray(
        np.random.default_rng(4).normal(size=(4, n)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_allreduce(x[0], "x")[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    for r in range(4):
        np.testing.assert_allclose(
            out[r], np.asarray(data).sum(0), rtol=1e-4, atol=1e-5
        )


def test_ring_allgather():
    mesh = _mesh(4)
    blk = 8 * 128
    data = jnp.asarray(
        np.random.default_rng(5).normal(size=(4 * blk,)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_allgather(x, "x", num_segments=2),
            mesh=mesh, in_specs=P("x"), out_specs=P(None), check_vma=False,
        )
    )
    np.testing.assert_allclose(np.asarray(fn(data)), np.asarray(data))


def test_ring_reduce_scatter():
    mesh = _mesh(4)
    data = jnp.asarray(
        np.random.default_rng(6).normal(size=(4, _RING_N)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_reduce_scatter(x[0], "x").reshape(1, -1),
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data)).reshape(4, -1)
    expect = np.asarray(data).sum(0).reshape(4, -1)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-5)


def test_ring_allreduce_race_free(capsys):
    """Run the remote-DMA kernel under the interpreter's vector-clock race
    detector — the dataplane analog of running the engine under TSAN
    (a tier the reference doesn't have: SURVEY.md §5 'race detection:
    none').  Size 4 with 2 segments so the slot-ack flow-control path
    (ack waits at hop>2, releases through hop 2P-4) actually executes.
    The detector only *prints* findings, so assert on captured stdout."""
    _interpreter_only()
    mesh = _mesh(4)
    n = 4 * 2 * 8 * 128
    data = jnp.ones((4, n), jnp.float32)
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_allreduce(
                x[0], "x", num_segments=2,
                interpret=pltpu.InterpretParams(detect_races=True),
            )[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    np.testing.assert_allclose(out, np.full((4, n), 4.0))
    assert "RACE DETECTED" not in capsys.readouterr().out


def test_empty_input_edge_cases():
    empty = jnp.zeros((0,), jnp.float32)
    assert pk.combine(empty, empty).shape == (0,)
    assert pk.cast(empty, jnp.bfloat16).shape == (0,)
    v, s, n = pk.quantize_int8(empty)
    assert pk.dequantize_int8(v, s, n, (0,)).shape == (0,)


def test_int8_dtype_restore():
    x = jnp.asarray(np.random.default_rng(9).normal(size=64), jnp.bfloat16)
    v, s, n = pk.quantize_int8(x)
    back = pk.dequantize_int8(v, s, n, x.shape, dtype=x.dtype)
    assert back.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# fused compute + put (device-initiated communication, vadd_put role)
# ---------------------------------------------------------------------------


def test_fused_shift_put():
    mesh = _mesh(4)
    n = 700
    data = jnp.asarray(
        np.random.default_rng(7).normal(size=(4, n)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.fused_shift(
                x[0], "x", 1, lambda v: v * 2.0
            )[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    expect = np.roll(np.asarray(data) * 2.0, 1, axis=0)
    np.testing.assert_allclose(out, expect)


def test_vadd_put_pallas_example():
    from accl_tpu.examples.vadd_put import vadd_put_pallas
    from accl_tpu.ops import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = make_mesh(4)
    data = np.arange(4 * 300, dtype=np.float32).reshape(4, 300)
    out = np.asarray(vadd_put_pallas(data, mesh, increment=1.0))
    np.testing.assert_allclose(out, np.roll(data + 1.0, 1, axis=0))


# ---------------------------------------------------------------------------
# ring attention kernel (long-context flagship on the Pallas substrate)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_ring_attention(causal):
    from accl_tpu.models.ring_attention import reference_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    B, H, T, D = 1, 2, 4 * 16, 64  # global T = 64, 16 rows per device
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    fn = jax.jit(
        shard_map(
            lambda q, k, v: pk.attention.ring_attention(
                q, k, v, "sp", causal=causal
            ),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = np.asarray(fn(q, k, v))
    expect = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=2e-3)


def test_pallas_ring_attention_matches_ppermute_version():
    """The kernel and the model-level ppermute formulation must agree —
    same strategy, two substrates (SURVEY.md §5: the ring machinery is the
    substrate; both express the same schedule)."""
    from accl_tpu.models.ring_attention import ring_attention as ra_ppermute

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    B, H, T, D = 2, 2, 4 * 8, 32
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    specs = (P(None, None, "sp", None),) * 3

    def run(body):
        return np.asarray(
            jax.jit(
                shard_map(
                    body, mesh=mesh, in_specs=specs,
                    out_specs=P(None, None, "sp", None), check_vma=False,
                )
            )(q, k, v)
        )

    a = run(lambda q, k, v: pk.attention.ring_attention(q, k, v, "sp"))
    # the XLA form at true-f32 matmul precision: the MXU's DEFAULT
    # multiplies f32 in one bf16 pass, the kernel asks for HIGHEST
    # (first run on four chips, PR 21: 1 of 4096 off by 2.2e-3)
    with jax.default_matmul_precision("highest"):
        b = run(lambda q, k, v: ra_ppermute(q, k, v, "sp"))
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_pallas_ring_attention_race_free(capsys):
    """Regression for the slot-ack ordering bug: with 4 ranks the ack for
    slot s%2 must not be released until the forwarding DMA reading it has
    drained — the interpreter's vector-clock detector catches the
    premature-release variant as a write/read race on the comm scratch."""
    from accl_tpu.models.ring_attention import reference_attention

    _interpreter_only()
    if len(jax.devices()) < 5:
        pytest.skip("needs 5 devices")
    # 5 ranks: 4 hops, so BOTH comm slots get reused (gates at hops 3 and
    # 4, releases at s=2 and s=3) — the full flow-control surface
    mesh = Mesh(np.array(jax.devices()[:5]), ("sp",))
    B, H, T, D = 1, 1, 5 * 8, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    fn = jax.jit(
        shard_map(
            lambda q, k, v: pk.attention.ring_attention(
                q, k, v, "sp",
                interpret=pltpu.InterpretParams(detect_races=True),
            ),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = np.asarray(fn(q, k, v))
    expect = np.asarray(reference_attention(q, k, v))
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=2e-3)
    assert "RACE DETECTED" not in capsys.readouterr().out


def test_pallas_ring_attention_validates_qkv():
    with pytest.raises(ValueError, match="shapes"):
        pk.attention.ring_attention(
            jnp.zeros((1, 1, 8, 32)), jnp.zeros((1, 1, 16, 32)),
            jnp.zeros((1, 1, 8, 32)), "sp",
        )
    with pytest.raises(ValueError, match="dtypes"):
        pk.attention.ring_attention(
            jnp.zeros((1, 1, 8, 32), jnp.float32),
            jnp.zeros((1, 1, 8, 32), jnp.bfloat16),
            jnp.zeros((1, 1, 8, 32), jnp.bfloat16), "sp",
        )


def test_ring_allreduce_bidirectional():
    """Bidirectional ring: the operand's halves travel opposite directions
    (both ICI links carry payload — pallas_guide bi-directional pattern).
    Sizes stay small: the interpreter's on_wait semaphore loop busy-spins,
    which convoys on few-core CI hosts at larger transfers."""
    mesh = _mesh(4)
    for n in (2 * 4 * 8 * 128, 1000):  # exact packing + ragged
        data = jnp.asarray(
            np.random.default_rng(8).normal(size=(4, n)), jnp.float32
        )
        fn = jax.jit(
            shard_map(
                lambda x: pk.ring_allreduce(
                    x[0], "x", bidirectional=True
                )[None],
                mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                check_vma=False,
            )
        )
        out = np.asarray(fn(data))
        expect = np.asarray(data).sum(0)
        for r in range(4):
            np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# alltoall kernel + Ulysses attention (all-to-all context parallelism)
# ---------------------------------------------------------------------------


def test_pallas_alltoall():
    mesh = _mesh(4)
    n_per = 4 * 50
    data = np.arange(4 * n_per * 3, dtype=np.float32).reshape(4, n_per, 3)
    fn = jax.jit(
        shard_map(
            lambda x: pk.alltoall_kernel(x[0], "x")[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(jnp.asarray(data)))
    expect = (
        data.reshape(4, 4, 50, 3).transpose(1, 0, 2, 3).reshape(4, n_per, 3)
    )
    np.testing.assert_array_equal(out, expect)


def test_pallas_alltoall_validates():
    mesh = _mesh(2)
    fn = jax.jit(
        shard_map(
            lambda x: pk.alltoall_kernel(x, "x"),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
    )
    with pytest.raises(ValueError, match="divisible"):
        fn(jnp.zeros((7, 3)))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_ulysses_attention(use_pallas):
    from accl_tpu.models import ulysses_attention
    from accl_tpu.models.ring_attention import reference_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    B, H, T, D = 1, 4, 4 * 8, 32
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    fn = jax.jit(
        shard_map(
            lambda q, k, v: ulysses_attention(
                q, k, v, "sp", use_pallas_alltoall=use_pallas
            ),
            mesh=Mesh(np.array(jax.devices()[:4]), ("sp",)),
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = np.asarray(fn(q, k, v))
    expect = np.asarray(reference_attention(q, k, v))
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=2e-3)


def test_ulysses_matches_ring_attention():
    """Both context-parallel strategies compute the same function."""
    from accl_tpu.models import ulysses_attention
    from accl_tpu.models.ring_attention import ring_attention as ra

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    B, H, T, D = 1, 4, 4 * 8, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5 for kk in keys
    )
    specs = (P(None, None, "sp", None),) * 3

    def run(body):
        return np.asarray(
            jax.jit(
                shard_map(
                    body, mesh=mesh, in_specs=specs,
                    out_specs=P(None, None, "sp", None), check_vma=False,
                )
            )(q, k, v)
        )

    a = run(lambda q, k, v: ulysses_attention(q, k, v, "sp"))
    b = run(lambda q, k, v: ra(q, k, v, "sp"))
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("bidirectional", [False, True])
def test_ring_allreduce_wire_compression(bidirectional):
    """bf16 on the wire, f32 accumulation — the ETH_COMPRESSED /
    hp_compression composition executed inside the kernel (compress lane
    before each DMA, decompress after)."""
    mesh = _mesh(4)
    n = 4 * 8 * 128
    data = jnp.asarray(
        np.random.default_rng(10).normal(size=(4, n)), jnp.float32
    )
    fn = jax.jit(
        shard_map(
            lambda x: pk.ring_allreduce(
                x[0], "x", wire_dtype=jnp.bfloat16,
                bidirectional=bidirectional,
            )[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    expect = np.asarray(data).sum(0)
    # bf16 wire: ~3 decimal digits of mantissa
    np.testing.assert_allclose(out[0], expect, rtol=3e-2, atol=3e-2)
    # and it must NOT be bit-identical to the uncompressed path (the wire
    # really was narrowed)
    assert not np.array_equal(out[0], expect)


@pytest.mark.parametrize("mdt_name", ["float8_e4m3fn", "float8_e5m2"])
def test_cast_fp8(mdt_name):
    """Kernel-tier fp8 compression lane (beyond the reference's f16-only
    hp_compression): tiled cast down to fp8 and back."""
    import ml_dtypes

    mdt = getattr(ml_dtypes, mdt_name)
    x = jnp.asarray(
        np.random.default_rng(9).standard_normal(1000).astype(np.float32)
    )
    down = pk.cast(x, mdt)
    assert down.dtype == np.dtype(mdt)
    up = pk.cast(down, jnp.float32)
    expect = np.asarray(x).astype(mdt).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(up), expect)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_naive(causal):
    """Single-chip flash kernel == materialized-softmax attention."""
    rng = np.random.default_rng(21)
    B, H, T, D = 2, 2, 96, 32
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )
    got = pk.flash_attention(q, k, v, causal=causal, block=32)

    # reference at true-f32 matmul precision: the TPU MXU's DEFAULT
    # multiplies f32 in one bf16 pass (~1e-1 error), which the 2e-5
    # comparison against the HIGHEST-precision kernel would expose
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        expect = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_attention_ragged_and_padded():
    """T not a block multiple and D below the lane width both pad
    internally; results still match the naive form."""
    rng = np.random.default_rng(22)
    B, H, T, D = 1, 3, 50, 24
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )
    got = pk.flash_attention(q, k, v, block=16)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        expect = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_attention_ragged_default_block():
    """T below the default block and NOT a sublane multiple: the block
    height must round up to the sublane grid (f32: 8), not shrink to an
    unalignable tile (Mosaic would reject (1, 50, D) f32 tiles)."""
    rng = np.random.default_rng(23)
    B, H, T, D = 1, 2, 50, 16
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )
    got = pk.flash_attention(q, k, v)  # default block=512
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        expect = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )


def test_flash_attention_validates():
    with pytest.raises(ValueError, match="must match"):
        pk.flash_attention(
            jnp.zeros((1, 1, 8, 8)), jnp.zeros((1, 1, 8, 8)),
            jnp.zeros((1, 1, 16, 8)),
        )


#: name -> (B, H, Hkv, T, D, dtype, block): every case has three or more
#: tiles a sequence, so under the causal skip a q tile's dq rows are summed
#: over up to that many k steps of the ONE backward kernel's grid
_FLASH_GRAD_CASES = {
    "mha_t96": (2, 2, 2, 96, 32, jnp.float32, 32),
    "mqa_g4_t128": (1, 4, 1, 128, 32, jnp.float32, 32),
    "gqa_g2_t96": (2, 4, 2, 96, 32, jnp.float32, 32),
    # Tp = 128: the fourth tile is 4 real rows and 28 of padding
    "ragged_t100_d24": (1, 2, 2, 100, 24, jnp.float32, 32),
    "bf16_mqa_t96": (1, 4, 1, 96, 32, jnp.bfloat16, 32),
    "bf16_ragged_t100": (1, 2, 2, 100, 24, jnp.bfloat16, 32),
    # five tiles: the last two rows fold three and four INTERIOR tiles (no
    # mask at all) before their diagonal one
    "mha_t160_interior": (1, 2, 2, 160, 32, jnp.float32, 32),
    # Tp = 160: three interior tiles in row 3, then the padded last row and
    # (backward) the padded last q tile of every k tile; not causal, every
    # tile but those is interior
    "gqa_g2_ragged_t150": (1, 4, 2, 150, 24, jnp.float32, 32),
    "bf16_gqa_ragged_t150": (1, 4, 2, 150, 24, jnp.bfloat16, 32),
}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("case", list(_FLASH_GRAD_CASES))
def test_flash_attention_grads_match_naive(case, causal):
    """The custom_vjp backward kernel (dq, dk and dv from ONE rebuild of a
    tile pair's probabilities out of the saved logsumexp) == autodiff
    through the materialized-softmax form."""
    B, H, Hkv, T, D, dtype, block = _FLASH_GRAD_CASES[case]
    rng = np.random.default_rng(24)
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), dtype)
    k, v = (
        jnp.asarray(rng.standard_normal((B, Hkv, T, D)), dtype)
        for _ in range(2)
    )
    w = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)

    def naive(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    # jitted: one program a side, its results waited for below
    got = jax.jit(jax.grad(
        loss(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=causal, block=block)),
        argnums=(0, 1, 2),
    ))(q, k, v)
    with jax.default_matmul_precision("highest"):
        expect = jax.jit(jax.grad(loss(naive), argnums=(0, 1, 2)))(q, k, v)
    assert got[1].shape == (B, Hkv, T, D)  # kv grads at kv-head count
    for a, b, name in zip(got, expect, "qkv"):
        assert a.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(
                a, b, rtol=2e-4, atol=_GRAD_ATOL, err_msg=f"d{name}")
        else:
            # bfloat16: p and ds rounded once before their products, each
            # result rounded once from its float32 sum (the bound
            # tests/test_grouped_matmul.py uses for the same reason)
            assert np.abs(a - b).max() <= 2 ** -7 * np.abs(b).max(), name


def test_train_step_text_has_two_flash_kernels_an_attention():
    """A train step calls two Pallas kernels a layer, ``flash_fwd`` and
    ``flash_bwd`` (no separate dq kernel), and the benchmark's reader
    (``perfbench/scope_ops.py``: innermost ``accl.<x>::<y>`` of an ENTRY
    instruction of the COMPILED text) finds everything under either name
    in ``accl.attn::core``.  On the chip the compiled text holds one
    ``tpu_custom_call`` a call there (CHANGES.md, PR 30); here the
    interpreter's expansion of each."""
    from accl_tpu.models import (
        TransformerConfig,
        init_params,
        make_sharded_train_step,
    )
    from perfbench import scope_ops

    cfg = TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_kv_heads=1, n_layers=2, d_ff=64,
        max_seq=64, attention="flash",
    )
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(cfg, mesh, lr=0.05)
    params = shard(init_params(jax.random.PRNGKey(0), cfg))
    tok = jnp.zeros((1, 48), jnp.int32)
    traced = step.trace(params, tok, tok)
    jaxpr = str(traced.jaxpr)
    assert len(re.findall(r"pallas_call\[", jaxpr)) == 2 * cfg.n_layers
    assert collections.Counter(re.findall(r"name=(flash\w*)", jaxpr)) == {
        "flash_fwd": cfg.n_layers, "flash_bwd": cfg.n_layers,
    }

    compiled = traced.lower().compile().as_text()
    core = set(scope_ops.scopes_of(compiled)["accl.attn::core"])
    start = compiled.find("\nENTRY ")
    by_name = collections.Counter()
    for line in compiled[start:compiled.find("\n}", start)].splitlines():
        m = re.match(
            r'\s*(?:ROOT )?%(\S+) = .*op_name="[^"]*'
            r'accl\.attn::core\)*/(\w+)/(?:pallas_call|io_callback)', line
        )
        if m:
            assert m[1] in core, line[:200]
            by_name[m[2]] += 1
    assert set(by_name) == {"flash_fwd", "flash_bwd"}


#: name -> (window, T).  The window against the tile (block 32): inside
#: one tile, a whole number of tiles, not a whole number, and wider than
#: the sequence, on T = 100 (three tiles and 4 rows: the last row of tiles
#: is the padded class).  Then the bodies by tile class: eight whole tiles
#: under a window of 4 tiles and 22 keys (three interior tiles a row
#: between two window-edge tiles and the diagonal), the same edge on a
#: padded T, and a window narrower than the tile on whole tiles (the
#: diagonal tile compares the window too).
_FLASH_WINDOWS = {
    "under_a_block": (8, 100), "two_blocks": (64, 100), "ragged": (40, 100),
    "past_t": (200, 100), "interior_between_edges": (150, 256),
    "interior_and_padded": (100, 250), "under_a_block_whole_tiles": (8, 160),
}


def _windowed_naive(q, k, v, window):
    """Materialized softmax under ``0 <= i - j < window``; GQA by repeat."""
    H, Hkv, T, D = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    s = jnp.where((dist >= 0) & (dist < window), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("window", list(_FLASH_WINDOWS))
def test_flash_attention_window_fwd_and_grads_match_masked_naive(window):
    """``flash_attention(window=)`` with GQA (G=2), D=24 padded to the
    lanes and T ragged (100 = three tiles of 32 and 4 rows) or long enough
    for interior tiles under the window: forward, dq, dk and dv against
    the masked naive form; a window the sequence does not reach is plain
    causal attention BIT FOR BIT."""
    W, T = _FLASH_WINDOWS[window]
    rng = np.random.default_rng(31)
    B, H, Hkv, D = 1, 4, 2, 24
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    k, v = (
        jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
        for _ in range(2)
    )
    w = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)

    def run(fn):
        def loss(q, k, v):
            out = fn(q, k, v)
            return (out * w).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    flash = lambda **kw: lambda q, k, v: pk.flash_attention(
        q, k, v, block=32, **kw)
    (_, got), grads = run(flash(window=W))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = run(lambda q, k, v: _windowed_naive(q, k, v, W))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    for a, b, name in zip(grads, want_grads, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=_GRAD_ATOL,
            err_msg=f"d{name}")
    if W >= T:
        (_, causal), causal_grads = run(flash())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(causal))
        for a, b in zip(grads, causal_grads):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        assert np.abs(np.asarray(got) - np.asarray(run(flash())[0][1])).max() > 1e-3


def test_flash_attention_window_validates_and_other_lowerings_agree():
    q = jnp.asarray(
        np.random.default_rng(32).standard_normal((1, 2, 64, 16)), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, q, q, causal=False, window=8)
    with pytest.raises(ValueError, match="at least 1"):
        pk.flash_attention(q, q, q, window=0)
    from accl_tpu.models.transformer import _attention

    with jax.default_matmul_precision("highest"):
        want = _windowed_naive(q, q, q, 24)
        for impl in ("naive", "blockwise", "flash"):
            np.testing.assert_allclose(
                np.asarray(_attention(q, q, q, impl=impl, window=24)),
                np.asarray(want), rtol=2e-5, atol=2e-5, err_msg=impl)


def _tile_pairs_by_hand(T, b, window):
    """Tile pairs that hold at least one (query, key) of the mask."""
    n = -(-T // b)
    pairs = 0
    for iq in range(n):
        for jk in range(n):
            # the closest query and key of the two tiles, the farthest
            nearest = max(iq * b - (jk * b + b - 1), 0)
            farthest = iq * b + b - 1 - jk * b
            pairs += farthest >= 0 and (window is None or nearest < window)
    return pairs


@pytest.mark.parametrize("T,block,window,pairs", [
    (8192, 512, None, 136),     # 16 tiles: 16 * 17 / 2
    (8192, 512, 2048, 70),      # rows 0-3: 1+2+3+4, rows 4-15: 5 each
    (8192, 512, 8192, 136),     # a window the sequence does not reach
    (8192, 512, 2561, 81),      # one tile further: 1+..+5, then 11 x 6
    (8192, 512, 512, 31),       # the diagonal and the tile before it
    (8192, 512, 1, 16),         # the diagonal alone
    (4096, 512, 2048, 30),      # 8 tiles: 10 + 4 x 5
    (100, 32, 40, 9),           # the ragged case above: 1 + 2 + 3 + 3
    (100, 32, 8, 7),
])
def test_flash_tile_pairs_counted_from_the_shapes(T, block, window, pairs):
    """The tile pairs the kernels visit, by their own bounds, forward and
    backward alike, against a count of the tile pairs the mask touches."""
    assert pk.flash_tile_pairs(T, block, window) == pairs
    from accl_tpu.ops.pallas.attention import _flash_block, _window_q_tiles

    b = _flash_block(T, jnp.bfloat16, block)
    n = -(-T // b)
    w = None if window is None or window >= T else window
    assert _tile_pairs_by_hand(T, b, w) == pairs
    backward = 0
    for jk in range(n):
        lo, hi = _window_q_tiles(jk, b, n, w)
        backward += int(hi) - int(lo)
    assert backward == pairs


def _every_tile_mask(iq, jk, b, T, causal, window, with_q=False):
    """The mask the kernels built on EVERY visited tile pair before there
    were tile classes: positions from an iota, compared with ``T``, with
    each other and with the window, ANDed."""
    q_pos = iq * b + jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    k_pos = jk * b + jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    mask = k_pos < T
    if with_q:
        mask &= q_pos < T
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _visited(fixed, n, b, causal, window, padded, forward):
    """``(x, class)`` of every tile a grid step ``fixed`` folds, by the
    kernels' own bounds and ranges, in the order it folds them."""
    from accl_tpu.ops.pallas import attention as fa

    for start, stop, cls in fa._tile_ranges(
        fixed, n, b, causal, window, padded, forward=forward
    ):
        for x in range(int(start), int(stop)):
            yield x, cls


def _tile_classes_by_hand(T, b, window):
    """The class of every tile pair the mask touches, from the closest and
    the farthest (query, key) of the two tiles."""
    n = -(-T // b)
    counts = collections.Counter()
    for iq in range(n):
        for jk in range(n):
            nearest = max(iq * b - (jk * b + b - 1), 0)
            farthest = iq * b + b - 1 - jk * b
            if farthest < 0 or (window is not None and nearest >= window):
                continue
            if T % b and n - 1 in (iq, jk):
                counts["padded"] += 1
            elif iq == jk:
                counts["diagonal"] += 1
            elif window is not None and farthest >= window:
                counts["edge"] += 1
            else:
                counts["interior"] += 1
    return counts


@pytest.mark.parametrize("T,block,window,classes", [
    # (interior, diagonal, window-edge, padded); the four train cells'
    # layers first: StarCoder and Trinity's full layer at 8192, Trinity's
    # sliding layers, OLMoE at 4096, StarCoder at 1024
    (8192, 512, None, (120, 16, 0, 0)),
    (8192, 512, 2048, (42, 16, 12, 0)),
    (4096, 512, None, (28, 8, 0, 0)),
    (1024, 512, None, (1, 2, 0, 0)),
    (8192, 512, 2561, (54, 16, 11, 0)),   # one key past 5 tiles: one edge tile a row
    (8192, 512, 2600, (54, 16, 21, 0)),   # no multiple: two edge tiles a row
    (8192, 512, 700, (0, 16, 29, 0)),     # W < 2 tiles: no interior tile
    (8192, 512, 200, (0, 16, 15, 0)),     # W < 1 tile: diagonal and edge at once
    (8000, 512, None, (105, 15, 0, 16)),  # a padded T: the last row of tiles
    (3000, 512, 700, (0, 5, 7, 3)),
    (100, 32, 40, (0, 3, 3, 3)),
])
def test_flash_tile_classes_counted_from_the_shapes(T, block, window, classes):
    """The visited tile pairs by class, forward and backward by the same
    ranges, and their sum against ``flash_tile_pairs``."""
    from accl_tpu.ops.pallas import attention as fa

    want = dict(zip(fa.TILE_CLASSES, classes))
    assert fa.flash_tile_classes(T, block, window) == want
    assert pk.flash_tile_pairs(T, block, window) == sum(classes)
    b = fa._flash_block(T, jnp.bfloat16, block)
    n = -(-T // b)
    assert _tile_classes_by_hand(T, b, window) == collections.Counter(
        {c: k for c, k in want.items() if k})
    for forward in (True, False):
        got = collections.Counter(
            cls for y in range(n)
            for _, cls in _visited(y, n, b, True, window, T % b != 0, forward)
        )
        assert {c: got[c] for c in fa.TILE_CLASSES} == want, forward


@pytest.mark.parametrize("causal,T,window", [
    (True, 160, None), (True, 256, 150), (True, 256, 64), (True, 256, 65),
    (True, 160, 8), (True, 160, 1), (True, 150, None), (True, 250, 100),
    (True, 250, 40), (True, 20, None), (False, 160, None), (False, 150, None),
])
def test_flash_tile_class_masks_have_the_every_tile_masks_truth(causal, T, window):
    """Tiles of 32: on every pair either kernel visits, the mask of the
    pair's class (one compare of the hoisted ``row - col`` against a scalar
    from the tiles' distance; none on an interior tile) has the truth
    values of the mask built from the positions, and the pairs come in
    the order they came in (k tiles ascending in the forward, q tiles
    ascending in the backward)."""
    from accl_tpu.ops.pallas import attention as fa

    b = 32
    n = -(-T // b)
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    for forward in (True, False):
        for y in range(n):
            xs = []
            for x, cls in _visited(y, n, b, causal, window, T % b != 0, forward):
                xs.append(x)
                iq, jk = (y, x) if forward else (x, y)
                pad = [cols < T - jk * b] + ([] if forward else [rows < T - iq * b])
                got = fa._tile_mask(
                    cls, rows - cols, iq - jk, b, causal, window,
                    pad if cls == "padded" else [],
                )
                want = _every_tile_mask(iq, jk, b, T, causal, window,
                                        with_q=not forward)
                assert (cls == "interior") == (got is None), (y, x, cls)
                if got is None:
                    got = jnp.ones((b, b), bool)
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want), err_msg=f"{y} {x} {cls}")
            bounds = fa._window_k_tiles if forward else fa._window_q_tiles
            lo, hi = bounds(y, b, n, window) if causal else (0, n)
            assert xs == list(range(int(lo), int(hi))), (forward, y)


def _every_tile_masked_fold(q, k, v, g, causal, block, window):
    """``o``, ``dq``, ``dk``, ``dv`` of ``flash_attention`` by the
    arithmetic the kernels had before the tile classes, written in
    ``jax.numpy``: the same tiles in the same order, the same products on
    the same padded tiles, and on EVERY visited pair the mask from the
    positions and the select.  One jitted function a tile pair and pass."""
    from accl_tpu.ops.pallas import attention as fa

    B, H, T, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    scale = 1.0 / D ** 0.5
    b = fa._flash_block(T, q.dtype, block)
    padT, padD = (-T) % b, (-D) % fa.LANES
    n, Dp = (T + padT) // b, D + padD
    if window is not None and window >= T:
        window = None
    pad = lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, padT), (0, padD)])
    qp, kp, vp, gp = pad(q), pad(k), pad(v), pad(g)

    def tile(a, bh, i):
        h = bh % H
        return a[bh // H, h if a.shape[1] == H else h // G, i * b:(i + 1) * b]

    def dot(a, c, dims):
        return jax.lax.dot_general(
            a, c, (dims, ((), ())), preferred_element_type=jnp.float32,
            precision=fa._mxu_precision(a.dtype))

    @jax.jit
    def fwd_pair(m, l, acc, qb, kb, vb, i, j):
        s = dot(qb, kb, ((1,), (1,))) * scale
        s = jnp.where(_every_tile_mask(i, j, b, T, causal, window), s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + dot(p.astype(vb.dtype), vb, ((1,), (0,))))

    @jax.jit
    def bwd_pair(dk, dv, dq, qb, kb, vb, dob, lse, delta, i, j):
        s = dot(qb, kb, ((1,), (1,))) * scale
        mask = _every_tile_mask(i, j, b, T, causal, window, with_q=True)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + dot(p.astype(dob.dtype), dob, ((0,), (0,)))
        ds = (p * (dot(dob, vb, ((1,), (1,))) - delta) * scale).astype(qb.dtype)
        return (dk + dot(ds, qb, ((0,), (0,))), dv,
                dq + dot(ds, kb, ((1,), (0,))))

    o, lse = [], []
    for bh in range(B * H):
        for i in range(n):
            m = jnp.full((b, 1), -1e30, jnp.float32)
            l = jnp.zeros((b, 1), jnp.float32)
            acc = jnp.zeros((b, Dp), jnp.float32)
            for j, _ in _visited(i, n, b, causal, window, False, True):
                m, l, acc = fwd_pair(
                    m, l, acc, tile(qp, bh, i), tile(kp, bh, j),
                    tile(vp, bh, j), i, j)
            o.append((acc / jnp.maximum(l, 1e-30)).astype(q.dtype))
            lse.append(m + jnp.log(jnp.maximum(l, 1e-30)))
    out = jnp.concatenate(o).reshape(B, H, n * b, Dp)[:, :, :T, :D]
    lse = jnp.concatenate(lse).reshape(B, H, n * b)[:, :, :T]
    # what _flash_bwd_impl hands its kernel
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    rows = [(0, 0), (0, 0), (0, padT)]
    lse = jnp.pad(lse, rows, constant_values=-1e30).reshape(B * H, n, b, 1)
    delta = jnp.pad(delta, rows).reshape(B * H, n, b, 1)
    dq, dk, dv = [], [], []
    for bh in range(B * H):
        dq_acc = [jnp.zeros((b, Dp), jnp.float32) for _ in range(n)]
        for j in range(n):
            dkj = dvj = jnp.zeros((b, Dp), jnp.float32)
            for i, _ in _visited(j, n, b, causal, window, False, False):
                dkj, dvj, dq_acc[i] = bwd_pair(
                    dkj, dvj, dq_acc[i], tile(qp, bh, i), tile(kp, bh, j),
                    tile(vp, bh, j), tile(gp, bh, i), lse[bh, i],
                    delta[bh, i], i, j)
            dk.append(dkj.astype(q.dtype))
            dv.append(dvj.astype(q.dtype))
        dq += [a.astype(q.dtype) for a in dq_acc]
    dq = jnp.concatenate(dq).reshape(B, H, n * b, Dp)[:, :, :T, :D]

    def group_sum(a):
        a = jnp.concatenate(a).reshape(B, Hkv, G, n * b, Dp)[:, :, :, :T, :D]
        if G == 1:
            return a[:, :, 0]
        return a.astype(jnp.float32).sum(2).astype(k.dtype)

    return out, dq, group_sum(dk), group_sum(dv)


#: name -> (H, Hkv, T, dtype, causal, window), tiles of 32, D = 64
_FLASH_BIT_CASES = {
    "interior_t160": (2, 2, 160, jnp.float32, True, None),
    "window_two_edges_t256": (2, 1, 256, jnp.float32, True, 150),
    "window_under_a_block_t160": (2, 2, 160, jnp.float32, True, 8),
    "ragged_t150_gqa": (4, 2, 150, jnp.float32, True, None),
    "ragged_t250_window": (2, 1, 250, jnp.float32, True, 100),
    "not_causal_ragged_t150": (2, 2, 150, jnp.float32, False, None),
    "bf16_window_t256": (2, 1, 256, jnp.bfloat16, True, 150),
    "bf16_ragged_t150": (2, 2, 150, jnp.bfloat16, True, None),
}


@pytest.mark.parametrize("case", list(_FLASH_BIT_CASES))
def test_flash_attention_bit_equal_to_the_every_tile_masked_fold(case):
    """A select whose mask is all true is the identity and the class
    masks have the old masks' truth values, so ``o``, ``dq``, ``dk`` and
    ``dv`` are the numbers they were when every visited tile was masked:
    equal BIT FOR BIT to that fold written out in ``jax.numpy``.  D = 64,
    so ``scale`` is a power of two: the CPU's compiler contracts ``dot *
    scale - m`` of an interior tile into one fused multiply-add, which
    rounds once where the select in between kept two roundings, and with
    an exact ``scale`` both round alike (on the chip, which has no such
    contraction, the kernels equal the parent's at D = 128: CHANGES.md,
    PR 32)."""
    _interpreter_only()
    H, Hkv, T, dtype, causal, window = _FLASH_BIT_CASES[case]
    rng = np.random.default_rng(33)
    q, g = (jnp.asarray(rng.standard_normal((1, H, T, 64)), dtype)
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, Hkv, T, 64)), dtype)
            for _ in range(2))
    out, vjp = jax.vjp(
        lambda q, k, v: pk.flash_attention(
            q, k, v, causal=causal, block=32, window=window), q, k, v)
    want = _every_tile_masked_fold(q, k, v, g, causal, 32, window)
    for a, b, name in zip((out, *vjp(g)), want, ("o", "dq", "dk", "dv")):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), err_msg=name)


def _pallas_calls(jaxpr, stack=""):
    """``(name stack, equation)`` of every ``pallas_call`` under ``jaxpr``,
    through shard_map, jit and custom_vjp bodies."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield here, eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner, here)


def test_trinity_step_text_has_window_and_core_attention_and_held_experts():
    """The Trinity step's kernel calls sit where the benchmark's readers
    look (``perfbench/scope_ops.py`` on the COMPILED text): a dense layer
    and a period of sliding, sliding, sliding, full: four layers' flash
    kernels under ``accl.attn::window``, one's under ``accl.attn::core``,
    and the four expert layers' grouped matmuls (9 a layer: three
    matrices, forward and two backward forms) under ``accl.moe::experts``,
    the shared expert's products under ``accl.moe::shared``."""
    from accl_tpu.models import (
        LayerKind,
        TransformerConfig,
        init_params,
        make_sharded_train_step,
    )
    from perfbench import scope_ops

    kinds = (
        LayerKind(16, True, "dense", 96), LayerKind(16, True, "moe", 32),
        LayerKind(16, True, "moe", 32), LayerKind(16, True, "moe", 32),
        LayerKind(None, False, "moe", 32),
    )
    cfg = TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32,
        n_layers=5, layers=kinds, d_ff=32, max_seq=64, pos_embedding="rope",
        norm="rmsnorm", ffn="swiglu", qk_norm="head", tie_head=False,
        attn_gate=True, post_norm=True, embed_scale=8.0, n_experts=4,
        moe_top_k=4, moe_capacity_factor=None, moe_aux_weight=0.0,
        moe_router_z_weight=0.0, moe_router="sigmoid", moe_route_scale=2.826,
        moe_bias_rate=0.001, moe_shared_d_ff=32, moe_router_experts=16,
        moe_first_expert=4, attention="flash",
    )
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(cfg, mesh, lr=0.05)
    params = shard(init_params(jax.random.PRNGKey(0), cfg))
    tok = jnp.zeros((1, 48), jnp.int32)
    traced = step.trace(params, tok, tok)
    # every kernel call of the traced step, by the innermost device scope
    # of its name stack: 4 sliding layers to 1 full one, 4 expert layers
    calls = collections.Counter()
    for stack, eqn in _pallas_calls(traced.jaxpr.jaxpr):
        name = re.search(r"flash_\w+|gmm_\w+", str(eqn.params))
        calls[re.findall(r"accl\.\w+::\w+", stack)[-1], name[0]] += 1
    assert calls == {
        ("accl.attn::window", "flash_fwd"): 4,
        ("accl.attn::window", "flash_bwd"): 4,
        ("accl.attn::core", "flash_fwd"): 1,
        ("accl.attn::core", "flash_bwd"): 1,
        ("accl.moe::experts", "gmm_fwd"): 12,
        ("accl.moe::experts", "gmm_dlhs"): 12,
        ("accl.moe::experts", "gmm_drhs"): 12,
    }

    compiled = traced.lower().compile().as_text()
    scopes = scope_ops.scopes_of(compiled)
    start = compiled.find("\nENTRY ")
    found = collections.Counter()
    for line in compiled[start:compiled.find("\n}", start)].splitlines():
        m = re.match(
            r'\s*(?:ROOT )?%(\S+) = .*op_name="[^"]*'
            r'(accl\.\w+::\w+)\)*/(?:[\w()]+/)*'
            r'(flash_(?:fwd|bwd)|gmm_(?:fwd|dlhs|drhs))/', line
        )
        if m:
            assert m[1] in scopes[m[2]], line[:200]
            found[m[2], m[3]] += 1
    assert set(found) == {
        ("accl.attn::window", "flash_fwd"), ("accl.attn::window", "flash_bwd"),
        ("accl.attn::core", "flash_fwd"), ("accl.attn::core", "flash_bwd"),
        ("accl.moe::experts", "gmm_fwd"), ("accl.moe::experts", "gmm_dlhs"),
        ("accl.moe::experts", "gmm_drhs"),
    }
    assert "accl.moe::shared" in scopes and "accl.moe::route" in scopes


def test_flash_attention_grads_ragged_and_padded():
    """Backward with T not a block multiple and D below the lane width:
    the pad rows/cols must contribute exactly zero gradient."""
    rng = np.random.default_rng(25)
    B, H, T, D = 1, 2, 50, 24
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )

    def naive(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    got = jax.grad(
        loss(lambda q, k, v: pk.flash_attention(q, k, v, block=16)),
        argnums=(0, 1, 2),
    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        expect = jax.grad(loss(naive), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, expect, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=_GRAD_ATOL,
            err_msg=f"d{name}",
        )


def test_int8_allreduce_error_bound():
    """End-to-end: blockwise-int8 wire compression over the Pallas ring
    transport (VERDICT r2 item 6).  The result must respect the ANALYTIC
    quantization bound: each rank's contribution errs at most scale/2 per
    element (round-to-nearest with its own tile scale), so the sum errs
    at most sum_r(scale_r)/2 — quantized exactly once, no per-hop
    cascade."""
    mesh = _mesh(4)
    n = 4 * 8 * 128
    rng = np.random.default_rng(33)
    data = jnp.asarray(rng.normal(size=(4, n)) * 3.0, jnp.float32)

    fn = jax.jit(
        shard_map(
            lambda x: pk.int8_allreduce(x[0], "x")[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    expect = np.asarray(data).sum(0)

    # analytic bound: every rank quantizes its full operand with one
    # scale per tile; this shape fits one tile per rank, so the bound is
    # sum over ranks of (absmax_r / 127) / 2 (+ f32 summation slack)
    scales = np.abs(np.asarray(data)).max(axis=1) / 127.0
    bound = scales.sum() / 2.0 + 1e-4
    err = np.abs(out[0] - expect).max()
    assert err <= bound, (err, bound)
    # all ranks agree (it is an ALLreduce)
    for r in range(1, 4):
        np.testing.assert_array_equal(out[r], out[0])
    # and the wire really was narrowed: int8 cannot be bit-exact here
    assert not np.array_equal(out[0], expect)


def test_int8_allreduce_matches_sum_tolerance():
    """Looser sanity at a larger, multi-tile size: relative agreement
    with the true sum at int8 precision."""
    mesh = _mesh(4)
    # 544 packed rows per rank: 544 = 2^5 * 17 has no 32-multiple
    # divisor in [64, 512], so block_rows falls to 32 -> nblk = 17 —
    # the multi-tile scale gather/reshape path is heavily exercised
    n = 544 * 128
    rng = np.random.default_rng(34)
    data = jnp.asarray(rng.normal(size=(4, n)), jnp.float32)
    fn = jax.jit(
        shard_map(
            lambda x: pk.int8_allreduce(x[0], "x", num_segments=2)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )
    )
    out = np.asarray(fn(data))
    expect = np.asarray(data).sum(0)
    np.testing.assert_allclose(out[0], expect, atol=0.1, rtol=0.1)


def test_pallas_striped_ring_attention_matches_reference():
    """The kernel form of striped attention: round-robin shards, every
    hop triangular, exact vs the full-sequence reference."""
    from functools import partial

    from accl_tpu.models import (
        reference_attention, stripe_sequence, unstripe_sequence,
    )

    mesh = _mesh(4)
    B, H, T, D = 1, 2, 64, 32
    rng = np.random.default_rng(80)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )
    fn = jax.jit(
        shard_map(
            partial(pk.attention.ring_attention, axis_name="x",
                    causal=True, striped=True),
            mesh=mesh,
            in_specs=(P(None, None, "x", None),) * 3,
            out_specs=P(None, None, "x", None),
            check_vma=False,
        )
    )
    out = unstripe_sequence(
        fn(stripe_sequence(q, 4), stripe_sequence(k, 4),
           stripe_sequence(v, 4)), 4,
    )
    with jax.default_matmul_precision("highest"):  # as above
        expect = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=2e-4, atol=_GRAD_ATOL
    )


def test_pallas_striped_matches_model_striped():
    """Kernel and ppermute forms of striped attention agree on the same
    striped shards."""
    from functools import partial

    from accl_tpu.models import striped_attention, stripe_sequence

    mesh = _mesh(4)
    B, H, T, D = 1, 2, 32, 16
    rng = np.random.default_rng(81)
    qs, ks, vs = (
        stripe_sequence(
            jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32), 4
        )
        for _ in range(3)
    )
    kernel_fn = jax.jit(
        shard_map(
            partial(pk.attention.ring_attention, axis_name="x",
                    causal=True, striped=True),
            mesh=mesh,
            in_specs=(P(None, None, "x", None),) * 3,
            out_specs=P(None, None, "x", None),
            check_vma=False,
        )
    )
    model_fn = jax.jit(
        shard_map(
            partial(striped_attention, axis_name="x", causal=True),
            mesh=mesh,
            in_specs=(P(None, None, "x", None),) * 3,
            out_specs=P(None, None, "x", None),
            check_vma=False,
        )
    )
    with jax.default_matmul_precision("highest"):  # as above
        expect = np.asarray(model_fn(qs, ks, vs))
    np.testing.assert_allclose(
        np.asarray(kernel_fn(qs, ks, vs)), expect,
        rtol=2e-4, atol=_GRAD_ATOL,
    )


def test_flash_attention_gqa_fwd_and_grads():
    """Grouped-query attention through the flash kernel (kv-head sharing
    via the BlockSpec index map, never expanded) == expanded-kv naive,
    values AND gradients."""
    rng = np.random.default_rng(26)
    B, H, Hkv, T, D = 2, 4, 2, 64, 32
    G = H // Hkv
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)

    def naive(q, k, v):
        kk = jnp.repeat(k, G, axis=1)
        vv = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv)

    got = pk.flash_attention(q, k, v, block=32)
    with jax.default_matmul_precision("highest"):
        expect = naive(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expect), rtol=2e-5, atol=2e-5
    )

    loss = lambda fn: lambda q, k, v: (fn(q, k, v) ** 2).sum()
    g1 = jax.grad(
        loss(lambda q, k, v: pk.flash_attention(q, k, v, block=32)),
        argnums=(0, 1, 2),
    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        g2 = jax.grad(loss(naive), argnums=(0, 1, 2))(q, k, v)
    assert g1[1].shape == (B, Hkv, T, D)  # kv grads at kv-head count
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=_GRAD_ATOL,
            err_msg=f"d{name}",
        )


def test_flash_attention_gqa_validates():
    with pytest.raises(ValueError, match="multiple of kv heads"):
        pk.flash_attention(
            jnp.zeros((1, 4, 16, 8)), jnp.zeros((1, 3, 16, 8)),
            jnp.zeros((1, 3, 16, 8)),
        )


# ---------------------------------------------------------------------------
# heads of two widths, a second score part on shared key heads, a caller's
# scale (the latent mixer's core)
# ---------------------------------------------------------------------------

#: name -> (H, Hkv, rope key heads, D, Dr, Dv, T): T = 100 is three tiles of
#: 32 and four rows, so the padded class is traced too
_FLASH_WIDE_CASES = {
    "mla_128_64_v128": (4, 4, 1, 128, 64, 128, 100),
    "small_32_16_v32": (4, 4, 1, 32, 16, 32, 100),
    "gqa_two_rope_heads": (4, 2, 2, 32, 16, 24, 100),
    "v_narrower_no_rope": (4, 2, 0, 48, 0, 32, 100),
    "v_wider_no_rope": (2, 2, 0, 24, 0, 40, 96),
}
_WIDE_SCALE = 0.11472


def _wide_naive(q, k, v, q_rope, k_rope, scale, window):
    H, T = q.shape[1], q.shape[2]
    rep = lambda a: jnp.repeat(a, H // a.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, rep(k))
    if q_rope is not None:
        s = s + jnp.einsum("bhqd,bhkd->bhqk", q_rope, rep(k_rope))
    dist = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    mask = dist >= 0
    if window is not None:
        mask &= dist < window
    s = jnp.where(mask, s * scale, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), rep(v))


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("case", list(_FLASH_WIDE_CASES))
def test_flash_attention_two_widths_and_a_shared_rope_key(case, window):
    """q and k scored over D + Dr columns (the Dr part of k on fewer heads,
    shared through the index map), v and the output Dv wide, the caller's
    scale, with and without a window: forward, dq, dk, dv and the two rope
    parts' gradients against the naive form."""
    H, Hkv, Hr, D, Dr, Dv, T = _FLASH_WIDE_CASES[case]
    rng = np.random.default_rng(34)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    args = [n(1, H, T, D), n(1, Hkv, T, D), n(1, Hkv, T, Dv)]
    if Dr:
        args += [n(1, H, T, Dr), n(1, Hr, T, Dr)]
    w = n(1, H, T, Dv)

    def run(fn):
        def loss(*a):
            out = fn(*a, *([None, None] if not Dr else []))
            return (out * w).sum(), out
        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(len(args))), has_aux=True))(*args)

    (_, got), grads = run(lambda q, k, v, qr, kr: pk.flash_attention(
        q, k, v, block=32, window=window, scale=_WIDE_SCALE,
        q_rope=qr, k_rope=kr))
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = run(lambda q, k, v, qr, kr: _wide_naive(
            q, k, v, qr, kr, _WIDE_SCALE, window))
    assert got.shape == (1, H, T, Dv)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    names = ("q", "k", "v", "q_rope", "k_rope")
    for a, b, name in zip(grads, want_grads, names):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=_GRAD_ATOL,
            err_msg=f"d{name}")


def test_flash_attention_default_scale_is_over_both_score_parts():
    rng = np.random.default_rng(35)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = n(1, 2, 64, 32), n(1, 2, 64, 32), n(1, 2, 64, 24)
    qr, kr = n(1, 2, 64, 16), n(1, 1, 64, 16)
    got = pk.flash_attention(q, k, v, block=32, q_rope=qr, k_rope=kr)
    with jax.default_matmul_precision("highest"):
        want = _wide_naive(q, k, v, qr, kr, 48 ** -0.5, None)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # and a caller's scale equal to the default is the plain call, bit for bit
    same = pk.flash_attention(q, k, q, block=32, scale=1.0 / (32 ** 0.5))
    np.testing.assert_array_equal(
        np.asarray(same), np.asarray(pk.flash_attention(q, k, q, block=32)))


def test_flash_attention_rope_parts_validate_and_other_lowerings_agree():
    rng = np.random.default_rng(36)
    n = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = n(1, 4, 64, 32), n(1, 4, 64, 32), n(1, 4, 64, 24)
    qr, kr = n(1, 4, 64, 16), n(1, 1, 64, 16)
    with pytest.raises(ValueError, match="come together"):
        pk.flash_attention(q, k, v, q_rope=qr)
    with pytest.raises(ValueError, match="second part"):
        pk.flash_attention(q, k, v, q_rope=qr, k_rope=n(1, 3, 64, 16))
    with pytest.raises(ValueError, match="second part"):
        pk.flash_attention(q, k, v, q_rope=qr, k_rope=n(1, 1, 64, 8))
    with pytest.raises(ValueError, match="outside the width"):
        pk.flash_attention(q, k, n(1, 2, 64, 24))
    from accl_tpu.models.transformer import _attention

    with jax.default_matmul_precision("highest"):
        want = _wide_naive(q, k, v, qr, kr, _WIDE_SCALE, 24)
        for impl in ("naive", "blockwise", "flash"):
            got = _attention(q, k, v, impl=impl, window=24, scale=_WIDE_SCALE,
                             q_rope=qr, k_rope=kr)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5,
                err_msg=impl)


# -- the block-diffusion layout (PR 38) ---------------------------------------


def _block_diffusion_naive(q, k, v, L, B, scale=None, q_rope=None, k_rope=None):
    """Materialised scores under the dense mask of
    ``ops.attention.block_diffusion_visible``, float32."""
    from accl_tpu.ops.attention import block_diffusion_visible

    Bn, H, T, D = q.shape
    Hkv = k.shape[1]
    if q_rope is not None:
        expand = lambda t: jnp.repeat(t, Hkv // t.shape[1], axis=1)
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([k, expand(k_rope)], axis=-1)
    qg = q.astype(jnp.float32).reshape(Bn, Hkv, H // Hkv, T, q.shape[-1])
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k.astype(jnp.float32)) * (
        q.shape[-1] ** -0.5 if scale is None else scale
    )
    pos = jnp.arange(T)
    mask = block_diffusion_visible(pos[:, None], pos[None, :], L, B)
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return out.reshape(Bn, H, T, v.shape[-1])


def _live_tiles(L, B, b):
    """Brute force: which (q tile, k tile) pairs of the padded halves hold
    a live (query, key) pair."""
    from accl_tpu.ops.attention import block_diffusion_visible

    n = -(-L // b)
    half = n * b
    pos = np.arange(2 * half)
    real = (pos % half) < L
    rows = np.where(pos < half, pos, pos - half + L)   # the unpadded index
    live = np.asarray(
        block_diffusion_visible(rows[:, None], rows[None, :], L, B)
    ) & real[:, None] & real[None, :]
    return live.reshape(2 * n, b, 2 * n, b).any(axis=(1, 3))


#: name -> (L, block length, tile, q heads, kv heads, dtype)
_BLOCK_DIFFUSION_CASES = {
    "b4": (64, 4, 16, 2, 2, jnp.float32),
    "b1": (64, 1, 16, 2, 2, jnp.float32),
    "b32_two_tiles_a_block": (64, 32, 16, 2, 2, jnp.float32),
    "b32_half_a_tile": (64, 32, 64, 2, 2, jnp.float32),
    "one_block": (64, 64, 16, 2, 2, jnp.float32),
    "padded_L": (40, 4, 16, 2, 2, jnp.float32),
    "padded_L_one_block_in_the_last_tile": (56, 8, 16, 2, 2, jnp.float32),
    "gqa_8_to_1": (48, 4, 16, 8, 1, jnp.float32),
    "bf16_padded": (40, 4, 16, 4, 2, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(_BLOCK_DIFFUSION_CASES))
def test_flash_attention_block_diffusion_fwd_and_grads_match_the_dense_mask(case):
    """The flash kernels (interpreted) under ``block_diffusion=(L, B)``
    against the naive dense mask: the output and all three gradients."""
    L, B, tile, H, Hkv, dtype = _BLOCK_DIFFUSION_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    shape = lambda h: (2, h, 2 * L, 32)
    q = jax.random.normal(ks[0], shape(H), dtype)
    k = jax.random.normal(ks[1], shape(Hkv), dtype)
    v = jax.random.normal(ks[2], shape(Hkv), dtype)
    w = jax.random.normal(ks[3], shape(H), jnp.float32)

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, block=tile, block_diffusion=(L, B))

    want = _block_diffusion_naive(q, k, v, L, B)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32), want, atol=tol, rtol=tol
    )
    got = jax.grad(
        lambda *a: (flash(*a).astype(jnp.float32) * w).sum(), (0, 1, 2)
    )(q, k, v)
    ref = jax.grad(
        lambda *a: (_block_diffusion_naive(*a, L, B) * w).sum(), (0, 1, 2)
    )(q, k, v)
    for g, r in zip(got, ref):
        scale = float(jnp.abs(r.astype(jnp.float32)).max())
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            atol=tol * scale, rtol=tol,
        )


def test_flash_attention_block_diffusion_takes_two_widths_and_a_rope_part():
    """PR 34's forms under the layout: v of another width, a caller's
    scale, a second score part on ONE shared key head."""
    L, B = 32, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    q = jax.random.normal(ks[0], (1, 4, 2 * L, 32))
    k = jax.random.normal(ks[1], (1, 4, 2 * L, 32))
    v = jax.random.normal(ks[2], (1, 4, 2 * L, 16))
    qr = jax.random.normal(ks[3], (1, 4, 2 * L, 8))
    kr = jax.random.normal(ks[4], (1, 1, 2 * L, 8))
    w = jax.random.normal(ks[5], (1, 4, 2 * L, 16))
    flash = lambda *a: pk.flash_attention(
        *a[:3], block=16, scale=0.2, q_rope=a[3], k_rope=a[4],
        block_diffusion=(L, B),
    )
    naive = lambda *a: _block_diffusion_naive(
        *a[:3], L, B, scale=0.2, q_rope=a[3], k_rope=a[4]
    )
    args = (q, k, v, qr, kr)
    np.testing.assert_allclose(flash(*args), naive(*args), atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), range(5))(*args)
    ref = jax.grad(lambda *a: (naive(*a) * w).sum(), range(5))(*args)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, atol=5e-5)


def test_flash_attention_block_diffusion_refuses_by_name_and_the_xla_forms_take_any():
    """A block length that neither divides the tile nor is a multiple of
    it, a window, a T that is not 2 L: refused by name.  The naive and the
    blockwise form take any block length."""
    from accl_tpu.models.transformer import _attention
    from accl_tpu.ops.attention import blockwise_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 96, 32))
    for kw, msg in (
        (dict(block_diffusion=(48, 6)), "divides their tile or is a multiple"),
        (dict(block_diffusion=(48, 5)), "must divide the half"),
        (dict(block_diffusion=(48, 4), window=8), "has no window"),
        (dict(block_diffusion=(40, 4)), "T = 2 L"),
    ):
        with pytest.raises(ValueError, match="block_diffusion.*" + msg):
            pk.flash_attention(q, q, q, block=16, **kw)
    with pytest.raises(ValueError, match="block_diffusion"):
        pk.flash_tile_pairs(96, 16, dtype=jnp.float32, block_diffusion=(48, 6))
    want = _block_diffusion_naive(q, q, q, 48, 6)
    np.testing.assert_allclose(
        blockwise_attention(q, q, q, block_q=16, block_k=16,
                            block_diffusion=(48, 6)), want, atol=2e-5)
    for impl in ("naive", "blockwise"):
        np.testing.assert_allclose(
            _attention(q, q, q, impl=impl, block_diffusion=(48, 6)), want,
            atol=2e-5,
        )
    # and the three lowerings agree where the kernels take the layout
    for impl in ("naive", "blockwise", "flash"):
        np.testing.assert_allclose(
            _attention(q, q, q, impl=impl, block_diffusion=(48, 4)),
            _block_diffusion_naive(q, q, q, 48, 4), atol=2e-5,
        )


@pytest.mark.parametrize("L,B,tile,classes", [
    # interior, block, strict, lower, padded
    (4096, 4, 512, (56, 8, 8, 8, 0)),       # the cell: 80 of 256
    (4096, 1, 512, (56, 8, 8, 8, 0)),
    (4096, 512, 512, (72, 0, 0, 0, 0)),     # a block a tile: nothing masked
    (4096, 1024, 512, (80, 0, 0, 0, 0)),    # two tiles a block
    (4096, 4096, 512, (128, 0, 0, 0, 0)),   # one block: noisy and clean whole
    (1024, 4, 512, (2, 2, 2, 2, 0)),
    (4000, 4, 512, (42, 7, 7, 7, 17)),      # padded halves
    (3600, 16, 512, (42, 7, 7, 7, 16)),     # ONE block in the last tile
    (64, 4, 16, (12, 4, 4, 4, 0)),
    (40, 4, 16, (2, 2, 2, 2, 7)),
])
def test_flash_tile_classes_under_block_diffusion_are_the_live_tiles(
        L, B, tile, classes):
    """``flash_tile_classes`` under the layout against a brute-force count
    of the tile pairs that hold a live (query, key) pair: every visited
    pair is live, every live pair is visited once, forward and backward
    lists alike, by the kernels' own ranges."""
    from accl_tpu.ops.pallas import attention as fa

    dtype = jnp.float32
    want = dict(zip(fa.LAYOUT_TILE_CLASSES, classes))
    for forward in (True, False):
        assert fa.flash_tile_classes(
            2 * L, tile, dtype=dtype, block_diffusion=(L, B), forward=forward
        ) == want, forward
    assert pk.flash_tile_pairs(
        2 * L, tile, dtype=dtype, block_diffusion=(L, B)
    ) == sum(classes)
    b = fa._layout_tile(L, B, dtype, tile)
    n = -(-L // b)
    live = _live_tiles(L, B, b)
    assert live.sum() == sum(classes)
    for forward in (True, False):
        seen = np.zeros_like(live)
        for noisy in (True, False):
            for y in range(n):
                for start, stop, _ in fa._tile_ranges(
                    y, n, b, False, None, L % b, forward, (noisy, B)
                ):
                    for x in range(int(start), int(stop)):
                        me = y if noisy else y + n
                        pair = (me, x) if forward else (x, me)
                        assert not seen[pair], (forward, pair)
                        seen[pair] = True
        assert (seen == live).all(), forward
