"""The main path's kernels at the benchmark's real widths, COMPILED for a
v5e that is described and not attached (the TPU's compiler is installed
where these tests run): what interpret mode cannot show, a tile Mosaic
refuses or more VMEM than a kernel may use, found on the CPU at no chip
time.  Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import (only one
process may hold the TPU's library; the fixture skips where it cannot be
described), and every compile of this tier lives in this one file."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding) for s in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _loss(attend):
    return lambda *a: jax.grad(
        lambda *b: attend(*b).astype(jnp.float32).sum(),
        argnums=tuple(range(len(a))),
    )(*a)


@pytest.mark.parametrize("window", [None, 1024])
def test_flash_kernels_compile_at_deepseek_v2_widths(one_chip, window):
    """``train_dsv2_t4096_b1``'s core: 128 heads of 128 + 64 columns, ONE
    rope key head, v heads of 128, T = 4096."""
    from accl_tpu.ops.pallas.attention import flash_attention

    B, H, T = 1, 128, 4096
    text = _compile(
        _loss(lambda q, k, v, qr, kr: flash_attention(
            q, k, v, window=window, scale=0.11472, q_rope=qr, k_rope=kr,
            interpret=False,
        )),
        [(B, H, T, 128), (B, H, T, 128), (B, H, T, 128), (B, H, T, 64),
         (B, 1, T, 64)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text


@pytest.mark.parametrize("case", ["t8192_mqa", "trinity_window"])
def test_flash_kernels_compile_at_the_other_cells_widths(one_chip, case):
    from accl_tpu.ops.pallas.attention import flash_attention

    H, Hkv, T, window = {
        "t8192_mqa": (32, 1, 8192, None),
        "trinity_window": (32, 4, 8192, 2048),
    }[case]
    text = _compile(
        _loss(lambda q, k, v: flash_attention(
            q, k, v, window=window, interpret=False
        )),
        [(1, H, T, 128), (1, Hkv, T, 128), (1, Hkv, T, 128)],
        one_chip,
    )
    assert "flash_fwd" in text and "flash_bwd" in text


def test_grouped_matmuls_compile_at_deepseek_v2_widths(one_chip):
    """20 held experts of 5120 x 1536 over the cell's 6,144 buffer rows:
    tiles that divide 5120 and 1536 (``grouped_matmul.tiles``)."""
    from accl_tpu.ops.pallas.grouped_matmul import grouped_matmul, tiles

    assert tiles("gmm_fwd", 6144, 5120, 1536, jnp.bfloat16) == (256, 1280, 1536)
    assert tiles("gmm_fwd", 6144, 1536, 5120, jnp.bfloat16) == (256, 1536, 1280)
    sizes = jax.ShapeDtypeStruct((20,), jnp.int32, sharding=one_chip)

    def f(rows, w1, w2, sizes):
        def loss(rows, w1, w2):
            h = grouped_matmul(rows, w1, sizes, interpret=False)
            return grouped_matmul(h, w2, sizes, interpret=False).astype(
                jnp.float32
            ).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(rows, w1, w2)

    args = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in [(6144, 5120), (20, 5120, 1536), (20, 1536, 5120)]
    ]
    text = jax.jit(f).lower(*args, sizes).compile().as_text()
    for name in ("gmm_fwd", "gmm_dlhs", "gmm_drhs"):
        assert name in text
