"""The Mamba-2 mixer's two float32 chains round the SSD core as Mosaic
kernels (``accl_tpu/ops/pallas/mamba_mixer.py``: ``mamba_in_*``,
``mamba_out_*``), interpreted on the CPU, against the XLA forms of
``accl_tpu/ops/ssd.py`` (``_xla_conv_silu``, ``_xla_gated_group_norm``):
forward and the gradient by the projection and by every parameter a chain
has, token-major in and out.

Both lowerings are float32 from the projection on, so they are held to
float32's noise (the order of the taps', the norm's and the gradients' sums
differs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.models.mixers import mamba2 as mixer
from accl_tpu.ops import ssd
from accl_tpu.ops.pallas import kda_mixer as geometry
from accl_tpu.ops.pallas import mamba_mixer as kernels

EPS = 1e-5
#: a chain's (columns, groups): the convolution over two lane tiles; the norm
#: over two groups of two lane tiles each, and over four of one (a column
#: block then holds several groups)
WIDTHS = {"conv": (256, 0), "norm": (512, 2), "norm_lane_groups": (512, 4)}


def _close(got, want, tol=2e-5):
    assert got.dtype == want.dtype
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-3)


def _all_close(got, want, tol=2e-5):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        _close(a, b, tol)


def _with_grads(fn, args, co):
    """``(fn(*args), its gradient by every argument)`` under ``co``."""
    return fn(*args), jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * co),
        argnums=tuple(range(len(args))),
    )(*args)


def _chain(name, T, B=2, wide=None, groups=None, dtype=jnp.float32,
           shut=False):
    """``(kernel form, XLA form, arguments, cotangent)`` of one chain;
    ``shut``: every gate saturated (``SiLU(z)`` at 0 or at ``z``)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    wide = wide or WIDTHS[name][0]
    groups = WIDTHS[name][1] if groups is None else groups
    x = jax.random.normal(ks[0], (B, T, wide))
    co = jax.random.normal(ks[1], (B, T, wide))
    if name == "conv":
        taps = 0.5 * jax.random.normal(ks[2], (4, wide))
        bias = 0.5 * jax.random.normal(ks[3], (wide,))
        return kernels.conv_silu, ssd._xla_conv_silu, (x, taps, bias), co
    y = 3.0 * jax.random.normal(ks[2], (B, T, wide))
    if shut:
        x = 40.0 * jnp.sign(x)
    scale = 1.0 + 0.1 * jax.random.normal(ks[3], (wide,))
    return (
        lambda *a, **kw: kernels.gated_group_norm(*a, groups, EPS, dtype, **kw),
        lambda *a: ssd._xla_gated_group_norm(*a, groups, EPS, dtype),
        (y, x, scale), co,
    )


#: (length, rows a tile at most): one tile; a tile part padding; two tiles of
#: 512, the second nearly all padding (the halo crosses row 512, forward and
#: backward); four tiles of 64, the last part padding (every length its own:
#: the jitted calls are cached a shape, whatever the tile)
LENGTHS = {"128": (128, 512), "200": (200, 512), "520": (520, 512), "232x64": (232, 64)}


@pytest.mark.parametrize("case", LENGTHS)
@pytest.mark.parametrize("name", WIDTHS)
def test_a_chain_against_its_xla_form(name, case, monkeypatch):
    """Forward and the gradient by the projection, the taps and the bias;
    by ``y``, ``z`` and the scale: B = 2, whole tiles and not, a sequence
    one tile long, the first rows against the zero padding, a tile's first
    and last rows against their neighbours' (the convolution's halo, both
    ways, at the first and the last tile)."""
    length, tile = LENGTHS[case]
    monkeypatch.setattr(geometry, "TILE", tile)
    fn, oracle, args, co = _chain(name, length)
    got = _with_grads(fn, args, co)
    assert got[0].dtype == jnp.float32 and got[0].shape == co.shape
    _all_close(got, _with_grads(oracle, args, co))


def test_every_gate_at_its_bound():
    """``SiLU(z)`` at 0 (no gradient reaches y, the row's norm is ``eps``'s)
    or at ``z`` in every column."""
    fn, oracle, args, co = _chain("norm", 200, shut=True)
    _all_close(_with_grads(fn, args, co), _with_grads(oracle, args, co))


@pytest.mark.parametrize("name", ["conv", "norm"])
def test_the_projections_type_is_the_cotangents(name):
    """bfloat16 projections and parameters (the cell's): the chain is
    float32 from the cast on, the norm's output and the projections'
    cotangents come back in bfloat16, ``y``'s in float32."""
    bf16 = jnp.bfloat16
    fn, oracle, args, co = _chain(name, 200, dtype=bf16)
    args = tuple(
        a if name == "norm" and i == 0 else a.astype(bf16)
        for i, a in enumerate(args)
    )
    got, want = _with_grads(fn, args, co), _with_grads(oracle, args, co)
    out, first = (jnp.float32, bf16) if name == "conv" else (bf16, jnp.float32)
    assert got[0].dtype == out and got[1][0].dtype == first
    assert got[1][1].dtype == got[1][2].dtype == bf16
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 2e-5 if a.dtype == jnp.float32 else 1e-2)   # one rounding


@pytest.mark.parametrize("name", ["conv", "norm"])
def test_columns_split_at_tp2_inside_a_checked_shard_map(name):
    """The columns (the norm's groups) split over two devices of a
    ``check_vma`` shard_map (the sharded train step's): results and
    gradients carry the operands' axes and equal the one-device kernels'."""
    wide, groups = WIDTHS[name]
    fn, _, args, co = _chain(name, 96, B=1)
    want = _with_grads(fn, args, co)
    fn = _chain(name, 96, B=1, wide=wide // 2, groups=groups // 2)[0]
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    flat, cols = P(None, None, "tp"), P("tp")
    specs = (flat, P(None, "tp"), cols) if name == "conv" else (flat, flat, cols)
    got = jax.jit(shard_map(
        lambda co, *a: _with_grads(fn, a, co), mesh=mesh,
        in_specs=(flat, *specs), out_specs=(flat, specs), check_vma=True,
    ))(co, *args)
    _all_close(got, want, 1e-6)


@pytest.mark.parametrize("name", ["conv", "norm"])
def test_the_replayed_forward_gives_the_same_gradients(name):
    """Under ``jax.checkpoint`` (the cell runs every block under ``remat``)
    the forward runs again before the backward, and the gradients are the
    plain ones."""
    fn, _, args, co = _chain(name, 200, B=1)
    # the generic interpreter: the TPU interpreter's kernels are host
    # callbacks, which ``jax.checkpoint`` refuses
    plain = lambda *a: fn(*a, interpret=True)
    want = _with_grads(plain, args, co)
    got = _with_grads(jax.checkpoint(plain), args, co)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


# -- the shape rule ----------------------------------------------------------------

#: (x's columns, B's and C's, groups, taps): x's chains by the kernels, B's
#: and C's convolutions by the kernels
SHAPES = {
    "the_cell": (8192, 1024, 8, 4, True, True),
    "the_cell_at_tp2": (4096, 512, 4, 4, True, False),
    "groups_of_one_lane_tile": (2048, 1024, 16, 4, True, True),
    "the_rehearsals_widths": (256, 32, 2, 4, False, False),
    "the_tests_tiny_widths": (64, 16, 2, 4, False, False),
    "part_of_a_column_block": (1536, 128, 1, 4, False, False),
    "taps_beyond_a_halo": (8192, 1024, 8, 8, False, False),
}


@pytest.mark.parametrize("case", SHAPES)
def test_the_shapes_pick_the_lowering(case, monkeypatch):
    """``ops.ssd``'s two chains from the shapes alone (nothing runs):
    whole column blocks take the kernels, every other width (the
    benchmark's ``--rehearse``, the model tests) the XLA forms, unchanged; a
    convolution longer than a halo block keeps x, B and C with XLA and
    leaves the norm to the kernels."""
    wide, state, groups, taps, x_kernel, bc_kernel = SHAPES[case]
    assert kernels.takes(wide, taps=taps) is x_kernel
    assert kernels.takes(state, taps=taps) is bc_kernel
    took = []

    def note(name):
        def lowering(x, *a, **kw):
            took.append(name)
            return jnp.zeros(x.shape)
        return lowering

    for fn in ("conv_silu", "gated_group_norm"):
        monkeypatch.setattr(kernels, fn, note("kernels"))
        monkeypatch.setattr(ssd, "_xla_" + fn, note("xla"))
    struct = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    for C in (wide, state):
        jax.eval_shape(
            lambda *a: ssd.conv_silu(*a), struct(1, 64, C), struct(taps, C), struct(C)
        )
    jax.eval_shape(
        lambda y, z, s: ssd.gated_group_norm(y, z, s, groups, EPS, jnp.float32),
        struct(1, 64, wide), struct(1, 64, wide), struct(wide),
    )
    name = lambda kernel: "kernels" if kernel else "xla"
    assert took == [
        name(x_kernel), name(bc_kernel), name(kernels.takes(wide, groups)),
    ]


def test_a_group_wider_than_a_grid_step_holds_is_xlas():
    assert kernels.takes(8192, 4) and not kernels.takes(8192, 2)
    assert not kernels.takes(8192, 3) and not kernels.takes(1024, 16)


# -- the mixer end to end ------------------------------------------------------------


def _mixer(T, d_model=64, heads=16, width=64, groups=2, state=512, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 16)
    inner, bc = heads * width, groups * state
    matrix = lambda key, n: 0.3 * jax.random.normal(key, (d_model, n))
    taps = lambda key, n: 0.5 * jax.random.normal(key, (4, n))
    bias = lambda key, n: 0.5 * jax.random.normal(key, (n,))
    lp = {
        "wz": matrix(ks[0], inner), "wx": matrix(ks[1], inner),
        "wb": matrix(ks[2], bc), "wc": matrix(ks[3], bc),
        "wdt": matrix(ks[4], heads),
        "conv_x": taps(ks[5], inner), "conv_b": taps(ks[6], bc),
        "conv_c": taps(ks[7], bc),
        "bias_x": bias(ks[8], inner), "bias_b": bias(ks[9], bc),
        "bias_c": bias(ks[10], bc),
        "dt_bias": jax.random.normal(ks[11], (heads,)),
        "a_log": jnp.log(jax.random.uniform(ks[12], (heads,), minval=1.0, maxval=16.0)),
        "d_skip": jnp.ones((heads,)),
        "y_norm": 1.0 + 0.1 * jax.random.normal(ks[13], (inner,)),
        "wo": 0.3 * jax.random.normal(ks[14], (inner, d_model)),
    }
    return jax.random.normal(ks[15], (2, T, d_model)), lp


def test_the_mixer_end_to_end_on_both_lowerings(monkeypatch):
    """``_mamba2_partial`` whole (projections, chains, core, ``W_out``), its
    output and its gradient by the activation and by every parameter: the
    chains as kernels (x's 1,024 columns, B's and C's, two groups of 512)
    against the chains as XLA's fusions, the core the XLA form under both
    (a chunk of 32 is not the kernels')."""
    h, lp = _mixer(72)
    co = jax.random.normal(jax.random.PRNGKey(7), h.shape)
    run = lambda h, lp: mixer._mamba2_partial(
        h, lp, {"state": 512, "chunk": 32, "eps": EPS}
    )
    took = []
    for fn in ("conv_silu", "gated_group_norm"):
        kernel = getattr(kernels, fn)
        monkeypatch.setattr(
            kernels, fn,
            lambda *a, _kernel=kernel: took.append(1) or _kernel(*a),
        )
    both = lambda: (run(h, lp), jax.grad(
        lambda h, lp: jnp.sum(run(h, lp) * co), argnums=(0, 1)
    )(h, lp))
    got = both()
    assert len(took) == 8                    # x, B, C and the norm, twice traced
    monkeypatch.setattr(kernels, "takes", lambda *a, **kw: False)
    want = both()
    assert len(took) == 8
    _all_close(got, want, 1e-4)
