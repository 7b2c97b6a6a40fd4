"""The KDA mixer's three float32 chains round the core as Mosaic kernels
(``accl_tpu/ops/pallas/kda_mixer.py``: ``kda_in_*``, ``kda_decay_*``,
``kda_out_*``), interpreted on the CPU, against the XLA forms of
``accl_tpu/ops/kda.py`` (``_xla_conv_in``, ``_xla_decay_in``,
``_xla_gated_out``): forward and the gradient by the projection and by
every parameter a chain has, at heads of one lane tile.

Both lowerings are float32 from the projection on, so they are held to
float32's noise (the order of the taps', the norms' and the gradients'
sums differs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.models.mixers import kda as mixer
from accl_tpu.ops import kda
from accl_tpu.ops.pallas import kda as core
from accl_tpu.ops.pallas import kda_mixer as kernels

H, D = 2, 128
LOWER, EPS = -5.0, 1e-6


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
        lambda *a: jnp.sum(fn(*a) * co), argnums=tuple(range(len(args)))
    )(*args)


def _chain(name, T, B=2, heads=H, at_bound=False, seed=0):
    """``(kernel form, XLA form, arguments, cotangent)`` of one chain;
    ``at_bound``: every gate saturated (the decay at ``lower_bound``, the
    output gate at 0 or 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = heads * D
    x = jax.random.normal(ks[0], (B, T, wide))
    heads_co = jax.random.normal(ks[1], (B, heads, T, D))
    if name in ("q", "k", "v"):
        how = dict(
            q=dict(unit=True, scale=D ** -0.5), k=dict(unit=True),
            v=dict(unit=False),
        )[name]
        taps = jax.random.normal(ks[2], (4, wide)) * 0.5
        return (
            lambda x, t, **kw: kernels.conv_in(x, t, heads, **how, **kw),
            lambda x, t: kda._xla_conv_in(x, t, heads, **how),
            (x, taps), heads_co,
        )
    if name == "decay":
        bias = jax.random.normal(ks[2], (wide,)) + (40.0 if at_bound else 0.0)
        a_log = jnp.log(jax.random.uniform(ks[3], (heads,), minval=1.0, maxval=16.0))
        return (
            lambda *a, **kw: kernels.decay_in(*a, LOWER, **kw),
            lambda *a: kda._xla_decay_in(*a, LOWER),
            (x, bias, a_log), heads_co,
        )
    assert name == "out"
    o = 3.0 * jax.random.normal(ks[2], (B, heads, T, D))
    if at_bound:
        x = 40.0 * jnp.sign(x)
    scale = 1.0 + 0.1 * jax.random.normal(ks[3], (D,))
    return (
        lambda *a, **kw: kernels.gated_out(*a, EPS, jnp.float32, **kw),
        lambda *a: kda._xla_gated_out(*a, EPS, jnp.float32),
        (o, x, scale), jax.random.normal(ks[4], (B, T, wide)),
    )


CHAINS = ("q", "k", "v", "decay", "out")
#: (length, rows a tile at most): one tile; a tile part padding; two tiles
#: of 512, the second nearly all padding (the halo crosses row 512, forward
#: and backward); four tiles of 64, the last part padding
LENGTHS = {"128": (128, 512), "200": (200, 512), "520": (520, 512), "200x64": (200, 64)}


@pytest.mark.parametrize("case", LENGTHS)
@pytest.mark.parametrize("name", CHAINS)
def test_a_chain_against_its_xla_form(name, case, monkeypatch):
    """Forward and the gradient by the projection and by ``conv_*``,
    ``dt_bias`` and ``a_log``, ``o`` and ``o_norm``: whole tiles and not,
    the first rows against the zero padding, a tile's first and last rows
    against their neighbours' (the convolution's halo, both ways)."""
    length, tile = LENGTHS[case]
    monkeypatch.setattr(kernels, "TILE", tile)
    fn, oracle, args, co = _chain(name, length)
    got = _with_grads(fn, args, co)
    assert got[0].dtype == jnp.float32
    _all_close(got, _with_grads(oracle, args, co))


@pytest.mark.parametrize("name", ["decay", "out"])
def test_every_gate_at_its_bound(name):
    """The decay at ``lower_bound`` in every channel (the sigmoid is 1 in
    float32: no gradient passes), the output gate shut or open."""
    fn, oracle, args, co = _chain(name, 200, at_bound=True)
    got = _with_grads(fn, args, co)
    if name == "decay":
        assert float(jnp.abs(got[0] - LOWER).max()) == 0.0
    _all_close(got, _with_grads(oracle, args, co))


def test_the_projections_type_is_the_cotangents():
    """bfloat16 projections and taps (the cell's): the chain is float32
    from the cast on, the cotangents come back in bfloat16."""
    fn, oracle, (x, taps), co = _chain("q", 200)
    args = (x.astype(jnp.bfloat16), taps.astype(jnp.bfloat16))
    got, want = _with_grads(fn, args, co), _with_grads(oracle, args, co)
    assert got[1][0].dtype == got[1][1].dtype == jnp.bfloat16
    _close(got[0], want[0])
    for a, b in zip(got[1], want[1]):
        _close(a, b, 1e-2)                                # one bfloat16 rounding


@pytest.mark.parametrize("name", CHAINS)
def test_heads_split_at_tp2_inside_a_checked_shard_map(name):
    """The heads split over two devices of a ``check_vma`` shard_map (the
    sharded train step's): results and gradients carry the operands' axes
    and equal the one-device kernels'; ``o_norm``, which no axis splits,
    has its gradient summed over the heads' devices."""
    fn, _, args, co = _chain(name, 96, B=1, heads=2)
    want = _with_grads(fn, args, co)
    fn = _chain(name, 96, B=1, heads=1)[0]               # a device's one head
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    flat, heads, cols = P(None, None, "tp"), P(None, "tp"), P("tp")
    specs, co_spec = {
        "decay": ((flat, cols, cols), heads),
        "out": ((heads, flat, P()), flat),
    }.get(name, ((flat, heads), heads))
    got = jax.jit(shard_map(
        lambda co, *a: _with_grads(fn, a, co), mesh=mesh,
        in_specs=(co_spec, *specs), out_specs=(co_spec, specs), check_vma=True,
    ))(co, *args)
    _all_close(got, want, 1e-6)


@pytest.mark.parametrize("name", CHAINS)
def test_the_replayed_forward_gives_the_same_gradients(name):
    """Under ``jax.checkpoint`` (the cell runs every layer under ``remat``)
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

SHAPES = {
    "the_cell": (4096, 32, 4, True),
    "the_cell_at_tp2": (2048, 16, 4, True),
    "heads_of_two_lane_tiles": (512, 2, 4, True),
    "a_head_of_64": (128, 2, 4, False),
    "a_head_of_96": (192, 2, 4, False),
    "the_tests_tiny_heads": (64, 4, 4, False),
    "taps_beyond_a_halo": (256, 2, 9, False),
}


@pytest.mark.parametrize("case", SHAPES)
def test_the_shapes_pick_the_lowering(case, monkeypatch):
    """``ops.kda``'s three chains from the shapes alone (nothing runs):
    heads of whole lanes take the kernels, every other shape the XLA
    forms; a convolution longer than a halo block keeps q, k and v with
    XLA and leaves the other two chains to the kernels."""
    wide, heads, taps, kernel = SHAPES[case]
    assert kernels.takes(wide, heads, taps) is kernel
    took = []

    def note(name, out):
        def lowering(*a, **kw):
            took.append(name)
            return out
        return lowering

    B, T, d = 1, 64, wide // heads
    struct = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    flat, head_major = struct(B, T, wide), struct(B, heads, T, d)
    for fn, out in (("conv_in", head_major), ("decay_in", head_major), ("gated_out", flat)):
        monkeypatch.setattr(kernels, fn, note("kernels", jnp.zeros(out.shape)))
        monkeypatch.setattr(kda, "_xla_" + fn, note("xla", jnp.zeros(out.shape)))
    jax.eval_shape(
        lambda x, t: kda.conv_in(x, t, heads, unit=True), flat, struct(taps, wide)
    )
    jax.eval_shape(
        lambda x, b, a: kda.decay_in(x, b, a, LOWER), flat, struct(wide), struct(heads)
    )
    jax.eval_shape(
        lambda o, g, s: kda.gated_out(o, g, s, EPS, jnp.float32),
        head_major, flat, struct(d),
    )
    others = "kernels" if kernels.takes(wide, heads) else "xla"
    assert took == ["kernels" if kernel else "xla", others, others]


def test_the_tiles_from_the_shapes():
    """Rows padded to whole tiles of at most :data:`TILE`, walked
    :data:`ROWS` at a time or, a tile those do not divide, at once; as
    many whole heads a column block as :data:`WIDTH` columns hold and the
    head count divides by."""
    assert kernels._geometry(8192, 32, 128) == (512, 256, 8192, 8)
    assert kernels._geometry(8192, 16, 128) == (512, 256, 8192, 8)
    assert kernels._geometry(520, 2, 128) == (512, 256, 1024, 2)
    assert kernels._geometry(200, 6, 256) == (224, 224, 224, 3)
    assert kernels._geometry(8, 1, 2048) == (32, 32, 32, 1)
    assert kernels.HALO % 16 == 0 and kernels.TILE % kernels.ROWS == 0
    assert kernels.ROWS % kernels.EDGE == 0 and kernels.EDGE == 8


# -- the mixer end to end ------------------------------------------------------------


def _mixer(T, d_model=64, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 14)
    wide = H * D
    matrix = lambda key, shape: 0.3 * jax.random.normal(key, shape)
    lp = {
        **{n: matrix(k, (d_model, wide)) for n, k in zip(("wq", "wk", "wv", "wf", "wg"), ks)},
        "wbeta": matrix(ks[5], (d_model, H)),
        **{n: 0.5 * jax.random.normal(k, (4, wide)) for n, k in zip(("conv_q", "conv_k", "conv_v"), ks[6:])},
        "a_log": jnp.log(jax.random.uniform(ks[9], (H,), minval=1.0, maxval=16.0)),
        "dt_bias": jax.random.normal(ks[10], (wide,)),
        "o_norm": 1.0 + 0.1 * jax.random.normal(ks[11], (D,)),
        "wo": matrix(ks[12], (wide, d_model)),
    }
    return jax.random.normal(ks[13], (2, T, d_model)), lp


def test_the_mixer_end_to_end_on_both_lowerings(monkeypatch):
    """``_kda_partial`` whole (projections, chains, core, ``wo``), its
    output and its gradient by the activation and by every parameter: the
    chains as kernels against the chains as XLA's fusions, the core the
    same kernels under both (their products in float32, as a CPU's XLA
    form computes them)."""
    monkeypatch.setattr(core, "_ONE_PASS", jnp.float32)
    h, lp = _mixer(200)
    co = jax.random.normal(jax.random.PRNGKey(7), h.shape)
    run = lambda h, lp: mixer._kda_partial(
        h, lp, H, {"lower_bound": LOWER, "eps": EPS}
    )
    both = lambda: (run(h, lp), jax.grad(
        lambda h, lp: jnp.sum(run(h, lp) * co), argnums=(0, 1)
    )(h, lp))
    got = both()
    monkeypatch.setattr(kernels, "takes", lambda *a: False)
    want = both()
    _all_close(got, want, 1e-4)
