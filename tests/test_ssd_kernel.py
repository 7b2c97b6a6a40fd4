"""The Mamba-2 core's Mosaic kernels (``accl_tpu/ops/pallas/ssd.py``:
``ssd_fwd`` / ``ssd_bwd``), interpreted on the CPU, against the
token-by-token recurrence of ``perfbench/reference/nemotron_h.py`` and
against the XLA form of ``accl_tpu/ops/ssd.py``, forward and the gradient
by every input, through the one entry the mixer calls
(``ops.ssd.ssd_mixer``, token-major).

A CPU's XLA form rounds nothing where the chip's default precision rounds
a product's operands to bfloat16, which the kernels do by explicit casts:
the tests of the MATHEMATICS run the kernels with float32 products
(``exact``) and hold them to float32's noise; the shipped rounding is
held to bfloat16's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.ops import ssd
from accl_tpu.ops.pallas import ssd as kernels
from perfbench.reference import nemotron_h as reference

ARGS = tuple(range(6))
NAMES = "x B C dt A D".split()


@pytest.fixture
def exact(monkeypatch):
    """The kernels' default-precision products in float32, as a CPU's XLA
    form computes them."""
    monkeypatch.setattr(kernels, "_ONE_PASS", jnp.float32)


def _inputs(T, B=1, H=4, G=2, width=64, N=128, seed=0, rate=(1.0, 16.0)):
    """Token-major operands as the mixer's chains leave them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H * width))
    b = jax.random.normal(ks[1], (B, T, G * N))
    c = jax.random.normal(ks[2], (B, T, G * N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)) - 2.0)
    a = -jax.random.uniform(ks[4], (H,), minval=rate[0], maxval=rate[1])
    d = jax.random.normal(ks[5], (H,))
    return x, b, c, dt, a, d


def _head_major(fn, G):
    """``fn`` on (B, H, T, .) arrays as a function of the token-major ones."""
    def call(x, b, c, dt, a, d):
        B, T, H = dt.shape
        heads = lambda t, n: t.reshape(B, T, n, -1).transpose(0, 2, 1, 3)
        y = fn(heads(x, H), heads(b, G), heads(c, G), dt.transpose(0, 2, 1), a, d)
        return y.transpose(0, 2, 1, 3).reshape(B, T, -1)
    return call


def _recurrence(x, b, c, dt, a, d):
    tokens_first = lambda v: v.transpose(1, 0, 2)
    return jnp.stack([
        reference.ssm_recurrence(
            tokens_first(x[i]), tokens_first(b[i]), tokens_first(c[i]),
            dt[i].T, a, d,
        ).transpose(1, 0, 2)
        for i in range(x.shape[0])
    ])


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


def _with_grads(fn, v, co):
    """``fn``'s output and its gradient by every input, as ONE compiled
    function (an eager walk compiles each of the XLA form's hundreds of
    operations by itself: most of what these cases cost, ROADMAP D14)."""
    return jax.jit(lambda v, co: (
        fn(*v), jax.grad(lambda *a: jnp.sum(fn(*a) * co), argnums=ARGS)(*v)
    ))(v, co)


def _cotangent(v):
    return jax.random.normal(jax.random.PRNGKey(9), v[0].shape)


def _mixer(G):
    def call(*v):
        assert kernels.takes(v[0].shape, v[1].shape, v[3].shape[-1], G)
        return ssd.ssd_mixer(*v, G)
    return call


#: groups -> heads: sixteen heads a group of one (the cell's count a group),
#: two a group of two and of eight (one slab a group)
HEADS = {1: 16, 2: 4, 8: 16}


@pytest.mark.parametrize("groups", [1, 2, 8])
@pytest.mark.parametrize("length", [128, 200])
def test_kernels_against_the_recurrence_and_the_xla_form(length, groups, exact):
    """One chunk and a padded tail, a group of eight slabs and groups of
    one; three chunks and five with a padded tail are the same case in
    ``tests/test_ssd_kernel_long.py`` (half of what was tier-1's longest
    file, for another xdist worker: ROADMAP D14)."""
    check_against_the_oracles(length, groups)


def check_against_the_oracles(length, groups):
    """The kernels' output and gradients against the recurrence and the
    XLA form (the state crosses a grid step in its scratch; the backward
    walks the chunks in reverse)."""
    v = _inputs(length, H=HEADS[groups], G=groups)
    co = _cotangent(v)
    got, grads = _with_grads(_mixer(groups), v, co)
    assert got.dtype == jnp.float32 and got.shape == v[0].shape
    for oracle in (_recurrence, ssd.ssd_chunked):
        want, want_grads = _with_grads(_head_major(oracle, groups), v, co)
        _close(got, want, 2e-5)
        for name, a, b in zip(NAMES, grads, want_grads):
            # A's is a sum over every token of a head
            _close(a, b, 2e-4 if name == "A" else 5e-5), name


@pytest.mark.parametrize("width", [32, 128])
def test_heads_of_other_widths(width, exact):
    """Four heads a slab and a head a slab: the rule takes every width
    that divides a lane row."""
    v = _inputs(256, H=128 // width * 2, G=2, width=width)
    co = _cotangent(v)
    got, grads = _with_grads(_mixer(2), v, co)
    want, want_grads = _with_grads(_head_major(ssd.ssd_chunked, 2), v, co)
    _close(got, want, 2e-5)
    for name, a, b in zip(NAMES, grads, want_grads):
        _close(a, b, 2e-4 if name == "A" else 5e-5), name


def test_a_long_decay_stays_finite(exact):
    """``dt A`` summing below -80 inside a chunk (``exp`` of it underflows
    float32's normal range, its inverse overflows): every exponent is a
    masked difference, so nothing is infinite, forward or backward."""
    v = _inputs(256, rate=(40.0, 60.0))
    x, b, c, dt, a, d = v
    dt = dt + 0.05
    v = (x, b, c, dt, a, d)
    assert float((dt[0, :128] * a).sum(0).max()) < -80.0
    co = _cotangent(v)
    got, grads = _with_grads(_mixer(2), v, co)
    want, want_grads = _with_grads(_head_major(ssd.ssd_chunked, 2), v, co)
    _close(got, want, 2e-5)
    for name, a_, b_ in zip(NAMES, grads, want_grads):
        _close(a_, b_, 2e-4), name


def test_kernels_at_the_chips_rounding():
    """As shipped: one bfloat16 pass a default-precision product, against
    the XLA form's float32 here (on the chip both round alike); the
    log-decay's cotangent, a sum of differences, keeps its two sides the
    same rounded numbers, so ``dt`` and ``A`` come out at the rounding's
    size too."""
    v = _inputs(384, H=16, G=2)
    co = _cotangent(v)
    got, grads = _with_grads(_mixer(2), v, co)
    want, want_grads = _with_grads(_head_major(ssd.ssd_chunked, 2), v, co)
    _close(got, want, 2e-2)
    for name, a, b in zip(NAMES, grads, want_grads):
        _close(a, b, 3e-2), name


def test_groups_split_at_tp2_inside_a_checked_shard_map():
    """The mixer's heads and groups split over two devices of a
    ``check_vma`` shard_map (the sharded train step's): outputs and
    gradients carry the operands' axes and equal the one-device kernels'."""
    v = _inputs(192, H=4, G=2)
    co = _cotangent(v)
    want = _with_grads(_mixer(2), v, co)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    cols, vec = P(None, None, "tp"), P("tp")
    specs = (cols, cols, cols, cols, vec, vec)
    got = jax.jit(shard_map(
        lambda co, *v: _with_grads(_mixer(1), v, co), mesh=mesh,
        in_specs=(cols,) + specs, out_specs=(cols, specs), check_vma=True,
    ))(co, *v)
    # the cumulative sum outside the kernels is XLA's, fused another way a
    # shard: float32's noise, nothing of bfloat16's
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, b, 2e-6)


def test_the_replayed_forward_gives_the_same_gradients(monkeypatch):
    """Under ``jax.checkpoint`` (the cell runs every block under ``remat``)
    the forward runs again before the backward, saves the chunk states
    then, and the gradients are the plain ones."""
    # the generic interpreter: the TPU interpreter's kernels are host
    # callbacks, which ``jax.checkpoint`` refuses
    monkeypatch.setattr(kernels, "default_interpret", lambda interpret=None: True)
    v = _inputs(200)
    co = _cotangent(v)
    want = _with_grads(_mixer(2), v, co)
    got = _with_grads(jax.checkpoint(_mixer(2)), v, co)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


# -- the shape rule ----------------------------------------------------------------

#: (B, T, heads, groups, a head's width, the state's width, chunk) -> kernels?
SHAPES = {
    "the_cell": ((1, 8192, 128, 8, 64, 128, 128), True),
    "the_cell_at_tp2": ((1, 8192, 64, 4, 64, 128, 128), True),
    "no_whole_chunks": ((2, 200, 4, 2, 64, 128, 128), True),
    "a_head_a_slab_one_group": ((1, 256, 3, 1, 128, 256, 128), True),
    "the_tests_tiny_widths": ((2, 80, 8, 2, 8, 16, 128), False),
    "a_group_96_wide": ((1, 256, 6, 2, 32, 128, 128), False),
    "a_head_no_part_of_a_lane_row": ((1, 256, 8, 2, 48, 128, 128), False),
    "a_state_half_a_lane_row": ((1, 256, 4, 2, 64, 64, 128), False),
    "another_chunk": ((1, 256, 4, 2, 64, 128, 64), False),
}


@pytest.mark.parametrize("case", SHAPES)
def test_the_shapes_pick_the_lowering(case, monkeypatch):
    """``ssd_mixer`` from the shapes alone (nothing runs): this module's
    chunk, a state of whole lanes, a head that divides a lane row and a
    group that fills whole lane rows take the kernels; every other shape
    the XLA form."""
    (B, T, H, G, width, N, chunk), kernel = SHAPES[case]
    x_shape, b_shape = (B, T, H * width), (B, T, G * N)
    assert kernels.takes(x_shape, b_shape, H, G, chunk) is kernel
    took = []
    monkeypatch.setattr(
        kernels, "ssd", lambda x, *rest, **kw: took.append("kernels") or x
    )
    monkeypatch.setattr(
        ssd, "ssd_chunked", lambda x, *rest, **kw: took.append("xla") or x
    )
    struct = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    out = jax.eval_shape(
        lambda *a: ssd.ssd_mixer(*a, G, chunk),
        struct(*x_shape), struct(*b_shape), struct(*b_shape), struct(B, T, H),
        struct(H), struct(H),
    )
    assert out.shape == x_shape
    assert took == ["kernels" if kernel else "xla"]


def test_one_chunk_for_both_lowerings():
    assert ssd.CHUNK == kernels.CHUNK == 128
    assert (kernels.FWD, kernels.BWD) == ("ssd_fwd", "ssd_bwd")
