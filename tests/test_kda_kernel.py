"""The KDA core's Mosaic kernels (``accl_tpu/ops/pallas/kda.py``:
``kda_fwd`` / ``kda_bwd``), interpreted on the CPU, against the
token-by-token recurrence of ``perfbench/reference/bailing_hybrid.py`` and
against the XLA form of ``accl_tpu/ops/kda.py``, forward and the gradient
by every input.

A CPU's XLA form rounds nothing where the chip's default precision rounds
a product's operands to bfloat16, which the kernels do by explicit casts:
the tests of the MATHEMATICS run the kernels with float32 products
(``exact``) and hold them to float32's noise; the shipped rounding is
held to bfloat16's."""

from functools import partial, reduce

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.ops import kda
from accl_tpu.ops.pallas import kda as kernels
from perfbench.reference import bailing_hybrid as reference

ARGS = (0, 1, 2, 3, 4)
NAMES = "q k v g beta".split()


@pytest.fixture
def exact(monkeypatch):
    """The kernels' default-precision products in float32, as a CPU's XLA
    form computes them."""
    monkeypatch.setattr(kernels, "_ONE_PASS", jnp.float32)


def _inputs(T, B=1, H=2, dk=128, dv=128, seed=0, at_bound=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = reference.l2_norm(jax.random.normal(ks[0], (B, H, T, dk))) * dk ** -0.5
    k = reference.l2_norm(jax.random.normal(ks[1], (B, H, T, dk)))
    v = jax.random.normal(ks[2], (B, H, T, dv))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (B, H, T, dk)))
    if at_bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, T)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    tokens_first = lambda x: x.transpose(1, 0, 2)
    return jnp.stack([
        reference.kda_recurrence(
            *(tokens_first(x[b]) for x in (q, k, v, g)), beta[b].T
        ).transpose(1, 0, 2)
        for b in range(q.shape[0])
    ])


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape and np.isfinite(got).all()
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


def _with_grads(fn, x, co):
    """``fn``'s output and its gradient by every input, as ONE compiled
    function (an eager walk compiles each of the XLA form's hundreds of
    operations by itself: most of what these cases cost, ROADMAP D14)."""
    return jax.jit(lambda x, co: (
        fn(*x), jax.grad(lambda *a: jnp.sum(fn(*a) * co), argnums=ARGS)(*x)
    ))(x, co)


def _cotangent(x):
    return jax.random.normal(jax.random.PRNGKey(9), x[2].shape)


#: (length, blocks a grid step): one block a step; a step whose second
#: block is part padding; three steps of two blocks, the last with a
#: block of padding; two steps of the default four
LENGTHS = {"128x1": (128, 1), "200x2": (200, 2), "600x2": (600, 2), "520x4": (520, 4)}


@pytest.mark.parametrize("at_bound", [False, True])
@pytest.mark.parametrize("case", LENGTHS)
def test_kernels_against_the_recurrence_and_the_xla_form(
    case, at_bound, exact, monkeypatch
):
    """Whole blocks and not, one and several grid steps (the state crosses
    a step in its scratch; the backward walks the steps in reverse);
    random gates, and every gate at the bound of -5, where a chunk's
    decay sums to -320."""
    length, blocks = LENGTHS[case]
    monkeypatch.setattr(kernels, "BLOCKS", blocks)
    x = _inputs(length, at_bound=at_bound)
    co = _cotangent(x)
    got, grads = _with_grads(kernels.kda, x, co)
    assert got.dtype == jnp.float32 and got.shape == x[2].shape
    for oracle in (_recurrence, kda._xla_form):
        want, want_grads = _with_grads(oracle, x, co)
        _close(got, want, 2e-5)
        for name, a, b in zip(NAMES, grads, want_grads):
            # at the bound a gate's gradient is a difference of terms of e^-5
            _close(a, b, 2e-4 if at_bound and name == "g" else 5e-5), name


@pytest.mark.parametrize("at_bound", [False, True])
def test_kernels_at_the_chips_rounding(at_bound):
    """As shipped: one bfloat16 pass a default-precision product, against
    the XLA form's float32 here (on the chip both round alike)."""
    x = _inputs(256, at_bound=at_bound)
    co = _cotangent(x)
    got, grads = _with_grads(kernels.kda, x, co)
    want, want_grads = _with_grads(kda._xla_form, x, co)
    _close(got, want, 2e-2)
    for name, a, b in zip(NAMES, grads, want_grads):
        if name == "g":
            # a difference of terms of the size of k dk: their rounding's
            a, b = a / jnp.abs(want_grads[1]).max(), b / jnp.abs(want_grads[1]).max()
            assert float(jnp.abs(a - b).max()) <= 3e-2 * float(jnp.abs(x[1]).max())
        else:
            _close(a, b, 3e-2), name


def test_heads_of_two_lane_widths(exact):
    """``dv`` of 256 beside ``dk`` of 128: the rule takes every whole
    number of lanes."""
    x = _inputs(128, H=1, dv=256)
    co = _cotangent(x)
    got, grads = _with_grads(kernels.kda, x, co)
    want, want_grads = _with_grads(kda._xla_form, x, co)
    _close(got, want, 2e-5)
    for a, b in zip(grads, want_grads):
        _close(a, b, 5e-5)


def test_heads_split_at_tp2_inside_a_checked_shard_map():
    """The KDA heads split over two devices of a ``check_vma`` shard_map
    (the sharded train step's): outputs and gradients carry the operands'
    axes and equal the one-device kernels'."""
    x = _inputs(192, H=2)
    co = _cotangent(x)
    want = _with_grads(kernels.kda, x, co)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    heads = P(None, "tp")
    got = jax.jit(shard_map(
        lambda co, *x: _with_grads(kernels.kda, x, co), mesh=mesh,
        in_specs=(heads,) * 6, out_specs=(heads, (heads,) * 5), check_vma=True,
    ))(co, *x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_replayed_forward_gives_the_same_gradients():
    """Under ``jax.checkpoint`` (the cell runs every layer under ``remat``)
    the forward runs again before the backward, saves the chunk states
    then, and the gradients are the plain ones."""
    x = _inputs(200)
    co = _cotangent(x)
    # the generic interpreter: the TPU interpreter's kernels are host
    # callbacks, which ``jax.checkpoint`` refuses
    fn = lambda *a: kernels.kda(*a, interpret=True)
    want = _with_grads(fn, x, co)
    got = _with_grads(jax.checkpoint(fn), x, co)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


# -- the inverse ---------------------------------------------------------------------


def _highest_rows(jaxpr):
    """The rows of the left factors of a traced program's ``highest``
    products, summed (a ``pallas_call``'s body and every other inner
    program included)."""
    rows = 0
    for eqn in jaxpr.eqns:
        precision = eqn.params.get("precision") if eqn.primitive.name == "dot_general" else None
        if precision is not None and set(np.ravel(precision)) == {lax.Precision.HIGHEST}:
            rows += eqn.invars[0].aval.shape[0]
        for value in eqn.params.values():
            for v in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(v, "jaxpr", v)              # a closed program's
                if hasattr(inner, "eqns"):
                    rows += _highest_rows(inner)
    return rows


@pytest.mark.parametrize("safe", [False, True])
def test_the_forwards_highest_products_stream_384_rows_a_block(safe):
    """The engagement: level 1 a subtraction, levels 2 and 4 float32 on the
    VPU, levels 8, 16 and 32 their lower rows alone through two products
    (3 x 2 x 64), under both gates (six whole levels were 1,536 rows, the
    bounded gate's squaring 1,280)."""
    f32 = jnp.float32
    rows, beta = jax.ShapeDtypeStruct((1, 256, 128), f32), jax.ShapeDtypeStruct((1, 2, 1, 128), f32)
    traced = jax.make_jaxpr(partial(
        kernels._forward, save=True, blocks=2, interpret=False, one_pass=jnp.bfloat16, safe=safe,
    ))(rows, rows, rows, rows, beta)
    assert _highest_rows(traced.jaxpr) == 2 * 384


def _whole_levels(a, masks, eye, hi=partial(jnp.matmul, precision="highest")):
    """The inverse by halving as it was: every level two whole products."""
    inv = jnp.where(eye, 1.0, 0.0)
    for mask in masks:
        inv = inv - hi(hi(inv, jnp.where(mask, a, 0.0)), inv)
    return inv


def _saved_inverses(x, safe, monkeypatch, inverse=None):
    """What the forward saves as the blocks' inverses, (blocks, ROWS, ROWS),
    float32 products; ``inverse`` in the kernel's place where given."""
    if inverse is not None:
        monkeypatch.setattr(kernels, "_inverse_by_halving", inverse)
    # not the jitted forward, which would answer from its cache
    monkeypatch.setattr(
        kernels, "_forward", getattr(kernels._forward, "__wrapped__", kernels._forward)
    )
    how = (kernels.default_interpret(None), jnp.float32, safe)
    return kernels._apply(*x, how, save=True)[1][1][0]


@pytest.mark.parametrize("case", ["128x1", "200x2", "520x4"])
@pytest.mark.parametrize("safe", [False, True])
def test_the_saved_inverse_is_the_whole_levels_and_the_xla_forms(case, safe, monkeypatch):
    """The blocks' ``A`` as the kernel built them (saved in the inverse's
    place), then: the saved inverse against all six levels WHOLE (what
    multiplying the zero rows too gave) and, under a bound, against the
    XLA form's inverse by squaring, chunk by chunk."""
    length, blocks = LENGTHS[case]
    monkeypatch.setattr(kernels, "BLOCKS", blocks)
    shipped = kernels._inverse_by_halving
    q, k, v, g, beta = _inputs(length, H=1)
    if safe:                     # gates down to -200, a write strength in (0, 2)
        g, beta = 40.0 * g, 2.0 * beta
    x = (q, k, v, g, beta)
    got = _saved_inverses(x, safe, monkeypatch)
    a = _saved_inverses(
        x, safe, monkeypatch,
        lambda a, masks, row, col: jnp.where(reduce(jnp.logical_or, masks), a, 0.0),
    )
    assert got.shape == a.shape == (-(-length // (blocks * 128)) * blocks, 128, 128)
    row, col = (lax.broadcasted_iota(jnp.int32, (128, 128), i) for i in (0, 1))
    masks, eye = kernels._pair_masks(row, col), row == col
    assert float(jnp.abs(a).max()) > 0.01 and not bool(jnp.any(jnp.triu(a)))
    _close(got, jax.vmap(lambda a: _whole_levels(a, masks, eye))(a), 1e-6)
    # level 1 has no product: the whole level to the bit
    first = jax.vmap(lambda a: shipped(a, masks[:1], row, col))(a)
    np.testing.assert_array_equal(
        first, jax.vmap(lambda a: _whole_levels(a, masks[:1], eye))(a)
    )
    if not safe:
        chunks = lambda m: jnp.stack([m[:, :64, :64], m[:, 64:, 64:]])
        with jax.default_matmul_precision("highest"):
            _close(chunks(got), kda._unit_lower_inverse(chunks(a)), 1e-6)


# -- the shape rule ----------------------------------------------------------------

SHAPES = {
    "the_cell": ((2, 32, 8192, 128), 128, {}, True),
    "the_cell_at_tp2": ((2, 16, 8192, 128), 128, {}, True),
    "no_whole_chunks": ((1, 2, 200, 128), 128, {}, True),
    "two_lanes_of_values": ((1, 2, 256, 128), 256, {}, True),
    "the_tests_tiny_heads": ((2, 4, 80, 16), 16, {}, False),
    "half_a_lane_more": ((1, 2, 256, 192), 128, {}, False),
    "values_no_whole_lanes": ((1, 2, 256, 128), 24, {}, False),
    "another_chunk": ((1, 2, 256, 128), 128, dict(chunk=32), False),
    "another_sub_block": ((1, 2, 256, 128), 128, dict(sub=32), False),
}


@pytest.mark.parametrize("case", SHAPES)
def test_the_shapes_pick_the_lowering(case, monkeypatch):
    """``kda_chunked`` from the shapes alone (nothing runs): heads whose
    ``dk`` and ``dv`` are whole lanes, at the default chunk and sub-block,
    take the kernels; every other shape the XLA form."""
    q_shape, dv, how, kernel = SHAPES[case]
    assert kernels.takes(q_shape, q_shape[:-1] + (dv,), **how) is kernel
    took = []

    def note(name, shape):
        def lowering(q, k, v, *rest, **kw):
            took.append(name)
            return jnp.zeros(shape, jnp.float32)
        return lowering

    o_shape = q_shape[:-1] + (dv,)
    monkeypatch.setattr(kernels, "kda", note("kernels", o_shape))
    monkeypatch.setattr(kda, "_xla_form", note("xla", o_shape))
    struct = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    out = jax.eval_shape(
        lambda *a: kda.kda_chunked(*a, **how),
        struct(q_shape), struct(q_shape), struct(o_shape), struct(q_shape),
        struct(q_shape[:-1]),
    )
    assert out.shape == o_shape
    assert took == ["kernels" if kernel else "xla"]


def test_one_chunk_and_sub_block_for_both_lowerings():
    assert (kda.CHUNK, kda.SUB) == (kernels.CHUNK, kernels.SUB) == (64, 16)
    assert kernels.ROWS == 2 * kernels.CHUNK
    assert (kernels.FWD, kernels.BWD) == ("kda_fwd", "kda_bwd")
