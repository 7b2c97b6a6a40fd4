"""The embedding lookup's cotangent (``models/transformer.py``
``_table_rows``, ``_gathered_rows_bwd``): XLA's scatter-add or one matmul
against the ids' one-hot, chosen from the shapes by ``_onehot_wins``.
Both lowerings against ``jax.grad`` of a float32 ``embed[ids]``."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.models import TransformerConfig, init_params
from accl_tpu.models import transformer as tr

V, D = 96, 64
LOWERINGS = {"onehot": True, "scatter": False}


def _ids(case):
    rng = np.random.default_rng(37)
    return jnp.asarray({
        "duplicates": rng.integers(0, V, 200) % 7 * 5,
        "all_equal": np.full(200, 11),
        "ends": np.array([0, V - 1, V - 1, 0, 0, 5]),
        "ragged": rng.integers(0, V, 130),        # not a multiple of 128
        "batched": rng.integers(0, V, (3, 50)),   # (B, T) as the model has
    }[case], jnp.int32)


@pytest.fixture
def lowering(request, monkeypatch):
    monkeypatch.setattr(
        tr, "_onehot_wins", lambda *shape: LOWERINGS[request.param]
    )
    return request.param


def _table_and_weights(ids, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    table = jax.random.normal(ks[0], (V, D), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], ids.shape + (D,), jnp.float32).astype(dtype)
    return table, w


def _grad(lookup, table, ids, w):
    return jax.grad(
        lambda e: jnp.sum(
            lookup(e, ids).astype(jnp.float32) * w.astype(jnp.float32)
        )
    )(table)


@pytest.mark.parametrize("lowering", list(LOWERINGS), indirect=True)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "case", ["duplicates", "all_equal", "ends", "ragged", "batched"]
)
def test_cotangent_matches_float32_grad(case, dtype, lowering):
    ids = _ids(case)
    table, w = _table_and_weights(ids, dtype)
    got = _grad(tr._table_rows, table, ids, w)
    assert got.dtype == table.dtype and got.shape == table.shape
    # the float32 sum of the SAME cotangent rows (w is already rounded)
    want = _grad(
        lambda e, i: e[i], table.astype(jnp.float32), ids,
        w.astype(jnp.float32),
    )
    got, want = np.asarray(got, np.float32), np.asarray(want)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    elif lowering == "onehot":
        # summed in float32, rounded ONCE: half a bf16 spacing
        assert np.all(np.abs(got - want) <= 2.0 ** -8 * np.abs(want) + 1e-30)
    if lowering == "scatter":
        # what the transpose of the plain gather emits, bit for bit
        plain = _grad(lambda e, i: e[i], table, ids, w)
        np.testing.assert_array_equal(got, np.asarray(plain, np.float32))
    # a row no id names has no gradient
    unused = np.setdiff1d(np.arange(V), np.asarray(ids).ravel())
    assert not got[unused].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_is_the_gather_bit_for_bit(dtype):
    ids = _ids("batched")
    table, _ = _table_and_weights(ids, dtype)
    got = jax.jit(tr._table_rows)(table, ids)
    assert got.dtype == table.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(table[ids], np.float32)
    )


@pytest.mark.parametrize("lowering", list(LOWERINGS), indirect=True)
def test_lowering_is_in_the_jaxpr(lowering):
    """The forced lowering is the one traced: a matmul and no scatter-add,
    or the scatter-add and no matmul."""
    ids = _ids("ragged")
    table, w = _table_and_weights(ids, jnp.bfloat16)
    text = str(jax.make_jaxpr(
        lambda e: _grad(tr._table_rows, e, ids, w)
    )(table))
    assert ("dot_general" in text) == (lowering == "onehot")
    assert ("scatter-add" in text) == (lowering == "scatter")


#: (V, N, D) of the five train cells' tables, bf16
CELLS = {
    "train_t8192_b1": (49152, 8192, 4096, False),
    "train_t1024_b8": (49152, 8192, 4096, False),
    "train_olmoe_t4096_b2": (50304, 8192, 2048, False),
    "train_trinity_t8192_b2": (25024, 16384, 2048, False),
    "train_dsv2_t4096_b1": (12800, 4096, 5120, True),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_rule_at_the_cells_shapes(cell):
    Vc, N, Dc, onehot = CELLS[cell]
    assert bool(tr._onehot_wins(Vc, N, Dc, 2)) is onehot


def test_rule_reads_shapes_only():
    """Four ints in, a bool out: no config, no environment, no array."""
    import inspect

    assert list(inspect.signature(tr._onehot_wins).parameters) == [
        "V", "N", "D", "itemsize",
    ]
    names = set(tr._onehot_wins.__code__.co_names)
    assert not names & {"os", "environ", "getenv", "cfg", "jax", "jnp"}
    assert tr._onehot_wins(12800, 4096, 5120, 2) == tr._onehot_wins(
        np.int64(12800), np.int64(4096), np.int64(5120), np.int64(2)
    )


@pytest.mark.parametrize("lowering", list(LOWERINGS), indirect=True)
def test_vocab_parallel_shards_get_their_own_rows(lowering):
    """tp 2, the table's rows split in two: a rank's shard of the gradient
    holds the cotangent of the ids it owns and zeros elsewhere; ids that
    all fall in rank 0's rows leave rank 1's shard zero."""
    cfg = TransformerConfig(
        vocab=V, d_model=D, n_heads=4, n_layers=1, d_ff=64, max_seq=64,
        vocab_parallel=True,
    )
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    ids = _ids("ragged") % (V // 2)
    ids = ids.at[:3].set(jnp.array([0, V // 2 - 1, 0]))
    table, w = _table_and_weights(ids, jnp.float32)

    def local_loss(embed, ids, w):
        return jnp.sum(tr._embed_rows(embed, ids, cfg, "tp") * w)

    grad = jax.jit(shard_map(
        jax.grad(local_loss), mesh=mesh,
        in_specs=(P("tp", None), P(), P()), out_specs=P("tp", None),
    ))
    want = np.asarray(_grad(lambda e, i: e[i], table, ids, w))
    got = np.asarray(grad(table, ids, w))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not got[V // 2:].any() and got[: V // 2].any()
    # and ids on both sides of the split
    both = _ids("ragged")
    got = np.asarray(grad(table, both, w))
    want = np.asarray(_grad(lambda e, i: e[i], table, both, w))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lowering", list(LOWERINGS), indirect=True)
def test_tied_head_table_gradient_is_lookup_share_plus_head_share(lowering):
    """A tied head reads the table twice; only the lookup's share of its
    gradient passes through ``_table_rows``."""
    cfg = TransformerConfig(
        vocab=V, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=16,
        tie_head=True,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, V - 8)
    targets = jnp.roll(tokens, -1, axis=1)
    total = jax.grad(lambda p: tr.loss_fn(p, tokens, targets, cfg))(params)
    real = tr._table_rows

    def split(e_lookup, e_head):
        with mock.patch.object(
            tr, "_table_rows", lambda embed, ids: real(e_lookup, ids)
        ):
            return tr.loss_fn(
                {**params, "embed": e_head}, tokens, targets, cfg
            )

    lookup, head = jax.grad(split, (0, 1))(params["embed"], params["embed"])
    np.testing.assert_allclose(
        np.asarray(total["embed"]), np.asarray(lookup + head),
        rtol=1e-5, atol=1e-6,
    )
    unused = np.setdiff1d(np.arange(V), np.asarray(tokens).ravel())
    assert not np.asarray(lookup)[unused].any()
    assert np.asarray(head)[unused].any()       # the head reaches every row


@pytest.mark.parametrize("lowering", list(LOWERINGS), indirect=True)
def test_train_step_on_dp2_sums_the_table_gradient_over_dp(lowering):
    """Under ``make_sharded_train_step`` the table is replicated over dp
    and each rank places its own tokens' cotangents: the update is the
    single-device step's."""
    from accl_tpu.models import make_sharded_train_step

    cfg = TransformerConfig(
        vocab=V, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=16,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 12), 0, V)
    targets = jnp.roll(tokens, -1, axis=1)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(cfg, mesh, lr=0.1)
    new, _ = step(shard(params), tokens, targets)
    grads = jax.grad(
        lambda p: jnp.mean(_plain_loss(p, tokens, targets, cfg))
    )(params)
    np.testing.assert_allclose(
        np.asarray(new["embed"]),
        np.asarray(params["embed"] - 0.1 * grads["embed"]),
        rtol=2e-5, atol=2e-6,
    )


def _plain_loss(params, tokens, targets, cfg):
    """``loss_fn`` with the lookup as the plain gather JAX transposes."""
    with mock.patch.object(tr, "_table_rows", lambda embed, ids: embed[ids]):
        return tr.loss_fn(params, tokens, targets, cfg)
