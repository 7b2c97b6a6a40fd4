"""The held experts' path (``models.moe._held_experts``) against the
scatter-adds it had until PR 35, written out here in plain ``jnp``: the
result, the counters and the gradients to the tokens, the bank and the
chosen probabilities, in float32 and bf16, under each of the path's two
lowerings; and that the lowering the rule picks is the one the compiled
program holds.

The path places a buffer row's result on its token.  Where
``moe._gathers_win`` says so it does that with k gathers a token through
the inverse of the sort (no scatter-add as wide as the model), else with
the scatter-add over the buffer's rows.  Both are the same arithmetic:
float32 products and sums, the result in the rows' type; only the order
of a token's at most k float32 additions differs.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accl_tpu.models import moe
from accl_tpu.models.moe import held_rows, init_moe_params, moe_ffn

N, D, F, ROUTER = 24, 32, 16, 12


# --- the reference: PR 31's form, a scatter-add each way -----------------


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_rows(x, idx, n):
    return x[idx]


def _gather_rows_fwd(x, idx, n):
    return x[idx], idx


def _gather_rows_bwd(n, idx, g):
    acc = jnp.zeros((n, g.shape[-1]), jnp.float32).at[idx].add(
        g.astype(jnp.float32)
    )
    return acc.astype(g.dtype), None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _scattered(flat, params, topk_e, topk_p, first, rows):
    """``_held_experts`` with ``.at[idx].add`` for the combine and for the
    dispatch gather's cotangent, differentiated by jax's own rules but for
    the float32 sum of the gather's cotangent."""
    n, _ = flat.shape
    k = topk_e.shape[-1]
    E = params["w1"].shape[0]
    expert = topk_e.reshape(-1)
    counts = jnp.zeros((params["gate"].shape[1],), jnp.int32).at[expert].add(1)
    local = expert - first
    key = jnp.where((local >= 0) & (local < E), local, E)
    order = jnp.argsort(key, stable=True)[:rows]
    ends = jnp.minimum(jnp.cumsum(counts[first:first + E]), rows)
    sizes = jnp.diff(ends, prepend=0)
    held, kept = jnp.sum(counts[first:first + E]), ends[-1]
    valid = jnp.arange(rows) < kept
    token = order // k
    x = jnp.where(valid[:, None], _gather_rows(flat, token, n), 0)
    out = moe._expert_bank(x, params, sizes)
    w = jnp.where(valid, topk_p.reshape(-1)[order], 0.0)
    out = jnp.where(valid[:, None], out, 0)
    acc = jnp.zeros((n, out.shape[-1]), jnp.float32).at[token].add(
        out.astype(jnp.float32) * w[:, None]
    )
    return acc.astype(out.dtype), {
        "expert_tokens": counts, "held_entries": held, "dropped": held - kept,
    }


# --- the cases -----------------------------------------------------------


def _routing(k, first, E, seed=0):
    """Seeded top-k over ``ROUTER`` experts with token 0 holding nothing
    here, token 1 one entry and token 2 all k (where the bank has k)."""
    rng = np.random.default_rng(seed)
    e = np.stack([rng.permutation(ROUTER)[:k] for _ in range(N)])
    inside = np.arange(first, first + E)
    outside = np.setdiff1d(np.arange(ROUTER), inside)
    e[0] = outside[:k]
    e[1] = np.concatenate([inside[:1], outside[:k - 1]])
    e[2] = inside[:k] if E >= k else e[2]
    p = rng.uniform(0.05, 1.0, (N, k)).astype(np.float32)
    return jnp.asarray(e, jnp.int32), jnp.asarray(p)


#: name -> (k, held experts, first expert, held_row_factor)
CASES = {
    "none_one_all_held": (3, 4, 0, 8.0),
    "first_expert_4": (3, 4, 4, 8.0),
    "last_share": (3, 4, 8, 8.0),
    "buffer_too_small": (3, 4, 4, 0.5),
    "k1": (1, 4, 4, 8.0),
    "k1_buffer_too_small": (1, 10, 1, 0.25),
    "every_entry_a_row": (2, 6, 3, 64.0),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
FORMS = {"gathers": True, "scatter_add": False}


def _ulp(a, dtype):
    """One unit in the last place of ``dtype`` at each ``|a|``."""
    a = np.abs(np.asarray(a, np.float64))
    a = np.maximum(a, float(jnp.finfo(dtype).tiny))
    return 2.0 ** (np.floor(np.log2(a)) - jnp.finfo(dtype).nmant)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if dtype == jnp.float32:
        scale = max(np.abs(want).max(), 1e-30)
        assert np.abs(got - want).max() <= 1e-6 * scale
    else:
        room = np.maximum(_ulp(got, dtype), _ulp(want, dtype))
        assert (np.abs(got - want) <= room).all()


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", CASES)
def test_held_path_equals_the_scatter_add_it_replaced(
    case, dtype, form, monkeypatch
):
    k, E, first, factor = CASES[case]
    dtype = DTYPES[dtype]
    monkeypatch.setattr(moe, "_gathers_win", lambda *shape: FORMS[form])
    params = init_moe_params(
        jax.random.PRNGKey(1), D, F, E, dtype, gated=True, router_experts=ROUTER
    )
    flat = jax.random.normal(jax.random.PRNGKey(2), (N, D), jnp.float32)
    flat = flat.astype(dtype)
    gy = jax.random.normal(jax.random.PRNGKey(3), (N, D), jnp.float32)
    topk_e, topk_p = _routing(k, first, E)
    rows = held_rows(N * k, E, ROUTER, factor)

    def loss(path, flat, bank, topk_p):
        y, counters = path(flat, {**params, **bank}, topk_e, topk_p)
        return jnp.sum(y.astype(jnp.float32) * gy), (y, counters)

    new = lambda f, p, e, w: moe._held_experts(f, p, e, w, None, first, rows)
    old = lambda f, p, e, w: _scattered(f, p, e, w, first, rows)
    bank = {n: params[n] for n in ("w1", "w2", "w3")}
    grad = lambda path: jax.grad(partial(loss, path), (0, 1, 2), has_aux=True)(
        flat, bank, topk_p
    )
    (d_flat, d_bank, d_p), (y, counters) = grad(new)
    (r_flat, r_bank, r_p), (r_y, r_counters) = grad(old)

    for name in ("expert_tokens", "held_entries", "dropped"):
        assert np.array_equal(counters[name], r_counters[name]), name
    held = int(counters["held_entries"])
    if "too_small" in case:
        assert int(counters["dropped"]) == held - rows > 0
    else:
        assert int(counters["dropped"]) == 0
    assert y.dtype == dtype and d_flat.dtype == dtype
    _close(y, r_y, dtype)
    _close(d_flat, r_flat, dtype)
    _close(d_p, r_p, jnp.float32)
    for name in bank:
        _close(d_bank[name], r_bank[name], dtype)
    # a token that holds nothing here gets nothing and gives nothing back
    assert not np.asarray(y[0]).any() and not np.asarray(d_flat[0]).any()
    assert not np.asarray(d_p[0]).any()
    if "too_small" not in case:
        assert np.asarray(y[1]).any() and np.asarray(d_p[1, 0]) != 0
        assert not np.asarray(d_p[1, 1:]).any()


def test_a_dropped_entry_adds_nothing_either_way():
    """Past the buffer an entry is dropped: the kept entries are the
    first ``rows`` of the held ones by expert, and only their weights have
    a gradient."""
    k, E, first = 3, 4, 4
    params = init_moe_params(
        jax.random.PRNGKey(1), D, F, E, jnp.float32, gated=True,
        router_experts=ROUTER,
    )
    flat = jax.random.normal(jax.random.PRNGKey(2), (N, D), jnp.float32)
    topk_e, topk_p = _routing(k, first, E)
    rows = 16
    path = lambda p: moe._held_experts(flat, params, topk_e, p, None, first, rows)
    _, counters = path(topk_p)
    assert int(counters["dropped"]) > 0
    # the kept entries are the first `rows` of the held ones, by expert
    local = np.asarray(topk_e).reshape(-1) - first
    inside = (local >= 0) & (local < E)
    key = np.where(inside, local, E)
    order = np.argsort(key, kind="stable")
    kept, lost = order[:rows], order[rows:][inside[order[rows:]]]
    assert len(lost) == int(counters["dropped"])
    d_p = jax.grad(lambda p: path(p)[0].sum())(topk_p)
    d_p = np.asarray(d_p).reshape(-1)
    assert not d_p[lost].any() and d_p[kept].all()


# --- the mechanism is engaged --------------------------------------------

#: the two held cells' layers: (tokens, D, d_ff, held, router's, call)
CELL_LAYERS = {
    "train_dsv2_t4096_b1": (4096, 5120, 1536, 20, 160, dict(
        k=6, renormalize=False, route_scale=16.0, n_group=8, topk_group=3,
    )),
    "train_trinity_t8192_b2": (16384, 2048, 1024, 16, 128, dict(
        k=8, router="sigmoid", route_scale=2.826,
    )),
}
#: what PERF.md section 5 (PR 35) says the rule picks there
CELL_GATHERS = {"train_dsv2_t4096_b1": True, "train_trinity_t8192_b2": False}


def _wide_scatter_adds(jaxpr, width):
    """The ``scatter-add`` equations of a jaxpr, and of every jaxpr inside
    it, whose updates are ``width`` columns wide."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add":
            updates = eqn.invars[2].aval.shape
            if updates and updates[-1] == width:
                found.append(updates)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _wide_scatter_adds(sub, width)
    return found


@pytest.mark.parametrize("cell", CELL_LAYERS)
def test_the_rule_by_shape_and_the_program_it_leaves(cell):
    tokens, d, d_ff, held, router, call = CELL_LAYERS[cell]
    k = call["k"]
    rows = held_rows(tokens * k, held, router, 2.0)
    assert moe._gathers_win(rows, tokens * k, d, 2) is CELL_GATHERS[cell]

    bank = jax.eval_shape(lambda: init_moe_params(
        jax.random.PRNGKey(0), d, d_ff, held, jnp.bfloat16, gated=True,
        router_experts=router,
    ))
    x = jax.ShapeDtypeStruct((1, tokens, d), jnp.bfloat16)

    def loss(x, bank):
        y = moe_ffn(x, bank, capacity_factor=None, **call)
        return jnp.sum(y.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, bank).jaxpr
    wide = _wide_scatter_adds(jaxpr, d)
    if CELL_GATHERS[cell]:
        assert wide == []
    else:  # the combine's, and the dispatch gather's cotangent
        assert wide == [(rows, d), (rows, d)]
