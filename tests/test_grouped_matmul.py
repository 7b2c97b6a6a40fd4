"""``ops.pallas.grouped_matmul`` (the tiled kernels behind the dropless
experts) against ``jax.lax.ragged_dot``: forward, the input's gradient and
the weights' gradient, and where the kernels sit in the train step's text.
Off the TPU the kernels run under the Pallas TPU interpreter.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh

from accl_tpu.ops.pallas.grouped_matmul import (
    DLHS,
    DRHS,
    FWD,
    grouped_matmul,
    tiles,
)

pytestmark = pytest.mark.pallas

#: name -> (m, k, n, group sizes)
CASES = {
    "balanced": (256, 128, 128, [64] * 4),
    "empty_groups": (256, 128, 128, [0, 100, 0, 0, 156, 0]),
    "one_group_holds_every_row": (256, 128, 128, [0, 0, 256, 0]),
    # tests/test_olmoe.py's rows (2 x 48 tokens x top-8 = 768 there, half
    # here), its widths below a tile, its 32 experts
    "m384_k64_n32": (384, 64, 32, [12] * 30 + [0, 24]),
    "m96_k64_n32": (96, 64, 32, [5, 0, 50, 41]),
    # the rehearsal of the OLMoE cell (perfbench/tests)
    "rehearsal_1024x128x64": (1024, 128, 64, [100, 0, 300, 24, 200, 200, 100, 100]),
    # a contraction past one tile that the tile does not divide (2048 + 128)
    "k2176_past_a_tile": (64, 2176, 128, [40, 0, 24]),
}
#: the cell's own two products (65,536 routing entries, 64 experts)
CELL = {"cell_up": (65536, 2048, 1024), "cell_down": (65536, 1024, 2048)}


def _operands(m, k, n, groups, dtype):
    keys = jax.random.split(jax.random.PRNGKey(m + k + n), 3)
    return (
        jax.random.normal(keys[0], (m, k), dtype),
        jax.random.normal(keys[1], (groups, k, n), dtype) * k ** -0.5,
        jax.random.normal(keys[2], (m, n), dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_forward_and_both_gradients_equal_ragged_dot(case, dtype):
    m, k, n, sizes = CASES[case]
    assert sum(sizes) == m
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs, cot = _operands(m, k, n, len(sizes), dtype)

    def run(matmul):
        out, vjp = jax.vjp(lambda a, w: matmul(a, w, sizes), lhs, rhs)
        return (out, *vjp(cot))

    # float32 operands at the precision tests/test_olmoe.py runs them at
    # (on the chip the default would round them to bfloat16 in both);
    # bfloat16 ones as they are: one exact MXU pass, and the compiler's
    # own ragged-dot kernel does not build for them under "highest"
    exact = dtype == jnp.float32
    with jax.default_matmul_precision("highest" if exact else "default"):
        got = jax.jit(lambda: run(grouped_matmul))()
        want = jax.jit(lambda: run(lax.ragged_dot))()
    # float32: the same products summed in another order; bfloat16: each
    # result rounded once from float32 sums that differ as little
    tol = 1e-5 if exact else 2 ** -7
    for name, g, w in zip(("out", "d_lhs", "d_rhs"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_tiles_divide_the_rows_and_fit_the_shapes(dtype):
    shapes = {c: v[:3] for c, v in CASES.items()} | CELL
    shapes["olmoe_test_step"] = (768, 64, 32)
    for case, (m, k, n) in shapes.items():
        for form in (FWD, DLHS, DRHS):
            tm, tk, tn = tiles(form, m, k, n, dtype)
            contract, cols = (n, k) if form == DLHS else (k, n)
            assert m % tm == 0 and 0 < tm <= 512, (case, form, tm)
            assert 0 < tk <= contract and 0 < tn <= cols, (case, form, tk, tn)
    # a row count with no aligned divisor: one tile of every row
    assert tiles(FWD, 1000, 64, 32, jnp.bfloat16)[0] == 1000


@pytest.mark.parametrize("case", list(CASES))
def test_group_metadata_equals_megablox(case):
    """The steps' groups and row tiles against jax's own megablox
    (``make_group_metadata`` with a step for every empty group), on the
    cases above and on seeded random sizes with empty groups."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    from accl_tpu.ops.pallas.grouped_matmul import group_metadata

    m, _, _, sizes = CASES[case]
    rng = np.random.default_rng(m + len(sizes))
    tm = tiles(FWD, m, 64, 64, jnp.bfloat16)[0]
    trials = [(sizes, m, tm)]
    # the same shapes again (megablox traces its metadata a shape), with
    # drawn sizes, a third of the groups empty, in smaller tiles
    for _ in range(4):
        w = rng.random(len(sizes)) * (rng.random(len(sizes)) > 0.3)
        w[0] += w.sum() == 0
        drawn = np.floor(w / w.sum() * m).astype(int)
        drawn[int(rng.integers(0, len(sizes)))] += m - drawn.sum()
        trials.append((drawn.tolist(), m, tm // 2 if m % (tm // 2) == 0 else tm))
    for sizes, m, tm in trials:
        sizes = jnp.asarray(sizes, jnp.int32)
        want, want_steps = make_group_metadata(
            group_sizes=sizes, m=m, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=sizes.shape[0], visit_empty_groups=True,
        )
        *got, steps = group_metadata(sizes, m=m, tm=tm)
        assert int(steps) == int(want_steps)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_train_step_text_has_every_grouped_matmul_under_the_experts_scope():
    """No ``ragged_dot`` is left in the OLMoE block's train step, it calls
    each of the three kernels three times a layer, and the benchmark's reader
    (``perfbench/scope_ops.py``: innermost ``accl.<x>::<y>`` of an ENTRY
    instruction of the COMPILED text) puts everything under a form's
    nested scope name in ``accl.moe::experts``.  On the chip the compiled
    text holds one ``tpu_custom_call`` a call there (CHANGES.md, PR 28);
    here the interpreter's expansion of each."""
    from accl_tpu.models import init_params, make_sharded_train_step
    from perfbench import scope_ops
    from test_olmoe import CFG, T

    cfg = dataclasses.replace(CFG, n_layers=1)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(cfg, mesh, lr=0.05)
    params = shard(init_params(jax.random.PRNGKey(0), cfg))
    tok = jnp.zeros((2, T), jnp.int32)
    traced = step.trace(params, tok, tok)
    # call sites of the jitted kernel wrappers (forward and the input's
    # gradient share one), and the kernels' own names inside them
    names = collections.Counter(re.findall(r"name=(_?t?gmm\w*)", str(traced.jaxpr)))
    assert names["_gmm"] == 6 * cfg.n_layers and names["_tgmm"] == 3 * cfg.n_layers
    assert set(names) == {"_gmm", "_tgmm", FWD, DLHS, DRHS}

    lowered = traced.lower()
    text = lowered.as_text()
    compiled = lowered.compile().as_text()
    assert "ragged" not in text and "ragged" not in compiled
    experts = set(scope_ops.scopes_of(compiled)["accl.moe::experts"])
    start = compiled.find("\nENTRY ")
    by_form = collections.Counter()
    for line in compiled[start:compiled.find("\n}", start)].splitlines():
        m = re.match(
            r'\s*(?:ROOT )?%(\S+) = .*op_name="[^"]*/(gmm_(?:fwd|dlhs|drhs))/',
            line,
        )
        if m:
            assert m[1] in experts, line[:200]
            by_form[m[2]] += 1
    assert set(by_form) == {FWD, DLHS, DRHS} and min(by_form.values()) > 0
