"""Expert parallelism (MoE all-to-all dispatch) and pipeline parallelism
(microbatch streaming over ppermute) — the ep and pp sharding axes of the
flagship family.  Both validated against single-device references on the
virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.models import (
    init_moe_params,
    moe_ffn,
    pipeline_apply,
    pipeline_loss,
)


def _mesh(n, axis):
    devs = jax.devices()[:n]
    return Mesh(devs, (axis,))


# ---------------------------------------------------------------------------
# MoE / expert parallelism
# ---------------------------------------------------------------------------


def test_moe_expert_parallel_matches_dense():
    """ep-sharded MoE == single-device MoE when capacity admits every
    token (the all-to-all dispatch must be a pure relayout)."""
    ep, B, T, D, F, E = 4, 2, 8, 16, 32, 8
    key = jax.random.PRNGKey(0)
    params = init_moe_params(key, D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (ep, B, T, D), jnp.float32)

    # reference: all tokens, all experts on one device, no-drop capacity
    ref = jnp.stack(
        [moe_ffn(x[r], params, None, capacity_factor=float(E)) for r in range(ep)]
    )

    mesh = _mesh(ep, "ep")
    local_params = {
        "gate": params["gate"],  # replicated
        "w1": params["w1"],  # sharded over experts
        "w2": params["w2"],
    }
    fn = jax.jit(
        shard_map(
            lambda xl, g, w1, w2: moe_ffn(
                xl[0], {"gate": g, "w1": w1, "w2": w2}, "ep",
                capacity_factor=float(E),
            )[None],
            mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=P("ep"),
            check_vma=False,
        )
    )
    out = fn(x, local_params["gate"], local_params["w1"], local_params["w2"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_moe_capacity_drops_fall_through():
    """Over-capacity tokens contribute exactly zero (residual path)."""
    B, T, D, F, E = 1, 16, 8, 16, 2
    params = init_moe_params(jax.random.PRNGKey(3), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, D), jnp.float32)
    cap = max(1, int(0.25 * B * T / E))
    y = moe_ffn(x, params, None, capacity_factor=0.25)
    # expected survivors: the first `cap` tokens routed to each expert
    logits = np.asarray(x.reshape(-1, D) @ params["gate"])
    routed = logits.argmax(-1)
    expect = sum(min((routed == e).sum(), cap) for e in range(E))
    nonzero = np.count_nonzero(np.abs(np.asarray(y)).sum(-1) > 1e-9)
    assert nonzero == expect and expect < B * T  # drops actually happened


def test_moe_is_differentiable():
    B, T, D, F, E = 2, 4, 8, 16, 4
    params = init_moe_params(jax.random.PRNGKey(5), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, T, D), jnp.float32)

    def loss(p):
        return jnp.sum(moe_ffn(x, p, None) ** 2)

    g = jax.grad(loss)(params)
    assert all(
        bool(jnp.all(jnp.isfinite(v))) for v in jax.tree_util.tree_leaves(g)
    )


# ---------------------------------------------------------------------------
# pipeline parallelism
# ---------------------------------------------------------------------------


def _stage(w, x):
    return jnp.tanh(x @ w)


def test_pipeline_matches_sequential():
    S, M, B, D = 4, 6, 2, 8
    ws = jax.random.normal(jax.random.PRNGKey(7), (S, D, D), jnp.float32) * 0.5
    mbs = jax.random.normal(jax.random.PRNGKey(8), (M, B, D), jnp.float32)

    # sequential reference: every microbatch through all stages in order
    ref = mbs
    for s in range(S):
        ref = jax.vmap(lambda x: _stage(ws[s], x))(ref)

    mesh = _mesh(S, "pp")
    fn = jax.jit(
        shard_map(
            lambda w, mb: pipeline_apply(w[0], mb, "pp", _stage)[None],
            mesh=mesh,
            in_specs=(P("pp"), P()),
            out_specs=P("pp"),
            check_vma=False,
        )
    )
    out = fn(ws, mbs)  # (S, M, B, D): row s = stage s's outputs
    np.testing.assert_allclose(
        np.asarray(out[-1]), np.asarray(ref), rtol=1e-5, atol=1e-6
    )
    # non-final stages return zeros (the DummyBuffer convention)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0)


def test_pipeline_loss_and_grads():
    """pipeline_loss equals the sequential loss and differentiates into
    per-stage gradients matching the sequential program's."""
    S, M, B, D = 2, 3, 2, 4
    ws = jax.random.normal(jax.random.PRNGKey(9), (S, D, D), jnp.float32) * 0.5
    mbs = jax.random.normal(jax.random.PRNGKey(10), (M, B, D), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(11), (M, B, D), jnp.float32)

    def seq_loss(ws):
        y = mbs
        for s in range(S):
            y = jax.vmap(lambda x: _stage(ws[s], x))(y)
        return jnp.mean(
            jax.vmap(lambda a, b: jnp.mean((a - b) ** 2))(y, tgt)
        )

    mesh = _mesh(S, "pp")

    def pp_loss(ws):
        return shard_map(
            lambda w, mb, t: pipeline_loss(
                w[0], mb, t, "pp", _stage,
                lambda a, b: jnp.mean((a - b) ** 2),
            ),
            mesh=mesh,
            in_specs=(P("pp"), P(), P()),
            out_specs=P(),
            check_vma=False,
        )(ws, mbs, tgt)

    l_seq = float(seq_loss(ws))
    l_pp = float(jax.jit(pp_loss)(ws))
    assert abs(l_seq - l_pp) < 1e-6

    g_seq = jax.grad(seq_loss)(ws)
    g_pp = jax.jit(jax.grad(pp_loss))(ws)
    np.testing.assert_allclose(
        np.asarray(g_pp), np.asarray(g_seq), rtol=1e-4, atol=1e-6
    )


def test_moe_top2_matches_dense_reference():
    """Top-2 routing == explicit dense computation: each token gets the
    renormalized-gate-weighted sum of its two best experts' FFN outputs
    (no-drop capacity)."""
    B, T, D, F, E = 2, 8, 16, 32, 8
    params = init_moe_params(jax.random.PRNGKey(2), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, T, D), jnp.float32)

    out = moe_ffn(x, params, None, capacity_factor=float(E), k=2)

    flat = x.reshape(-1, D)
    probs = jax.nn.softmax(flat @ params["gate"], axis=-1)
    topk_p, topk_e = jax.lax.top_k(probs, 2)
    topk_p = topk_p / topk_p.sum(-1, keepdims=True)
    expect = np.zeros_like(np.asarray(flat))
    for i in range(flat.shape[0]):
        for j in range(2):
            e = int(topk_e[i, j])
            h = np.asarray(jax.nn.gelu(flat[i] @ params["w1"][e]))
            expect[i] += float(topk_p[i, j]) * (h @ np.asarray(params["w2"][e]))
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, D), expect, rtol=2e-4, atol=2e-5
    )


def test_moe_top2_expert_parallel_matches_local():
    """Top-2 over the ep axis == top-2 with all experts local."""
    ep, B, T, D, F, E = 4, 1, 8, 16, 32, 8
    params = init_moe_params(jax.random.PRNGKey(4), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(5), (ep, B, T, D), jnp.float32)

    ref = jnp.stack([
        moe_ffn(x[r], params, None, capacity_factor=float(E), k=2)
        for r in range(ep)
    ])
    mesh = _mesh(ep, "ep")
    fn = jax.jit(
        shard_map(
            lambda xl, g, w1, w2: moe_ffn(
                xl[0], {"gate": g, "w1": w1, "w2": w2}, "ep",
                capacity_factor=float(E), k=2,
            )[None],
            mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep")),
            out_specs=P("ep"),
            check_vma=False,
        )
    )
    out = fn(x, params["gate"], params["w1"], params["w2"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("S,M", [(4, 6), (2, 3), (4, 2)])
def test_pipeline_1f1b_matches_gpipe(S, M):
    """The hand-scheduled 1F1B backward must produce bit-comparable loss
    and gradients to autodiff-through-GPipe (and hence to the sequential
    program).  (4, 2) exercises M < S (all-warmup, no steady state)."""
    from accl_tpu.models import pipeline_loss_and_grads

    B, D = 2, 4
    ws = jax.random.normal(jax.random.PRNGKey(9), (S, D, D), jnp.float32) * 0.5
    mbs = jax.random.normal(jax.random.PRNGKey(10), (M, B, D), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(11), (M, B, D), jnp.float32)
    mesh = _mesh(S, "pp")

    def run(schedule):
        return jax.jit(
            shard_map(
                lambda w, mb, t: pipeline_loss_and_grads(
                    w[0], mb, t, "pp", _stage,
                    lambda a, b: jnp.mean((a - b) ** 2),
                    schedule=schedule,
                ),
                mesh=mesh,
                in_specs=(P("pp"), P(), P()),
                out_specs=(P(), P("pp")),
                check_vma=False,
            )
        )(ws, mbs, tgt)

    l_g, g_g = run("gpipe")
    l_1, g_1 = run("1f1b")
    np.testing.assert_allclose(float(l_1), float(l_g), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_1), np.asarray(g_g), rtol=1e-4, atol=1e-6
    )

    # anchor both schedules to the sequential program's autodiff (rules
    # out a shared scaling error, e.g. the in-shard_map psum transpose)
    def seq_loss(ws):
        y = mbs
        for s in range(S):
            y = jax.vmap(lambda x: _stage(ws[s], x))(y)
        return jnp.mean(jax.vmap(lambda a, b: jnp.mean((a - b) ** 2))(y, tgt))

    l_s, g_s = jax.value_and_grad(seq_loss)(ws)
    np.testing.assert_allclose(float(l_g), float(l_s), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_g).reshape(S, D, D), np.asarray(g_s),
        rtol=1e-4, atol=1e-6,
    )


@pytest.mark.parametrize("S,V,M", [(2, 2, 4), (4, 2, 4), (2, 3, 4)])
def test_pipeline_interleaved_matches_sequential(S, V, M):
    """The interleaved virtual-stage schedule (V round-robin chunks per
    device, L = V*S global stages) computes the same loss and per-chunk
    gradients as the sequential L-stage program."""
    from accl_tpu.models import pipeline_loss_and_grads

    B, D = 2, 4
    L = V * S
    ws = jax.random.normal(jax.random.PRNGKey(12), (L, D, D), jnp.float32) * 0.5
    mbs = jax.random.normal(jax.random.PRNGKey(13), (M, B, D), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(14), (M, B, D), jnp.float32)
    # device d's chunk v is global stage v*S + d: lay the stack out as
    # (S, V, D, D) so shard_map's leading-dim split hands each device
    # its V chunks
    wsp = jnp.stack([ws[d::S] for d in range(S)])  # (S, V, D, D)

    mesh = _mesh(S, "pp")
    l_i, g_i = jax.jit(
        shard_map(
            lambda w, mb, t: pipeline_loss_and_grads(
                w[0], mb, t, "pp", _stage,
                lambda a, b: jnp.mean((a - b) ** 2),
                schedule="interleaved", v_stages=V,
            ),
            mesh=mesh,
            in_specs=(P("pp"), P(), P()),
            out_specs=(P(), P("pp")),
            check_vma=False,
        )
    )(wsp, mbs, tgt)

    def seq_loss(ws):
        y = mbs
        for s in range(L):
            y = jax.vmap(lambda x: _stage(ws[s], x))(y)
        return jnp.mean(jax.vmap(lambda a, b: jnp.mean((a - b) ** 2))(y, tgt))

    l_s, g_s = jax.value_and_grad(seq_loss)(ws)
    np.testing.assert_allclose(float(l_i), float(l_s), rtol=1e-6)
    # shard_map concatenated the per-device (V, D, D) grads device-major
    # into (S*V, D, D): flat index d*V + v is global stage v*S + d
    g_i = np.asarray(g_i).reshape(S, V, D, D)
    for d in range(S):
        for v in range(V):
            np.testing.assert_allclose(
                g_i[d, v], np.asarray(g_s[v * S + d]),
                rtol=1e-4, atol=1e-6,
            )


def test_pipeline_interleaved_v1_matches_gpipe():
    """At V=1 the interleaved schedule degenerates to the plain pipeline:
    identical loss/grads to GPipe on the same mesh."""
    from accl_tpu.models import pipeline_loss_and_grads

    S, M, B, D = 4, 4, 2, 4
    ws = jax.random.normal(jax.random.PRNGKey(15), (S, D, D), jnp.float32) * 0.5
    mbs = jax.random.normal(jax.random.PRNGKey(16), (M, B, D), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(17), (M, B, D), jnp.float32)
    mesh = _mesh(S, "pp")

    def run(schedule, w, v):
        return jax.jit(
            shard_map(
                lambda w, mb, t: pipeline_loss_and_grads(
                    w[0], mb, t, "pp", _stage,
                    lambda a, b: jnp.mean((a - b) ** 2),
                    schedule=schedule, v_stages=v,
                ),
                mesh=mesh,
                in_specs=(P("pp"), P(), P()),
                out_specs=(P(), P("pp")),
                check_vma=False,
            )
        )(w, mbs, tgt)

    l_g, g_g = run("gpipe", ws, 1)
    l_i, g_i = run("interleaved", ws[:, None], 1)  # (S, 1, D, D) chunks
    np.testing.assert_allclose(float(l_i), float(l_g), rtol=1e-6)
    # gpipe grads concat per-device (D, D) -> (S*D, D); interleaved
    # concat per-device (1, D, D) -> (S, D, D): same data, reshaped
    np.testing.assert_allclose(
        np.asarray(g_i).reshape(S, D, D),
        np.asarray(g_g).reshape(S, D, D),
        rtol=1e-4, atol=1e-6,
    )


def test_pipeline_interleaved_constraints_and_bubble():
    """M % S is enforced, and the bubble-fraction note is quantitative:
    interleaving divides the warmup cost by V."""
    from accl_tpu.models import (
        pipeline_apply_interleaved, pipeline_bubble_fraction,
    )

    mesh = _mesh(4, "pp")
    ws = jnp.zeros((4, 2, 4, 4))
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(
            shard_map(
                lambda w, mb: pipeline_apply_interleaved(
                    w[0], mb, "pp", _stage, 2
                )[None],
                mesh=mesh,
                in_specs=(P("pp"), P()),
                out_specs=P("pp"),
                check_vma=False,
            )
        )(ws, jnp.zeros((6, 2, 4)))  # M=6 not divisible by S=4

    # 1F1B shares GPipe's bubble; interleaving beats both for V >= 2
    S, M = 8, 16
    b_gpipe = pipeline_bubble_fraction("gpipe", S, M)
    b_1f1b = pipeline_bubble_fraction("1f1b", S, M)
    b_int = pipeline_bubble_fraction("interleaved", S, M, v_stages=2)
    assert b_gpipe == b_1f1b == (S - 1) / (M + S - 1)
    assert b_int < b_1f1b
    assert b_int == (S - 1) / (M * 2 + S - 1)
    with pytest.raises(ValueError, match="unknown"):
        pipeline_bubble_fraction("dave", S, M)


def test_pipeline_unknown_schedule_raises():
    from accl_tpu.models import pipeline_loss_and_grads

    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        pipeline_loss_and_grads(
            None, jnp.zeros((2, 2)), jnp.zeros((2, 2)), "pp",
            lambda p, x: x, lambda a, b: 0.0, schedule="dave",
        )


@pytest.mark.parametrize(
    "shape3d,n_layers,microbatches,batch,seqlen",
    [
        ((2, 2, 2), 2, 2, 8, 16),  # balanced composition
        ((4, 1, 2), 4, 4, 4, 8),   # deep pipeline: one layer per stage
    ],
    ids=["pp2xdp2xtp2", "pp4xdp1xtp2"],
)
def test_composed_pp_dp_tp_matches_plain_train_step(
    shape3d, n_layers, microbatches, batch, seqlen
):
    """The 3-axis composition (pipeline stages of tp-sharded blocks,
    dp-sharded microbatched batch) computes the SAME loss and SAME
    updated parameters as the plain dp x tp train step on the identical
    global batch — parallelism layout, not math.  The deep-pipeline
    shape (one layer per stage) is where scheduling bugs hide."""
    from jax.sharding import Mesh
    from accl_tpu.models import (
        TransformerConfig, init_params, make_sharded_train_step,
    )
    from accl_tpu.models.composed import make_pp_train_step, unstack_params

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=n_layers, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seqlen), 0, cfg.vocab
    )
    tgts = jnp.roll(toks, -1, axis=1)

    # plain dp x tp over the same 8 devices
    mesh2d = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    pstep, pshard = make_sharded_train_step(cfg, mesh2d, lr=0.05)
    p_params, p_loss = pstep(pshard(params0), toks, tgts)

    # composed pp x dp x tp
    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(*shape3d), ("pp", "dp", "tp")
    )
    cstep, cshard = make_pp_train_step(
        cfg, mesh3d, num_microbatches=microbatches, lr=0.05
    )
    c_params, c_loss = cstep(cshard(params0), toks, tgts)

    assert float(c_loss) == pytest.approx(float(p_loss), rel=1e-5)
    c_tree = unstack_params(jax.tree.map(np.asarray, c_params))
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, p_params)),
        jax.tree.leaves(c_tree),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_composed_interleaved_matches_plain_train_step():
    """The composed pp x dp x tp step with v_stages=2 (each pp rank
    holding two round-robin layer chunks) computes the same loss and
    updated params as the plain dp x tp step — the interleaved schedule
    inside the FLAGSHIP, not just the toy stage_fn."""
    from jax.sharding import Mesh
    from accl_tpu.models import (
        TransformerConfig, init_params, interleave_layer_order,
        make_sharded_train_step,
    )
    from accl_tpu.models.composed import make_pp_train_step, unstack_params

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)

    mesh2d = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    pstep, pshard = make_sharded_train_step(cfg, mesh2d, lr=0.05)
    p_params, p_loss = pstep(pshard(params0), toks, tgts)

    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    cstep, cshard = make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, lr=0.05, v_stages=2,
    )
    c_params, c_loss = cstep(cshard(params0), toks, tgts)

    assert float(c_loss) == pytest.approx(float(p_loss), rel=1e-5)
    # the committed stack is in device-major chunk order: un-permute
    # before comparing layer-by-layer
    perm = np.asarray(interleave_layer_order(cfg.n_layers, 2, 2))
    inv = np.argsort(perm)
    c_np = jax.tree.map(np.asarray, c_params)
    c_np = {
        **c_np,
        "layers": {k: a[inv] for k, a in c_np["layers"].items()},
    }
    c_tree = unstack_params(c_np)
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, p_params)),
        jax.tree.leaves(c_tree),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_composed_1f1b_matches_gpipe_and_plain():
    """schedule='1f1b' on the composed flagship step — the hand-
    scheduled pipeline backward plus the maker's explicit embedding-vjp
    and head-grad psums — computes the same loss and updated params as
    the autodiff gpipe composed step AND the plain dp x tp step."""
    from jax.sharding import Mesh
    from accl_tpu.models import (
        TransformerConfig, init_params, make_sharded_train_step,
    )
    from accl_tpu.models.composed import make_pp_train_step, unstack_params

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)

    mesh2d = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    pstep, pshard = make_sharded_train_step(cfg, mesh2d, lr=0.05)
    p_params, p_loss = pstep(pshard(params0), toks, tgts)

    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    g_step, g_shard = make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, lr=0.05
    )
    g_params, g_loss = g_step(g_shard(params0), toks, tgts)
    f_step, f_shard = make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, lr=0.05, schedule="1f1b"
    )
    f_params, f_loss = f_step(f_shard(params0), toks, tgts)

    assert float(f_loss) == pytest.approx(float(g_loss), rel=1e-5)
    assert float(f_loss) == pytest.approx(float(p_loss), rel=1e-5)
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, g_params)),
        jax.tree.leaves(jax.tree.map(np.asarray, f_params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    f_tree = unstack_params(jax.tree.map(np.asarray, f_params))
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, p_params)),
        jax.tree.leaves(f_tree),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    with pytest.raises(ValueError, match="unknown composed"):
        make_pp_train_step(cfg, mesh3d, num_microbatches=2, schedule="dave")
    with pytest.raises(ValueError, match="does not compose"):
        make_pp_train_step(
            cfg, mesh3d, num_microbatches=2, schedule="1f1b", v_stages=2
        )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_composed_zero_adam_matches_flagship_zero(schedule):
    """make_pp_train_step(adam=...) — ZeRO-1 Adam under the composed
    pipeline — produces the same updated params as make_zero_train_step
    on the plain dp x tp mesh (the dp moment slices partition the
    elementwise update differently but compute identical math)."""
    from jax.sharding import Mesh
    from accl_tpu.models import TransformerConfig, init_params
    from accl_tpu.models.composed import make_pp_train_step, unstack_params
    from accl_tpu.parallel.zero import AdamConfig, make_zero_train_step

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)
    # eps large enough that first-step Adam (~sign(g) * lr at tiny eps)
    # doesn't amplify reduction-order noise into false failures
    adam = AdamConfig(lr=0.01, eps=1e-3, clip_grad_norm=1.0)

    mesh2d = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    zstep, zshard, zinit = make_zero_train_step(cfg, mesh2d, adam)
    zp, _, zl = zstep(zshard(params0), zinit(params0), toks, tgts)

    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    cstep, cshard, cinit = make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, adam=adam, schedule=schedule,
    )
    cp_, _, cl = cstep(cshard(params0), cinit(params0), toks, tgts)

    assert float(cl) == pytest.approx(float(zl), rel=1e-5)
    c_tree = unstack_params(jax.tree.map(np.asarray, cp_))
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, zp)),
        jax.tree.leaves(c_tree),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_composed_validates_divisibility():
    from jax.sharding import Mesh
    from accl_tpu.models import TransformerConfig
    from accl_tpu.models.composed import make_pp_train_step

    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    with pytest.raises(ValueError, match="must divide"):
        make_pp_train_step(
            TransformerConfig(n_layers=3), mesh3d, num_microbatches=2
        )



def test_moe_aux_losses():
    """Router health terms: the Switch load-balance aux is ~1 at perfect
    balance and approaches E when the router collapses; the z-loss
    penalizes large logits; both carry router gradients."""
    import jax.numpy as jnp

    D, F, E = 16, 32, 4
    params = init_moe_params(jax.random.PRNGKey(5), D, F, E)
    # positive activations so a positive gate column dominates every row
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 16, D)))

    y, aux = moe_ffn(x, params, return_aux=True)
    assert y.shape == x.shape
    # random small gates route near-uniformly: aux near its 1.0 optimum
    assert 0.9 < float(aux["load_balance"]) < 1.5

    collapsed = dict(
        params, gate=jnp.zeros((D, E)).at[:, 0].set(50.0)
    )
    _, aux_c = moe_ffn(x, collapsed, return_aux=True)
    assert float(aux_c["load_balance"]) > 0.9 * E  # ~E when collapsed
    assert float(aux_c["router_z"]) > float(aux["router_z"])

    g = jax.grad(
        lambda p: moe_ffn(x, p, return_aux=True)[1]["load_balance"]
    )(params)
    assert float(jnp.abs(g["gate"]).max()) > 0


def test_moe_aux_under_expert_parallelism():
    """return_aux composes with ep sharding: per-rank terms average to
    the dense layer's value when every rank sees the same tokens."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    D, F, E, ep = 8, 16, 4, 4
    devs = jax.devices()[:ep]
    if len(devs) < ep:
        pytest.skip(f"needs {ep} devices")
    params = init_moe_params(jax.random.PRNGKey(7), D, F, E)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, D))

    _, aux_dense = moe_ffn(x, params, None, capacity_factor=float(E),
                           return_aux=True)

    mesh = Mesh(np.array(devs), ("ep",))

    def run(xl, g, w1, w2):
        y, aux = moe_ffn(
            xl, {"gate": g, "w1": w1, "w2": w2}, "ep",
            capacity_factor=float(E), return_aux=True,
        )
        return y, aux["load_balance"]

    fn = jax.jit(
        shard_map(
            run, mesh=mesh,
            in_specs=(P(), P(), P("ep"), P("ep")),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    _, lb = fn(x, params["gate"], params["w1"], params["w2"])
    np.testing.assert_allclose(
        float(lb), float(aux_dense["load_balance"]), rtol=1e-5
    )


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_composed_debug_invariants_zero_2x2x2(schedule):
    """debug_invariants re-arms, at runtime, what check_vma=False turned
    off statically: the returned invariant scalar (max neighbor
    difference of loss and replicated-param grads under a one-step
    rotation per mesh axis) sits at the rounding floor when every
    hand-placed 1F1B transpose is right (VERDICT r4 item 5)."""
    from jax.sharding import Mesh
    from accl_tpu.models import TransformerConfig, init_params
    from accl_tpu.models.composed import make_pp_train_step

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)
    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    step, shard = make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, lr=0.05, schedule=schedule,
        debug_invariants=True,
    )
    params, loss, inv = step(shard(params0), toks, tgts)
    assert np.isfinite(float(loss))
    assert float(inv) <= 1e-6  # rounding floor; violations are ~1e-2


def test_composed_debug_invariants_catch_missing_transpose(monkeypatch):
    """The detector test: break the hand-placed fan-out transpose (drop
    its backward psum) and the invariant scalar must go NONZERO — this
    is the bug class the disabled vma checker would have caught
    statically, now caught at runtime instead."""
    from jax.sharding import Mesh
    from accl_tpu.models import TransformerConfig, init_params
    from accl_tpu.models import composed

    # plain identity: backward loses the tp psum the dual wrapper exists
    # to place, so stage-0 input grads (and thus the embedding grad)
    # become tp-rank-varying
    monkeypatch.setattr(composed, "_fanout_psum_bwd", lambda x, ax: x)

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)
    mesh3d = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("pp", "dp", "tp")
    )
    step, shard = composed.make_pp_train_step(
        cfg, mesh3d, num_microbatches=2, lr=0.05, schedule="1f1b",
        debug_invariants=True,
    )
    _, _, inv = step(shard(params0), toks, tgts)
    assert float(inv) > 1e-4  # gradient-magnitude signal, not noise


def test_composed_debug_invariants_4x2x2_subprocess():
    """The invariant holds as the mesh GROWS past the 8-device fixture:
    pp=4 x dp=2 x tp=2 on 16 virtual devices, both schedules, equal
    losses and a zero invariant scalar (VERDICT r4 item 5's 4x2x2 leg)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
        from accl_tpu.models import TransformerConfig, init_params
        from accl_tpu.models.composed import make_pp_train_step

        devs = jax.devices()
        assert len(devs) == 16, len(devs)
        cfg = TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
            max_seq=32, attention="naive",
        )
        p0 = init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab
        )
        tgts = jnp.roll(toks, -1, axis=1)
        mesh = Mesh(np.array(devs).reshape(4, 2, 2), ("pp", "dp", "tp"))
        losses = {}
        for sched in ("gpipe", "1f1b"):
            step, shard = make_pp_train_step(
                cfg, mesh, num_microbatches=4, lr=0.05, schedule=sched,
                debug_invariants=True,
            )
            _, loss, inv = step(shard(p0), toks, tgts)
            assert float(inv) <= 1e-6, (sched, float(inv))
            losses[sched] = float(loss)
        assert abs(losses["gpipe"] - losses["1f1b"]) <= (
            1e-5 * abs(losses["gpipe"])
        ), losses
        print("OK", losses)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=repo,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_composed_debug_invariants_floor_on_non_pow2_axis(schedule):
    """On a non-power-of-two axis the scalar sits at the rounding floor
    (~1e-9 float32 ulp of the grads; XLA's fused-program lowering is not
    bitwise rank-identical on dp=3) — far below the ~1e-2 signal of a
    real mis-placed transpose, so the 1e-6 threshold separates cleanly.
    A mean-compare would add rounding of its own; the neighbor-compare
    keeps the floor at ulp level."""
    from jax.sharding import Mesh
    from accl_tpu.models import TransformerConfig, init_params
    from accl_tpu.models.composed import make_pp_train_step

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=32, attention="naive",
    )
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, cfg.vocab)
    tgts = jnp.roll(toks, -1, axis=1)
    mesh = Mesh(
        np.array(jax.devices()[:6]).reshape(2, 3, 1), ("pp", "dp", "tp")
    )
    step, shard = make_pp_train_step(
        cfg, mesh, num_microbatches=2, lr=0.05, schedule=schedule,
        debug_invariants=True,
    )
    _, loss, inv = step(shard(params0), toks, tgts)
    assert np.isfinite(float(loss))
    assert float(inv) <= 1e-6  # rounding floor; violations are ~1e-2
