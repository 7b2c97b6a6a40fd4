"""ZeRO-sharded optimizer: dp-sharded Adam must equal unsharded Adam.

The sharded step's only cross-dp gradient exchange is reduce-scatter +
allgather (the two legs the reference's fused ring allreduce interleaves,
ccl_offload_control.c:1888-2071) with fp32 moments living 1/dp per rank.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from accl_tpu.models import TransformerConfig, init_params
from accl_tpu.models.transformer import loss_fn
from accl_tpu.parallel import AdamConfig, make_zero_train_step


@pytest.fixture(scope="module")
def cfg():
    # attention="naive": this suite asserts ZeRO-vs-unsharded ADAM
    # equivalence at tight tolerance; the blockwise lowering's scan-
    # ordered sums interact with CPU thread partitioning to shift
    # near-zero-gradient Adam updates run-to-run, which is attention
    # numerics, not the optimizer under test (covered separately by
    # test_blockwise_train_step_matches_naive)
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32,
        attention="naive",
    )


@pytest.fixture(scope="module")
def mesh42():
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("dp", "tp"))


def _reference_adam(params, tokens, targets, cfg, adam, steps, clip=None):
    """Unsharded fp32 Adam with the same formula, full batch; ``clip``
    applies textbook global-norm gradient clipping."""
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    losses = []
    for t in range(1, steps + 1):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg)
        losses.append(float(loss))
        if clip is not None:
            norm = jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)
                )
            )
            scale = clip / jnp.maximum(norm, clip)
            grads = jax.tree.map(lambda g: g * scale, grads)
        bc1 = 1.0 - adam.b1**t
        bc2 = 1.0 - adam.b2**t

        def upd(p, g, m_, v_):
            g = g.astype(jnp.float32)
            m_ = adam.b1 * m_ + (1 - adam.b1) * g
            v_ = adam.b2 * v_ + (1 - adam.b2) * g * g
            step_ = adam.lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + adam.eps)
            return (p.astype(jnp.float32) - step_).astype(p.dtype), m_, v_

        out = jax.tree.map(upd, params, grads, m, v)
        leaves = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        st = jax.tree.structure(params)
        params = jax.tree.unflatten(st, [x[0] for x in leaves])
        m = jax.tree.unflatten(st, [x[1] for x in leaves])
        v = jax.tree.unflatten(st, [x[2] for x in leaves])
    return params, losses


def test_zero_matches_unsharded_adam(cfg, mesh42):
    adam = AdamConfig(lr=0.01)
    params0 = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    expected, ref_losses = _reference_adam(
        params0, tokens, targets, cfg, adam, steps=3
    )

    step, shard, init_state = make_zero_train_step(cfg, mesh42, adam)
    params = shard(params0)
    state = init_state(params0)
    losses = []
    for _ in range(3):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    # atol floor: Adam's update is ~ g/(|g|+eps), so near-zero gradient
    # elements amplify reduction-order roundoff to ~1e-5 over 3 steps
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_zero_state_is_dp_sharded(cfg, mesh42):
    _, _, init_state = make_zero_train_step(cfg, mesh42)
    state = init_state(init_params(jax.random.PRNGKey(0), cfg))
    leaf = state["m"]["embed"]
    spec = leaf.sharding.spec
    assert spec == P("dp"), spec
    # each dp rank materializes 1/dp of the moments
    shard_elems = {s.data.shape[0] for s in leaf.addressable_shards}
    assert shard_elems == {leaf.shape[0] // 4}, shard_elems


def test_zero_loss_decreases(cfg, mesh42):
    step, shard, init_state = make_zero_train_step(
        cfg, mesh42, AdamConfig(lr=0.02)
    )
    params0 = init_params(jax.random.PRNGKey(3), cfg)
    params = shard(params0)
    state = init_state(params0)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_zero_trainer_checkpoint_resume(tmp_path):
    """The trainer example with optimizer=zero_adam checkpoints and
    resumes the SHARDED optimizer state alongside the params."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss1 = train(
        steps=6, ckpt_dir=ckpt, save_every=3, log_every=0,
        optimizer="zero_adam",
    )
    assert done == 6 and np.isfinite(loss1)
    done, loss2 = train(
        steps=8, ckpt_dir=ckpt, save_every=3, log_every=0,
        optimizer="zero_adam",
    )
    assert done == 8 and np.isfinite(loss2)


def test_optimizer_mismatch_diagnosable(tmp_path):
    from accl_tpu.examples.train import train
    ckpt = str(tmp_path / "ck")
    train(steps=3, ckpt_dir=ckpt, save_every=2, log_every=0)  # sgd tree
    with pytest.raises(ValueError, match="different --optimizer"):
        train(steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
              optimizer="zero_adam")


def test_schedule_lr_warmup_cosine():
    from accl_tpu.parallel import schedule_lr

    adam = AdamConfig(
        lr=1.0, warmup_steps=10, decay_steps=110, min_lr_ratio=0.1
    )
    # linear warmup: step 5 of 10 is half the peak
    assert float(schedule_lr(adam, 5)) == pytest.approx(0.5)
    assert float(schedule_lr(adam, 10)) == pytest.approx(1.0)
    # midpoint of the cosine span (steps 10..110): halfway to the floor
    assert float(schedule_lr(adam, 60)) == pytest.approx(0.55, abs=1e-6)
    # at/after decay_steps: the floor
    assert float(schedule_lr(adam, 110)) == pytest.approx(0.1)
    assert float(schedule_lr(adam, 500)) == pytest.approx(0.1)
    # no schedule configured: constant
    assert float(schedule_lr(AdamConfig(lr=0.3), 1234)) == pytest.approx(0.3)


def test_zero_adamw_decays_matrices_not_vectors(cfg, mesh42):
    """AdamW's decoupled decay must shrink matrix params even at zero
    gradient, and leave 1-D leaves (ln scales) untouched."""
    key = jax.random.PRNGKey(5)
    params = init_params(key, cfg)
    adam = AdamConfig(lr=0.1, weight_decay=0.5)
    step, shard, init_state = make_zero_train_step(cfg, mesh42, adam)
    sharded = shard(params)
    state = init_state(params)
    # compare norms across two identical steps that differ only in
    # weight_decay: the decoupled decay term must shrink matrix norms
    tokens = jnp.zeros((4, 8), jnp.int32)
    targets = jnp.zeros((4, 8), jnp.int32)
    p_wd, _, _ = step(sharded, state, tokens, targets)

    step2, shard2, init2 = make_zero_train_step(
        cfg, mesh42, AdamConfig(lr=0.1, weight_decay=0.0)
    )
    p_plain, _, _ = step2(shard2(params), init2(params), tokens, targets)

    w_wd = np.asarray(p_wd["layers"][0]["w1"])
    w_plain = np.asarray(p_plain["layers"][0]["w1"])
    assert np.linalg.norm(w_wd) < np.linalg.norm(w_plain)
    # 1-D leaves exempt: identical under either setting
    np.testing.assert_array_equal(
        np.asarray(p_wd["layers"][0]["ln1"]),
        np.asarray(p_plain["layers"][0]["ln1"]),
    )


def test_zero_schedule_applies_inside_step(cfg, mesh42):
    """warmup_steps > first steps => tiny LR => params barely move;
    the schedule is read from the CHECKPOINTED step counter."""
    key = jax.random.PRNGKey(6)
    params = init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 8), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    def delta(adam):
        step, shard, init_state = make_zero_train_step(cfg, mesh42, adam)
        p1, _, _ = step(shard(params), init_state(params), tokens, targets)
        return float(
            np.abs(
                np.asarray(p1["embed"]) - np.asarray(params["embed"])
            ).max()
        )

    big = delta(AdamConfig(lr=0.1))
    small = delta(AdamConfig(lr=0.1, warmup_steps=1000))
    assert small < big / 100


def test_schedule_rejects_decay_before_warmup():
    from accl_tpu.parallel import schedule_lr

    with pytest.raises(ValueError, match="must exceed warmup"):
        schedule_lr(AdamConfig(warmup_steps=100, decay_steps=50), 1)


def test_step_builder_rejects_bad_schedule(cfg, mesh42):
    with pytest.raises(ValueError, match="must exceed warmup"):
        make_zero_train_step(
            cfg, mesh42, AdamConfig(warmup_steps=100, decay_steps=50)
        )


# ---------------------------------------------------------------------------
# gradient clipping + accumulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("clip", [0.05, 1e6])
def test_zero_clip_matches_unsharded(cfg, mesh42, clip):
    """Sharded global-norm clipping (tp-psum'd squared sums) == plain
    unsharded clipping — both in the clipping regime (tiny max norm)
    and the no-op regime (huge max norm)."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    adam = AdamConfig(lr=0.01, clip_grad_norm=clip)

    expected, _ = _reference_adam(
        params, tokens, targets, cfg, adam, steps=3, clip=clip
    )

    step, shard, init_state = make_zero_train_step(cfg, mesh42, adam)
    p, s = shard(params), init_state(params)
    for _ in range(3):
        p, s, _ = step(p, s, tokens, targets)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(p)):
        # reduction order differs (tp-psum'd vs flat sum of squares), so
        # a near-threshold clip scale shifts a few updates by ~1e-6
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_zero_accumulation_matches_full_batch(cfg, mesh42):
    """accum_steps=2 (scan of microbatch grads, one optimizer step) must
    equal the single full-batch step exactly: the mean loss's gradient
    IS the average of the microbatch gradients."""
    params = init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    # eps=1e-3: the FIRST Adam step is g/(|g|+eps), so tiny eps turns
    # ulp-level summation-order deltas on near-zero gradients into
    # lr-scale update swings (measured: accumulated grads match the
    # full batch to 1e-8, yet eps=1e-8 params differed by 5e-4).  A
    # fatter eps keeps the comparison about the ACCUMULATION math.
    adam = AdamConfig(lr=0.01, eps=1e-3, clip_grad_norm=1.0)

    step1, shard, init_state = make_zero_train_step(cfg, mesh42, adam)
    p1, s1 = shard(params), init_state(params)
    p1, s1, l1 = step1(p1, s1, tokens, targets)

    step2, shard2, init2 = make_zero_train_step(
        cfg, mesh42, adam, accum_steps=2
    )
    p2, s2 = shard2(params), init2(params)
    p2, s2, l2 = step2(p2, s2, tokens, targets)

    assert float(l2) == pytest.approx(float(l1), rel=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_zero_accumulation_rejects_ragged_batch(cfg, mesh42):
    params = init_params(jax.random.PRNGKey(2), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab)
    step, shard, init_state = make_zero_train_step(
        cfg, mesh42, AdamConfig(), accum_steps=3
    )
    with pytest.raises(Exception, match="divide|accum"):
        step(shard(params), init_state(params), tokens, jnp.roll(tokens, -1, 1))


# ---------------------------------------------------------------------------
# fp32 master weights (mixed-precision training)
# ---------------------------------------------------------------------------


def test_master_weights_state_and_f32_noop(cfg, mesh42):
    """With f32 params the master track is exact, so master_weights=True
    must produce the identical trajectory to the plain step; the state
    gains sharded fp32 'w' slices."""
    params = init_params(jax.random.PRNGKey(6), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    s1, sh1, i1 = make_zero_train_step(cfg, mesh42, AdamConfig(lr=0.01))
    s2, sh2, i2 = make_zero_train_step(
        cfg, mesh42, AdamConfig(lr=0.01, master_weights=True)
    )
    st2 = i2(params)
    assert "w" in st2 and st2["w"]["embed"].dtype == jnp.float32
    # master slices are dp-sharded like the moments
    assert st2["w"]["embed"].sharding.spec == P("dp")

    p1, st1, l1 = s1(sh1(params), i1(params), tokens, targets)
    p2, st2, l2 = s2(sh2(params), st2, tokens, targets)
    assert float(l1) == float(l2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_master_weights_bf16_matches_f32_track(mesh42):
    """bf16 params + master weights == the reference mixed-precision
    loop: an exact fp32 weight track whose bf16 cast feeds each forward.
    Run several steps so update accumulation matters."""
    cfg16 = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32,
        attention="naive", dtype=jnp.bfloat16,
    )
    params = init_params(jax.random.PRNGKey(8), cfg16)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1)
    adam = AdamConfig(lr=1e-3, eps=1e-3, master_weights=True)

    # reference: fp32 master w; grads at bf16(w); exact fp32 Adam update
    w = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    for t in range(1, 4):
        p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), w)
        grads = jax.grad(loss_fn)(p16, tokens, targets, cfg16)
        bc1, bc2 = 1.0 - adam.b1**t, 1.0 - adam.b2**t

        def upd(w_, g, m_, v_):
            g = g.astype(jnp.float32)
            m_ = adam.b1 * m_ + (1 - adam.b1) * g
            v_ = adam.b2 * v_ + (1 - adam.b2) * g * g
            return (
                w_ - adam.lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + adam.eps),
                m_, v_,
            )

        out = jax.tree.map(upd, w, grads, m, v)
        leaves = jax.tree.leaves(out, is_leaf=lambda x: isinstance(x, tuple))
        st = jax.tree.structure(params)
        w = jax.tree.unflatten(st, [x[0] for x in leaves])
        m = jax.tree.unflatten(st, [x[1] for x in leaves])
        v = jax.tree.unflatten(st, [x[2] for x in leaves])
    expected = jax.tree.map(lambda x: x.astype(jnp.bfloat16), w)

    step, shard, init_state = make_zero_train_step(cfg16, mesh42, adam)
    p, s = shard(params), init_state(params)
    for _ in range(3):
        p, s, _ = step(p, s, tokens, targets)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(p)):
        # ulp-level f32-track noise (bf16 grads, reduction order) flips
        # the bf16 cast by one ulp where the track sits on a rounding
        # boundary — allow exactly that much
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-2, atol=5e-4,
        )


def test_master_weights_keep_sub_ulp_updates(mesh42):
    """The motivating property: updates far below bf16's ulp accumulate
    on the master track (and eventually surface in the bf16 cast), while
    the plain bf16 step loses them forever."""
    cfg16 = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=32,
        attention="naive", dtype=jnp.bfloat16,
    )
    params = init_params(jax.random.PRNGKey(10), cfg16)
    tokens = jax.random.randint(jax.random.PRNGKey(11), (8, 16), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1)
    # lr so small each update is ~1e-6 — far below bf16 ulp (~3e-3 of
    # magnitude-0.4 values, i.e. ~0.4*2^-8)
    adam_m = AdamConfig(lr=3e-7, master_weights=True)
    adam_p = AdamConfig(lr=3e-7)

    sm, shm, im = make_zero_train_step(cfg16, mesh42, adam_m)
    sp, shp, ip = make_zero_train_step(cfg16, mesh42, adam_p)
    pm, stm = shm(params), im(params)
    pp, stp = shp(params), ip(params)
    for _ in range(5):
        pm, stm, _ = sm(pm, stm, tokens, targets)
        pp, stp, _ = sp(pp, stp, tokens, targets)
    # plain bf16: updates rounded away wherever the element's half-ulp
    # exceeds the ~3e-7 update (|p| > 0.01 -> ulp/2 ~ 2e-5); near-zero
    # elements have proportionally tiny ulps and may legitimately move
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(pp)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        big = np.abs(a) > 0.01
        np.testing.assert_array_equal(a[big], b[big])
    # master track: the fp32 slices moved even though the bf16 cast
    # hasn't crossed an ulp boundary yet
    w0 = jax.tree.leaves(im(params)["w"])
    w5 = jax.tree.leaves(stm["w"])
    moved = max(
        float(jnp.abs(a - b).max()) for a, b in zip(w0, w5)
    )
    assert moved > 1e-7, moved


# ---------------------------------------------------------------------------
# MoE (expert banks dp-sharded) through the ZeRO optimizer
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_cfg():
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32,
        n_experts=8, moe_capacity_factor=4.0, attention="naive",
        moe_aux_weight=0.0, moe_router_z_weight=0.0,
    )


def test_zero_moe_state_is_expert_sharded(moe_cfg, mesh42):
    """Expert-bank moments take no further dp split: each rank's state
    covers exactly its expert shard (dp already partitions the bank)."""
    _, _, init_state = make_zero_train_step(moe_cfg, mesh42)
    state = init_state(init_params(jax.random.PRNGKey(0), moe_cfg))
    w1_m = state["m"]["layers"][0]["moe"]["w1"]
    # experts shard over dp AND each expert's d_ff over tp: the moments
    # live with the (dp, tp) weight shard, no further split
    assert w1_m.sharding.spec == P(("dp", "tp")), w1_m.sharding.spec
    n = 8 * 32 * 64  # E * D * F
    assert w1_m.shape == (n,)
    assert {s.data.shape[0] for s in w1_m.addressable_shards} == {n // 8}
    # the router gate is dp-replicated -> classic 1/dp moment slices
    g_m = state["m"]["layers"][0]["moe"]["gate"]
    assert g_m.sharding.spec == P("dp")
    assert {s.data.shape[0] for s in g_m.addressable_shards} == {
        g_m.shape[0] // 4
    }


@pytest.mark.parametrize("extras", ["plain", "clip_master_accum"])
def test_zero_moe_matches_unsharded_adam(moe_cfg, mesh42, extras):
    """ZeRO Adam with dp-sharded expert banks == unsharded Adam — the
    expert grads arrive through the backward all-to-all and update
    rank-locally (no dp slice, no allgather).  The second variant piles
    on clipping + master weights + accumulation simultaneously."""
    if extras == "plain":
        adam = AdamConfig(lr=0.01, eps=1e-3)
        accum = 1
    else:
        adam = AdamConfig(
            lr=0.01, eps=1e-3, clip_grad_norm=0.05, master_weights=True
        )
        accum = 2
    params = init_params(jax.random.PRNGKey(30), moe_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(31), (8, 16), 0, moe_cfg.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)

    # ONE step only: MoE routing is discontinuous (top-1 argmax), so
    # after any update, ulp-level parameter differences can flip a
    # near-tie expert choice and the two trajectories diverge by a full
    # expert's worth — a property of MoE, not of the optimizer under
    # test.  One step pins grads + update + state exactly.
    expected, _ = _reference_adam(
        params, tokens, targets, moe_cfg, adam, steps=1,
        clip=adam.clip_grad_norm,
    )

    step, shard, init_state = make_zero_train_step(
        moe_cfg, mesh42, adam, accum_steps=accum
    )
    p, s = shard(params), init_state(params)
    p, s, _ = step(p, s, tokens, targets)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_zero_context_parallel_matches_dense(cfg, mesh42):
    """zero_adam + context_parallel: the ZeRO maker stripes and
    sequence-shards tokens like the SGD maker, so the cp step's loss
    and params equal the dense zero_adam step exactly."""
    import dataclasses

    cp = dataclasses.replace(cfg, context_parallel=True)
    params = init_params(jax.random.PRNGKey(40), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(41), (8, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    adam = AdamConfig(lr=0.01, eps=1e-3, clip_grad_norm=1.0)

    s1, sh1, i1 = make_zero_train_step(cfg, mesh42, adam)
    p1, _, l1 = s1(sh1(params), i1(params), tokens, targets)
    s2, sh2, i2 = make_zero_train_step(cp, mesh42, adam)
    p2, _, l2 = s2(sh2(params), i2(params), tokens, targets)
    assert float(l2) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_zero_moe_divisibility_diagnostic(mesh42):
    """The ZeRO maker raises the friendly n_experts/dp error, not a raw
    sharding failure."""
    bad = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=32,
        n_experts=6,
    )
    with pytest.raises(ValueError, match="n_experts .6. must divide by dp"):
        make_zero_train_step(bad, mesh42, AdamConfig())
