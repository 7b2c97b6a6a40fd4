"""Flagship model layer: tp/dp-sharded transformer and ring attention must
match their single-device references — the framework's collectives are the
only cross-device edges, so agreement validates those edges end-to-end.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from accl_tpu.compat import has_interpret_params
from accl_tpu.models import (
    TransformerConfig,
    forward,
    init_params,
    make_sharded_forward,
    make_sharded_train_step,
    reference_attention,
    ring_attention,
)


def _skip_unless_flash_runnable():
    """The Pallas flash kernel needs Mosaic (real TPU) or a working
    Pallas TPU interpreter."""
    if jax.default_backend() != "tpu" and not has_interpret_params():
        pytest.skip("flash kernel needs Mosaic or pallas TPU interpret mode")


@pytest.fixture(scope="module")
def cfg():
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32
    )


@pytest.fixture(scope="module")
def mesh22():
    devs = np.array(jax.devices()[:4]).reshape(2, 2)
    return Mesh(devs, ("dp", "tp"))


def test_sharded_forward_matches_single_device(cfg, mesh22):
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)

    expected = forward(params, tokens, cfg)

    fwd, shard = make_sharded_forward(cfg, mesh22)
    logits = fwd(shard(params), tokens)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_sharded_train_step_decreases_loss(cfg, mesh22):
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    step, shard = make_sharded_train_step(cfg, mesh22, lr=0.1)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    sharded = shard(params)
    losses = []
    for _ in range(5):
        sharded, loss = step(sharded, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_sharded_train_step_matches_single_device(cfg, mesh22):
    """One step on the mesh == one step single-device (same grads)."""
    from accl_tpu.models.transformer import loss_fn

    key = jax.random.PRNGKey(2)
    params = init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    lr = 0.05
    loss0, grads = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg)
    expected = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    step, shard = make_sharded_train_step(cfg, mesh22, lr=lr)
    new_params, loss = step(shard(params), tokens, targets)

    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_remat_train_step_matches_plain(cfg, mesh22):
    """remat=True (jax.checkpoint around each block) changes the backward
    schedule, not the math: same loss and same updated params."""
    import dataclasses

    key = jax.random.PRNGKey(4)
    params = init_params(key, cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    outs = []
    for remat in (False, True):
        c = dataclasses.replace(cfg, remat=remat)
        step, shard = make_sharded_train_step(c, mesh22, lr=0.05)
        new_params, loss = step(shard(params), tokens, targets)
        outs.append((float(loss), jax.tree.leaves(new_params)))
    assert outs[0][0] == pytest.approx(outs[1][0], rel=1e-6)
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    B, H, T, D = 2, 2, 64, 16
    sp = 8
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expected = reference_attention(q, k, v, causal=causal)

    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    fn = jax.jit(
        shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_ring_attention_long_sequence():
    """Sequence far larger than any single shard: the long-context case."""
    B, H, T, D = 1, 2, 512, 8
    sp = 8
    key = jax.random.PRNGKey(7)
    q, k, v = (
        jax.random.normal(kk, (B, H, T, D), jnp.float32) * 0.5
        for kk in jax.random.split(key, 3)
    )
    expected = reference_attention(q, k, v, causal=True)
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    fn = jax.jit(
        shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
            check_vma=False,
        )
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=3e-4, atol=3e-5
    )


def test_train_checkpoint_resume(tmp_path):
    """End-to-end trainer with orbax checkpoint/resume (beyond reference:
    SURVEY.md §5 records the reference has no checkpointing at all)."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    _, loss1 = train(steps=6, ckpt_dir=ckpt, save_every=3, log_every=0)
    assert np.isfinite(loss1)
    # second invocation resumes from the saved step and continues further
    _, loss2 = train(steps=8, ckpt_dir=ckpt, save_every=3, log_every=0)
    assert np.isfinite(loss2)


def test_train_resume_past_end(tmp_path):
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    train(steps=4, ckpt_dir=ckpt, save_every=2, log_every=0)
    done, loss = train(steps=4, ckpt_dir=ckpt, save_every=2, log_every=0)
    assert done == 4 and loss is None  # nothing ran, reported honestly



def _naive_greedy(params, prompt, steps, cfg):
    """From-scratch decode oracle: re-run the FULL forward every step."""
    from accl_tpu.models.transformer import forward

    seq = np.asarray(prompt)
    for _ in range(steps):
        logits = forward(params, jnp.asarray(seq), cfg)
        nxt = np.argmax(np.asarray(logits[:, -1]), axis=-1)
        seq = np.concatenate([seq, nxt[:, None].astype(seq.dtype)], axis=1)
    return seq[:, prompt.shape[1]:]


def test_generate_matches_naive_greedy(cfg):
    """KV-cache decode == re-running the full forward each step (greedy).
    Serving-side correctness of the cache layout + masking."""
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(7), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 5), 0, cfg.vocab)
    steps = 6

    got = np.asarray(generate(params, prompt, steps, cfg))
    np.testing.assert_array_equal(got, _naive_greedy(params, prompt, steps, cfg))


def test_sharded_generate_matches_single_device(cfg, mesh22):
    """dp/tp-sharded generation (head-sharded KV cache, tp-allreduce per
    block) produces the same tokens as the single-device decode."""
    from accl_tpu.models import generate, make_sharded_generate

    params = init_params(jax.random.PRNGKey(9), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(10), (2, 4), 0, cfg.vocab)
    steps = 5

    expected = np.asarray(generate(params, prompt, steps, cfg))
    fn, shard = make_sharded_generate(cfg, mesh22, steps)
    got = np.asarray(fn(shard(params), prompt))
    np.testing.assert_array_equal(got, expected)


def test_generate_bfloat16(cfg):
    """bf16 decode must trace and match the full-forward oracle in the
    SAME dtype.  Regression: a strongly-typed NumPy sqrt scalar in the
    decode block once promoted the residual stream to f32, breaking the
    bf16 KV-cache update on the second layer (dynamic_update_slice dtype
    mismatch) — caught only on-chip because the bench decode config is
    the only bf16 decode user."""
    import dataclasses

    from accl_tpu.models import generate

    bcfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(7), bcfg)
    prompt = jax.random.randint(jax.random.PRNGKey(8), (2, 5), 0, bcfg.vocab)
    steps = 6

    got = np.asarray(generate(params, prompt, steps, bcfg))
    np.testing.assert_array_equal(
        got, _naive_greedy(params, prompt, steps, bcfg)
    )


def test_seq_parallel_forward_matches(cfg, mesh22):
    """Megatron-SP: sequence-sharded activations between blocks produce
    the SAME logits as the replicated-activation form."""
    import dataclasses

    params = init_params(jax.random.PRNGKey(11), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (4, 16), 0, cfg.vocab)

    base = forward(params, tokens, cfg)

    sp_cfg = dataclasses.replace(cfg, seq_parallel=True)
    fwd, shard = make_sharded_forward(sp_cfg, mesh22)
    got = fwd(shard(params), tokens)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-4, atol=2e-5
    )


def test_seq_parallel_train_step_matches(cfg, mesh22):
    """SP changes the activation layout, not the math: same loss and same
    updated params as the plain sharded step."""
    import dataclasses

    params = init_params(jax.random.PRNGKey(13), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(14), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    outs = []
    for sp in (False, True):
        c = dataclasses.replace(cfg, seq_parallel=sp)
        step, shard = make_sharded_train_step(c, mesh22, lr=0.05)
        new_params, loss = step(shard(params), tokens, targets)
        outs.append((float(loss), jax.tree.leaves(new_params)))
    assert outs[0][0] == pytest.approx(outs[1][0], rel=1e-5)
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
        )


def test_seq_parallel_rejects_ragged():
    import dataclasses

    c = dataclasses.replace(
        TransformerConfig(vocab=32, d_model=16, n_heads=4, n_layers=1,
                          d_ff=32, max_seq=32),
        seq_parallel=True,
    )
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    fwd, shard = make_sharded_forward(c, mesh)
    params = shard(init_params(jax.random.PRNGKey(0), c))
    tokens = jnp.zeros((2, 15), jnp.int32)  # 15 % tp(2) != 0
    with pytest.raises(Exception, match="divisible"):
        fwd(params, tokens)


def test_generate_sampling(cfg, mesh22):
    """temperature>0 sampling: deterministic per key, in-vocab, and
    near-greedy at tiny temperature; the sharded form matches the
    single-device sampler key-for-key (per-dp-fold)."""
    from accl_tpu.models import generate, make_sharded_generate

    params = init_params(jax.random.PRNGKey(20), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(21), (2, 4), 0, cfg.vocab)

    a = np.asarray(generate(params, prompt, 6, cfg, temperature=1.0,
                            top_k=8, rng=jax.random.PRNGKey(7)))
    b = np.asarray(generate(params, prompt, 6, cfg, temperature=1.0,
                            top_k=8, rng=jax.random.PRNGKey(7)))
    np.testing.assert_array_equal(a, b)
    assert ((0 <= a) & (a < cfg.vocab)).all()

    greedy = np.asarray(generate(params, prompt, 6, cfg))
    cold = np.asarray(generate(params, prompt, 6, cfg, temperature=1e-4,
                               rng=jax.random.PRNGKey(9)))
    np.testing.assert_array_equal(cold, greedy)

    fn, shard = make_sharded_generate(cfg, mesh22, 6, temperature=1.0,
                                      top_k=8)
    key = jax.random.PRNGKey(7)
    toks = np.asarray(fn(shard(params), prompt, key))
    assert toks.shape == (2, 6)
    assert ((0 <= toks) & (toks < cfg.vocab)).all()
    # key-for-key parity: dp shard d must equal the single-device sampler
    # run on its batch rows with the dp-folded key
    for d in range(2):
        expect = np.asarray(generate(
            params, prompt[d:d + 1], 6, cfg, temperature=1.0, top_k=8,
            rng=jax.random.fold_in(key, d),
        ))
        np.testing.assert_array_equal(toks[d:d + 1], expect)


def test_generate_sampling_requires_rng(cfg):
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="requires rng"):
        generate(params, jnp.zeros((1, 4), jnp.int32), 4, cfg,
                 temperature=0.7)


def test_seq_parallel_generate_matches(cfg, mesh22):
    """Serving-side consistency of the SP plan (VERDICT r2 item 7): a
    seq-parallel config must decode to EXACTLY the tokens of the plain
    plan — prefill runs sequence-sharded like the training forward, the
    cache it builds is the same head-sharded layout, and per-token decode
    proceeds on it."""
    import dataclasses

    from accl_tpu.models import generate, make_sharded_generate

    params = init_params(jax.random.PRNGKey(30), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(31), (2, 4), 0, cfg.vocab)
    steps = 6

    expected = np.asarray(generate(params, prompt, steps, cfg))

    sp_cfg = dataclasses.replace(cfg, seq_parallel=True)
    fn, shard = make_sharded_generate(sp_cfg, mesh22, steps)
    got = np.asarray(fn(shard(params), prompt))
    np.testing.assert_array_equal(got, expected)

    # and against the step-by-step full forward (the from-scratch oracle)
    np.testing.assert_array_equal(
        got, _naive_greedy(params, prompt, steps, cfg)
    )


def test_seq_parallel_prefill_rejects_ragged_prompt(cfg, mesh22):
    import dataclasses

    from accl_tpu.models import make_sharded_generate

    sp_cfg = dataclasses.replace(cfg, seq_parallel=True)
    fn, shard = make_sharded_generate(sp_cfg, mesh22, 2)
    params = shard(init_params(jax.random.PRNGKey(0), sp_cfg))
    with pytest.raises(Exception, match="divisible"):
        fn(params, jnp.zeros((2, 5), jnp.int32))  # 5 % tp(2) != 0


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_attention_impls_match_naive(cfg, impl):
    """The fused attention paths (XLA blockwise fold; Pallas flash
    kernel) must match the materialized-scores baseline on the flagship
    forward — the MFU lever cannot change the math."""
    if impl == "flash":
        _skip_unless_flash_runnable()
    import dataclasses

    params = init_params(jax.random.PRNGKey(40), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(41), (2, 30), 0, cfg.vocab)

    base = forward(
        params, tokens, dataclasses.replace(cfg, attention="naive")
    )
    got = forward(params, tokens, dataclasses.replace(cfg, attention=impl))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_blockwise_train_step_matches_naive(cfg, mesh22):
    """Same loss and same updated params whichever attention lowering the
    sharded train step compiles."""
    import dataclasses

    params = init_params(jax.random.PRNGKey(42), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(43), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    outs = []
    for impl in ("naive", "blockwise", "flash"):
        c = dataclasses.replace(cfg, attention=impl)
        step, shard = make_sharded_train_step(c, mesh22, lr=0.05)
        new_params, loss = step(shard(params), tokens, targets)
        outs.append((float(loss), jax.tree.leaves(new_params)))
    for other in outs[1:]:
        assert outs[0][0] == pytest.approx(other[0], rel=1e-5)
        for a, b in zip(outs[0][1], other[1]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5
            )


def test_unknown_attention_impl_raises(cfg):
    import dataclasses

    params = init_params(jax.random.PRNGKey(44), cfg)
    with pytest.raises(ValueError, match="unknown attention impl"):
        forward(
            params, jnp.zeros((1, 8), jnp.int32),
            dataclasses.replace(cfg, attention="dave"),
        )


def test_unknown_attention_rejected_upfront(cfg, mesh22):
    """The train-step builders reject an unknown attention name at build
    time (clear error up front), not deep inside a traced forward."""
    import dataclasses

    from accl_tpu.parallel import AdamConfig, make_zero_train_step

    c = dataclasses.replace(cfg, attention="dave")
    with pytest.raises(ValueError, match="unknown attention impl"):
        make_sharded_train_step(c, mesh22)
    with pytest.raises(ValueError, match="unknown attention impl"):
        make_zero_train_step(c, mesh22, AdamConfig())


# ---------------------------------------------------------------------------
# encoder family (bidirectional blocks + MLM head)
# ---------------------------------------------------------------------------


def test_encoder_is_bidirectional(cfg):
    """Changing a LATE token must change EARLY positions' hidden states —
    the defining property the causal decoder forbids."""
    from accl_tpu.models import encoder_forward, forward

    params = init_params(jax.random.PRNGKey(50), cfg)
    a = jax.random.randint(jax.random.PRNGKey(51), (1, 16), 0, cfg.vocab)
    b = a.at[0, -1].set((a[0, -1] + 1) % cfg.vocab)

    ha = np.asarray(encoder_forward(params, a, cfg))
    hb = np.asarray(encoder_forward(params, b, cfg))
    assert np.abs(ha[0, 0] - hb[0, 0]).max() > 1e-6  # early saw late

    # and the decoder provably did NOT
    la = np.asarray(forward(params, a, cfg))
    lb = np.asarray(forward(params, b, cfg))
    np.testing.assert_allclose(la[0, 0], lb[0, 0], rtol=1e-6)


@pytest.mark.parametrize("impl", ["naive", "blockwise"])
def test_encoder_attention_impls_match(cfg, impl):
    """Full (non-causal) attention matches across lowerings too."""
    import dataclasses

    from accl_tpu.models import encoder_forward

    params = init_params(jax.random.PRNGKey(52), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(53), (2, 20), 0, cfg.vocab)
    base = encoder_forward(
        params, tokens, dataclasses.replace(cfg, attention="naive")
    )
    got = encoder_forward(
        params, tokens, dataclasses.replace(cfg, attention=impl)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_sharded_encoder_step_matches_single_device(cfg, mesh22):
    """The dp x tp MLM step equals the unsharded step: same loss, same
    updated params."""
    from accl_tpu.models import make_sharded_encoder_step, mlm_loss

    params0 = init_params(jax.random.PRNGKey(54), cfg)
    rng = jax.random.PRNGKey(55)
    targets = jax.random.randint(rng, (4, 16), 0, cfg.vocab)
    mask = (jax.random.uniform(jax.random.PRNGKey(56), (4, 16)) < 0.2
            ).astype(jnp.float32)
    # corrupt masked positions with token 0 (the [MASK] stand-in)
    tokens = jnp.where(mask.astype(bool), 0, targets)

    lr = 0.05
    loss_ref, grads = jax.value_and_grad(
        lambda p: mlm_loss(p, tokens, targets, mask, cfg)
    )(params0)
    expected = jax.tree.map(lambda p, g: p - lr * g, params0, grads)

    step, shard = make_sharded_encoder_step(cfg, mesh22, lr=lr)
    new_params, loss = step(shard(params0), tokens, targets, mask)
    assert float(loss) == pytest.approx(float(loss_ref), rel=1e-5)
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, expected)),
        jax.tree.leaves(jax.tree.map(np.asarray, new_params)),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_encode_pools(cfg):
    from accl_tpu.models import encode

    params = init_params(jax.random.PRNGKey(57), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(58), (3, 12), 0, cfg.vocab)
    emb = np.asarray(encode(params, tokens, cfg))
    assert emb.shape == (3, cfg.d_model) and np.isfinite(emb).all()


def test_encoder_seq_parallel_matches(cfg, mesh22):
    """The encoder honors Megatron-SP: sequence-sharded activations
    between bidirectional blocks produce the same hidden states."""
    import dataclasses

    from accl_tpu.models import encoder_forward, make_sharded_encoder_step

    params0 = init_params(jax.random.PRNGKey(60), cfg)
    tgts = jax.random.randint(jax.random.PRNGKey(61), (4, 16), 0, cfg.vocab)
    mask = (jax.random.uniform(jax.random.PRNGKey(62), (4, 16)) < 0.2
            ).astype(jnp.float32)
    tokens = jnp.where(mask.astype(bool), 0, tgts)

    outs = []
    for sp in (False, True):
        c = dataclasses.replace(cfg, seq_parallel=sp)
        step, shard = make_sharded_encoder_step(c, mesh22, lr=0.05)
        new_params, loss = step(shard(params0), tokens, tgts, mask)
        outs.append((float(loss), jax.tree.leaves(new_params)))
    assert outs[0][0] == pytest.approx(outs[1][0], rel=1e-5)
    for a, b in zip(outs[0][1], outs[1][1]):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_striped_attention_matches_reference():
    """Striped (round-robin) causal ring attention == the full-sequence
    reference after layout round-trip; every hop's mask is triangular so
    the causal work balances across the ring (Striped Attention)."""
    from functools import partial

    from accl_tpu.models import (
        reference_attention, stripe_sequence, striped_attention,
        unstripe_sequence,
    )

    P_ = 4
    mesh = Mesh(np.array(jax.devices()[:P_]), ("sp",))
    B, H, T, D = 2, 2, 32, 16
    rng = np.random.default_rng(70)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
        for _ in range(3)
    )

    for causal in (True, False):
        fn = jax.jit(
            shard_map(
                partial(striped_attention, axis_name="sp", causal=causal),
                mesh=mesh,
                in_specs=(P(None, None, "sp", None),) * 3,
                out_specs=P(None, None, "sp", None),
                check_vma=False,
            )
        )
        out = fn(
            stripe_sequence(q, P_), stripe_sequence(k, P_),
            stripe_sequence(v, P_),
        )
        got = np.asarray(unstripe_sequence(out, P_))
        expect = np.asarray(reference_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(got, expect, rtol=2e-4, atol=2e-5)


def test_stripe_roundtrip():
    from accl_tpu.models import stripe_sequence, unstripe_sequence

    x = jnp.arange(2 * 3 * 12 * 4, dtype=jnp.float32).reshape(2, 3, 12, 4)
    np.testing.assert_array_equal(
        np.asarray(unstripe_sequence(stripe_sequence(x, 4), 4)),
        np.asarray(x),
    )
    with pytest.raises(ValueError, match="divide"):
        stripe_sequence(x, 5)


def test_trainer_pipeline_parallelism(tmp_path):
    """The trainer example over the composed pp x dp x tp mesh: trains,
    checkpoints stacked params, resumes, and rejects the unsupported
    optimizer combination loudly."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss1 = train(
        steps=4, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline",
    )
    assert done == 4 and np.isfinite(loss1)
    done, loss2 = train(
        steps=6, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline",
    )
    assert done == 6 and np.isfinite(loss2)

    # pipeline + zero_adam is SUPPORTED now; what stays rejected is
    # accum_steps (the pipeline accumulates through its microbatches)
    with pytest.raises(ValueError, match="microbatches"):
        train(
            steps=2, parallelism="pipeline", optimizer="zero_adam",
            accum_steps=2,
        )


def test_trainer_parallelism_mismatch_diagnosable(tmp_path):
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ck2")
    train(steps=3, ckpt_dir=ckpt, save_every=2, log_every=0)  # dp_tp layout
    with pytest.raises(ValueError, match="--parallelism"):
        train(steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
              parallelism="pipeline")


# ---------------------------------------------------------------------------
# grouped-query attention (GQA / MQA)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gqa_cfg():
    return TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_kv_heads=2, n_layers=2,
        d_ff=96, max_seq=48,
    )


def test_gqa_param_shapes_and_validation(gqa_cfg):
    import dataclasses

    params = init_params(jax.random.PRNGKey(0), gqa_cfg)
    hd = gqa_cfg.d_model // gqa_cfg.n_heads
    assert params["layers"][0]["wk"].shape == (gqa_cfg.d_model, 2 * hd)
    assert params["layers"][0]["wv"].shape == (gqa_cfg.d_model, 2 * hd)
    assert params["layers"][0]["wq"].shape == (
        gqa_cfg.d_model, gqa_cfg.d_model
    )
    with pytest.raises(ValueError, match="divide"):
        dataclasses.replace(gqa_cfg, n_kv_heads=3).kv_heads()


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_gqa_attention_impls_match_naive(gqa_cfg, impl):
    """Every attention lowering must implement the same grouped-query
    math (q head h reads kv head h // G)."""
    if impl == "flash":
        _skip_unless_flash_runnable()
    import dataclasses

    params = init_params(jax.random.PRNGKey(7), gqa_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(8), (2, 20), 0, gqa_cfg.vocab
    )
    base = forward(
        params, tokens, dataclasses.replace(gqa_cfg, attention="naive")
    )
    got = forward(
        params, tokens, dataclasses.replace(gqa_cfg, attention=impl)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_gqa_decode_token_exact(gqa_cfg):
    """KV-cache decode over the (B, Hkv, S, hd) GQA cache must reproduce
    the full-forward greedy continuation exactly."""
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(9), gqa_cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(10), (2, 12), 0, gqa_cfg.vocab
    )
    got = generate(params, prompt, 6, gqa_cfg)
    cur = prompt
    for _ in range(6):
        lg = forward(params, cur, gqa_cfg)
        nxt = lg[:, -1].argmax(-1)[:, None].astype(cur.dtype)
        cur = jnp.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cur[:, 12:]))


def test_gqa_sharded_train_matches_sp(gqa_cfg, mesh22):
    """GQA under tp=2 (each chip owns one kv head): the sequence-parallel
    layout must produce the identical loss."""
    import dataclasses

    tokens = jax.random.randint(
        jax.random.PRNGKey(11), (4, 16), 0, gqa_cfg.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for sp in (False, True):
        c = dataclasses.replace(gqa_cfg, seq_parallel=sp)
        step, shard = make_sharded_train_step(c, mesh22, lr=0.05)
        params = shard(init_params(jax.random.PRNGKey(0), c))
        _, loss = step(params, tokens, targets)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)


def test_gqa_rejects_kv_heads_below_tp(gqa_cfg, mesh22):
    """MQA (1 kv head) cannot shard over tp=2: clear build-time error."""
    import dataclasses

    from accl_tpu.models import make_sharded_generate

    c = dataclasses.replace(gqa_cfg, n_kv_heads=1)
    fn, shard = make_sharded_generate(c, mesh22, 2)
    prompt = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="divisible by tp"):
        fn(shard(init_params(jax.random.PRNGKey(0), c)), prompt)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rope_cfg():
    return TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_kv_heads=4, n_layers=2,
        d_ff=96, max_seq=48, pos_embedding="rope",
    )


def test_rope_has_no_pos_table(rope_cfg):
    import dataclasses

    params = init_params(jax.random.PRNGKey(0), rope_cfg)
    assert "pos" not in params
    from accl_tpu.models.transformer import param_specs

    assert "pos" not in param_specs(rope_cfg)
    with pytest.raises(ValueError, match="even head dim"):
        dataclasses.replace(
            rope_cfg, d_model=40, n_heads=8  # head dim 5
        ).uses_rope()
    with pytest.raises(ValueError, match="unknown pos_embedding"):
        dataclasses.replace(rope_cfg, pos_embedding="alibi").uses_rope()


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_rope_attention_impls_match_naive(rope_cfg, impl):
    """Rotation happens before the lowering, so every attention impl
    must agree under rope too."""
    if impl == "flash":
        _skip_unless_flash_runnable()
    import dataclasses

    params = init_params(jax.random.PRNGKey(13), rope_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(14), (2, 20), 0, rope_cfg.vocab
    )
    base = forward(
        params, tokens, dataclasses.replace(rope_cfg, attention="naive")
    )
    got = forward(
        params, tokens, dataclasses.replace(rope_cfg, attention=impl)
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), rtol=2e-5, atol=2e-5
    )


def test_rope_decode_token_exact(rope_cfg):
    """Decode rotates q/k at the dynamic cursor against a cache of keys
    rotated at THEIR positions: must reproduce the full forward exactly
    (the relative-position property, end to end)."""
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(15), rope_cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(16), (2, 11), 0, rope_cfg.vocab
    )
    got = generate(params, prompt, 7, rope_cfg)
    cur = prompt
    for _ in range(7):
        lg = forward(params, cur, rope_cfg)
        nxt = lg[:, -1].argmax(-1)[:, None].astype(cur.dtype)
        cur = jnp.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cur[:, 11:]))


def test_rope_relative_position_invariance(rope_cfg):
    """The defining rope property: with no position table, attention
    depends only on RELATIVE offsets — feeding the same embeddings at a
    shifted absolute position changes nothing about causal attention
    among them.  Compare hidden states of a window decoded at offset 0
    vs the same window after a shared prefix of repeated tokens is
    dropped from the cache... realized here as: rotating q/k by
    positions p and p+s gives identical scores."""
    from accl_tpu.models.transformer import _rope_rotate, _rope_tables

    rng = np.random.default_rng(17)
    q = jnp.asarray(rng.standard_normal((1, 2, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 8, 16)), jnp.float32)
    base = rope_cfg.rope_base
    t0 = _rope_tables(jnp.arange(8), 8, base)
    t1 = _rope_tables(jnp.arange(8) + 1000, 8, base)
    s0 = jnp.einsum(
        "bhqd,bhkd->bhqk", _rope_rotate(q, t0), _rope_rotate(k, t0)
    )
    s1 = jnp.einsum(
        "bhqd,bhkd->bhqk", _rope_rotate(q, t1), _rope_rotate(k, t1)
    )
    np.testing.assert_allclose(
        np.asarray(s0), np.asarray(s1), rtol=2e-4, atol=2e-4
    )


def test_rope_generates_past_max_seq(rope_cfg):
    """rope has no position table, so max_seq is not a serving cliff:
    prompt + steps may exceed it (the cache sizes to T + steps)."""
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(21), rope_cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(22), (1, 40), 0, rope_cfg.vocab
    )
    out = generate(params, prompt, 16, rope_cfg)  # 56 > max_seq=48
    assert np.asarray(out).shape == (1, 16)


def test_rope_sharded_train_matches_sp(rope_cfg, mesh22):
    import dataclasses

    tokens = jax.random.randint(
        jax.random.PRNGKey(18), (4, 16), 0, rope_cfg.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)
    losses = []
    for sp in (False, True):
        c = dataclasses.replace(rope_cfg, seq_parallel=sp)
        step, shard = make_sharded_train_step(c, mesh22, lr=0.05)
        params = shard(init_params(jax.random.PRNGKey(0), c))
        _, loss = step(params, tokens, targets)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)


def test_rope_encoder_forward(rope_cfg):
    """The encoder family shares the block path: rope must flow through
    causal=False blocks too (and change with token positions)."""
    from accl_tpu.models import encoder_forward

    params = init_params(jax.random.PRNGKey(19), rope_cfg)
    toks = jax.random.randint(
        jax.random.PRNGKey(20), (2, 12), 0, rope_cfg.vocab
    )
    h = encoder_forward(params, toks, rope_cfg)
    assert h.shape == (2, 12, rope_cfg.d_model)
    # position sensitivity: the same token repeated inside a VARIED
    # sequence must get different hidden states at its two positions
    # (position enters via q/k rotation; note an all-identical sequence
    # would NOT show this — every value vector is identical, so any
    # score pattern averages to the same output)
    varied = jnp.asarray([[7, 1, 2, 7, 3, 4, 5, 6, 8, 9, 10, 11]], toks.dtype)
    h2 = np.asarray(encoder_forward(params, varied, rope_cfg))
    assert not np.allclose(h2[0, 0], h2[0, 3], atol=1e-5)


# ---------------------------------------------------------------------------
# Megatron vocab parallelism (sharded embedding + fused cross-entropy)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def vp_cfg(cfg):
    import dataclasses

    return dataclasses.replace(cfg, vocab_parallel=True)


def test_vocab_parallel_shards_embedding(vp_cfg, mesh22):
    from accl_tpu.models.transformer import _shard_params, param_specs

    params = init_params(jax.random.PRNGKey(0), vp_cfg)
    sharded = _shard_params(params, specs=param_specs(vp_cfg), mesh=mesh22)
    shapes = {s.data.shape for s in sharded["embed"].addressable_shards}
    assert shapes == {(vp_cfg.vocab // 2, vp_cfg.d_model)}, shapes


@pytest.mark.parametrize("sp", [False, True])
def test_vocab_parallel_train_matches_replicated(vp_cfg, cfg, mesh22, sp):
    """The fused vocab-parallel cross-entropy (sharded logits never
    materialized) must produce the identical loss AND updated params as
    the replicated head — with and without sequence parallelism (where
    the hidden exits the SP regime before the vocab-parallel head)."""
    import dataclasses

    tokens = jax.random.randint(jax.random.PRNGKey(30), (4, 16), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    params = init_params(jax.random.PRNGKey(0), cfg)

    step_b, shard_b = make_sharded_train_step(cfg, mesh22, lr=0.05)
    pb, loss_b = step_b(shard_b(params), tokens, targets)

    c = dataclasses.replace(vp_cfg, seq_parallel=sp)
    step_v, shard_v = make_sharded_train_step(c, mesh22, lr=0.05)
    pv, loss_v = step_v(shard_v(params), tokens, targets)

    assert float(loss_v) == pytest.approx(float(loss_b), rel=1e-5)
    for a, b in zip(jax.tree.leaves(pv), jax.tree.leaves(pb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_vocab_parallel_forward_and_generate_match(vp_cfg, cfg, mesh22):
    from accl_tpu.models import make_sharded_forward, make_sharded_generate

    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(31), (4, 10), 0, cfg.vocab)
    # an out-of-range id must clamp to the last vocab row on BOTH paths
    # (the replicated gather's semantics), not zero out on the vp path
    tokens = tokens.at[0, 0].set(cfg.vocab + 5)

    fwd_b, shard_b = make_sharded_forward(cfg, mesh22)
    fwd_v, shard_v = make_sharded_forward(vp_cfg, mesh22)
    np.testing.assert_allclose(
        np.asarray(fwd_v(shard_v(params), tokens)),
        np.asarray(fwd_b(shard_b(params), tokens)),
        rtol=2e-4, atol=2e-5,
    )

    g_b, sh_b = make_sharded_generate(cfg, mesh22, 4)
    g_v, sh_v = make_sharded_generate(vp_cfg, mesh22, 4)
    np.testing.assert_array_equal(
        np.asarray(g_v(sh_v(params), tokens)),
        np.asarray(g_b(sh_b(params), tokens)),
    )


def test_vocab_parallel_rejected_outside_decoder(vp_cfg, mesh22):
    from accl_tpu.models import encoder_forward

    params = init_params(jax.random.PRNGKey(0), vp_cfg)
    with pytest.raises(ValueError, match="decoder flagship only"):
        encoder_forward(params, jnp.zeros((1, 8), jnp.int32), vp_cfg)


def test_vocab_parallel_requires_divisible_vocab(mesh22):
    import dataclasses

    from accl_tpu.models import make_sharded_forward

    bad = TransformerConfig(
        vocab=63, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=16,
        vocab_parallel=True,
    )
    fwd, shard = make_sharded_forward(bad, mesh22)
    with pytest.raises(Exception, match="divisible|divide"):
        fwd(
            shard(init_params(jax.random.PRNGKey(0), bad)),
            jnp.zeros((2, 8), jnp.int32),
        )


# ---------------------------------------------------------------------------
# context parallelism (striped ring attention inside the flagship)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh24():
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("dp", "tp"))


@pytest.mark.parametrize(
    "pos,remat", [("learned", False), ("rope", False), ("rope", True)]
)
def test_context_parallel_train_matches_dense(mesh24, pos, remat):
    """A cp=4 train step (weights replicated over the ring, activations
    sequence-sharded end-to-end, striped ring attention, local loss +
    ring mean) must match the dense tp-sharded step on the same mesh —
    loss and updated params — including GQA + rope + remat."""
    import dataclasses

    base = TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_kv_heads=4, n_layers=2,
        d_ff=96, max_seq=32, pos_embedding=pos, remat=remat,
    )
    cp = dataclasses.replace(base, context_parallel=True)
    tokens = jax.random.randint(jax.random.PRNGKey(40), (4, 16), 0, base.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    params = init_params(jax.random.PRNGKey(1), base)

    step_b, shard_b = make_sharded_train_step(base, mesh24, lr=0.05)
    pb, loss_b = step_b(shard_b(params), tokens, targets)
    step_c, shard_c = make_sharded_train_step(cp, mesh24, lr=0.05)
    pc, loss_c = step_c(shard_c(params), tokens, targets)

    assert float(loss_c) == pytest.approx(float(loss_b), rel=1e-5)
    for a, b in zip(jax.tree.leaves(pc), jax.tree.leaves(pb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


@pytest.mark.parametrize("mesh_kind", ["auto", "explicit"])
def test_context_parallel_forward_matches_dense(mesh24, mesh_kind):
    """make_sharded_forward under cp stripes in / unstripes out, so the
    caller sees token-order logits identical to the dense lowering — on
    BOTH mesh axis modes (jax.make_mesh defaults to EXPLICIT sharding
    axes, where the exit edge must reshard before the unstripe
    permutation; plain Mesh gives auto axes)."""
    import dataclasses

    if mesh_kind == "explicit":
        mesh = jax.make_mesh((2, 4), ("dp", "tp"))
        if jax.sharding.AxisType.Explicit not in mesh.axis_types:
            pytest.skip("make_mesh is not explicit-axes on this jax")
    else:
        mesh = mesh24

    base = TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=96, max_seq=32,
    )
    cp = dataclasses.replace(base, context_parallel=True)
    params = init_params(jax.random.PRNGKey(2), base)
    tokens = jax.random.randint(jax.random.PRNGKey(41), (2, 16), 0, base.vocab)

    fwd_b, shard_b = make_sharded_forward(base, mesh)
    fwd_c, shard_c = make_sharded_forward(cp, mesh)
    np.testing.assert_allclose(
        np.asarray(fwd_c(shard_c(params), tokens)),
        np.asarray(fwd_b(shard_b(params), tokens)),
        rtol=2e-4, atol=2e-5,
    )


def test_context_parallel_params_replicated_and_servable(mesh24):
    """cp shards nothing but the sequence: every param is fully
    replicated over tp, and the updated params re-shard directly under
    the dense config for serving (the documented serving path)."""
    import dataclasses

    from accl_tpu.models import make_sharded_generate
    from accl_tpu.models.transformer import _shard_params, param_specs

    base = TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_layers=2, d_ff=96, max_seq=32,
    )
    cp = dataclasses.replace(base, context_parallel=True)
    params = init_params(jax.random.PRNGKey(3), base)
    sharded = _shard_params(params, specs=param_specs(cp), mesh=mesh24)
    w = sharded["layers"][0]["wq"]
    assert {s.data.shape for s in w.addressable_shards} == {w.shape}

    tokens = jax.random.randint(jax.random.PRNGKey(42), (2, 16), 0, 64)
    step_c, shard_c = make_sharded_train_step(cp, mesh24, lr=0.05)
    pc, _ = step_c(shard_c(params), tokens, jnp.roll(tokens, -1, 1))

    gen, shard_g = make_sharded_generate(base, mesh24, 4)
    out = np.asarray(gen(shard_g(jax.tree.map(np.asarray, pc)), tokens))
    assert out.shape == (2, 4)  # generate returns the generated tokens


def test_context_parallel_gqa_ring_rotates_unexpanded_kv():
    """The ring fold accepts k/v carrying only the kv heads (GQA):
    striped ring output == reference attention with kv expanded."""
    from functools import partial

    from accl_tpu.models import (
        reference_attention, stripe_sequence, striped_attention,
        unstripe_sequence,
    )

    P_ = 4
    mesh = Mesh(np.array(jax.devices()[:P_]), ("sp",))
    B, H, Hkv, T, D = 2, 8, 2, 32, 16
    rng = np.random.default_rng(71)
    q = jnp.asarray(rng.standard_normal((B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)

    want = reference_attention(
        q, jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1),
        causal=True,
    )
    # block_k sub-tiles the visiting block inside each ring hop (the
    # within-hop blockwise memory contract); None folds whole hops —
    # identical results either way
    for block_k in (None, 4):
        fn = jax.jit(
            shard_map(
                partial(
                    striped_attention, axis_name="sp", causal=True,
                    block_k=block_k,
                ),
                mesh=mesh,
                in_specs=(P(None, None, "sp", None),) * 3,
                out_specs=P(None, None, "sp", None),
                check_vma=False,
            )
        )
        got = unstripe_sequence(
            fn(*(stripe_sequence(t, P_) for t in (q, k, v))), P_
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )


def test_cp_block_k_honors_attention_contract():
    """The cp block's within-hop sub-tiling follows the config's
    attention lowering: naive = whole-hop folds; blockwise/flash always
    sub-tile; auto sub-tiles at the measured fused crossover."""
    from accl_tpu.models.transformer import _AUTO_FUSED_MIN_T, _cp_block_k

    assert _cp_block_k(8192, "naive") is None
    assert _cp_block_k(8192, "blockwise") == 512
    assert _cp_block_k(8192, "flash") == 512
    assert _cp_block_k(_AUTO_FUSED_MIN_T // 2, "auto") is None
    assert _cp_block_k(_AUTO_FUSED_MIN_T, "auto") == 512
    assert _cp_block_k(8, "flash") is None  # tiny shard: nothing to tile


def test_context_parallel_rejections(mesh24):
    import dataclasses

    from accl_tpu.models import encoder_forward, make_sharded_generate

    base = TransformerConfig(
        vocab=64, d_model=64, n_heads=8, n_layers=1, d_ff=96, max_seq=32,
        context_parallel=True,
    )
    with pytest.raises(ValueError, match="incompatible"):
        make_sharded_train_step(
            dataclasses.replace(base, seq_parallel=True), mesh24
        )
    with pytest.raises(ValueError, match="incompatible"):
        make_sharded_train_step(
            dataclasses.replace(base, vocab_parallel=True), mesh24
        )
    with pytest.raises(ValueError, match="no serving path"):
        make_sharded_generate(base, mesh24, 4)
    params = init_params(jax.random.PRNGKey(0), base)
    with pytest.raises(ValueError, match="decoder-only"):
        encoder_forward(
            params, jnp.zeros((1, 8), jnp.int32), base, tp_axis=None
        )


# ---------------------------------------------------------------------------
# MoE in the flagship (expert parallelism on the dp axis)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def moe_cfg():
    # capacity 4.0: nothing drops, so sharded dispatch (per-rank slot
    # assignment) and single-device dispatch produce identical outputs
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32,
        n_experts=8, moe_capacity_factor=4.0, attention="naive",
    )


@pytest.fixture(scope="module")
def mesh42m():
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("dp", "tp"))


def test_moe_flagship_forward_matches_single_device(moe_cfg, mesh42m):
    """ep=dp=4 sharded forward (experts sharded, tokens dispatched over
    the all-to-all) == the all-experts-local single-device forward."""
    params = init_params(jax.random.PRNGKey(20), moe_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(21), (4, 16), 0, moe_cfg.vocab
    )
    expected = forward(params, tokens, moe_cfg)
    fwd, shard = make_sharded_forward(moe_cfg, mesh42m)
    np.testing.assert_allclose(
        np.asarray(fwd(shard(params), tokens)), np.asarray(expected),
        rtol=2e-4, atol=2e-5,
    )


def test_moe_flagship_train_matches_single_device(moe_cfg, mesh42m):
    """One sharded MoE train step == the single-device step — loss AND
    params, expert grads riding the backward all-to-all.  Router aux
    weights are zeroed: the load-balance term is computed over each
    rank's LOCAL tokens (mean of products != product of means), the
    documented approximation under dp."""
    import dataclasses

    from accl_tpu.models.transformer import loss_fn as lf

    c = dataclasses.replace(
        moe_cfg, moe_aux_weight=0.0, moe_router_z_weight=0.0
    )
    params = init_params(jax.random.PRNGKey(22), c)
    tokens = jax.random.randint(jax.random.PRNGKey(23), (8, 16), 0, c.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    lr = 0.05
    loss0, grads = jax.value_and_grad(lf)(params, tokens, targets, c)
    expected = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    step, shard = make_sharded_train_step(c, mesh42m, lr=lr)
    new_params, loss = step(shard(params), tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_moe_aux_terms_in_loss(moe_cfg):
    """loss_fn adds the router health penalty: positive, finite, and
    equal to the configured weighting of the layer-averaged aux terms."""
    import dataclasses

    from accl_tpu.models.transformer import loss_fn as lf

    params = init_params(jax.random.PRNGKey(24), moe_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(25), (4, 16), 0, moe_cfg.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)
    bare = dataclasses.replace(
        moe_cfg, moe_aux_weight=0.0, moe_router_z_weight=0.0
    )
    l0 = float(lf(params, tokens, targets, bare))
    l1 = float(lf(params, tokens, targets, moe_cfg))
    assert np.isfinite(l1) and l1 > l0  # the penalty is positive


def test_moe_generate_matches_naive_greedy(moe_cfg):
    """KV-cache decode through the MoE blocks == re-running the full
    forward every step (greedy)."""
    from accl_tpu.models import generate

    params = init_params(jax.random.PRNGKey(26), moe_cfg)
    prompt = jax.random.randint(
        jax.random.PRNGKey(27), (2, 5), 0, moe_cfg.vocab
    )
    got = np.asarray(generate(params, prompt, 6, moe_cfg))
    np.testing.assert_array_equal(
        got, _naive_greedy(params, prompt, 6, moe_cfg)
    )


def test_moe_rejections(moe_cfg, mesh42m):
    import dataclasses

    from accl_tpu.models import encoder_forward, make_pp_train_step

    params = init_params(jax.random.PRNGKey(0), moe_cfg)
    with pytest.raises(ValueError, match="decoder flagship only"):
        encoder_forward(params, jnp.zeros((1, 8), jnp.int32), moe_cfg)
    with pytest.raises(ValueError, match="does not compose"):
        make_sharded_train_step(
            dataclasses.replace(moe_cfg, seq_parallel=True), mesh42m
        )
    with pytest.raises(ValueError, match="cannot be 'tp'"):
        make_sharded_train_step(
            dataclasses.replace(moe_cfg, moe_mesh_axis="tp"), mesh42m
        )
    with pytest.raises(ValueError, match="not an axis"):
        make_sharded_train_step(
            dataclasses.replace(moe_cfg, moe_mesh_axis="ep"), mesh42m
        )


def test_moe_composes_with_vocab_parallel(moe_cfg, mesh42m):
    """MoE (experts on dp) + vocab parallelism (embedding/loss on tp)
    use different axes and compose: identical loss and params to the
    replicated-head MoE step."""
    import dataclasses

    vp = dataclasses.replace(moe_cfg, vocab_parallel=True)
    params = init_params(jax.random.PRNGKey(28), moe_cfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(29), (8, 16), 0, moe_cfg.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)
    s1, sh1 = make_sharded_train_step(moe_cfg, mesh42m, lr=0.05)
    p1, l1 = s1(sh1(params), tokens, targets)
    s2, sh2 = make_sharded_train_step(vp, mesh42m, lr=0.05)
    p2, l2 = s2(sh2(params), tokens, targets)
    assert float(l2) == pytest.approx(float(l1), rel=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_moe_composes_with_context_parallelism(moe_cfg, mesh24_moecp):
    """Long-context MoE: experts dispatch over the dp all-to-all while
    the K/V ring turns over tp — one train step equals the single-device
    MoE step (aux weights zeroed: the load-balance term is a per-rank-
    tokens approximation, and cp ranks see different token subsets)."""
    import dataclasses

    from accl_tpu.models.transformer import loss_fn as lf

    c = dataclasses.replace(
        moe_cfg, context_parallel=True,
        moe_aux_weight=0.0, moe_router_z_weight=0.0,
        # capacity = E: cap == local entry count, so no token can drop —
        # cp ranks route tiny T/cp shards where the module-default
        # capacity would drop entries the dense reference keeps
        moe_capacity_factor=8.0,
    )
    ref = dataclasses.replace(c, context_parallel=False)
    params = init_params(jax.random.PRNGKey(30), c)
    tokens = jax.random.randint(jax.random.PRNGKey(31), (4, 16), 0, c.vocab)
    targets = jnp.roll(tokens, -1, axis=1)

    lr = 0.05
    loss0, grads = jax.value_and_grad(lf)(params, tokens, targets, ref)
    expected = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    step, shard = make_sharded_train_step(c, mesh24_moecp, lr=lr)
    new_params, loss = step(shard(params), tokens, targets)
    # ring-mean + a2a reorder the f32 accumulation: ~2e-5 relative
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


@pytest.fixture(scope="module")
def mesh24_moecp():
    # dp=2 (expert axis under the welded layout) x tp=4 (the cp ring)
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    return Mesh(devs, ("dp", "tp"))


def test_moe_cp_aux_terms_flow(moe_cfg, mesh24_moecp):
    """Under MoE x cp the router health penalty still reaches the loss
    (positive delta vs zeroed weights) and stays finite."""
    import dataclasses

    c = dataclasses.replace(moe_cfg, context_parallel=True)
    bare = dataclasses.replace(
        c, moe_aux_weight=0.0, moe_router_z_weight=0.0
    )
    params = init_params(jax.random.PRNGKey(32), c)
    tokens = jax.random.randint(jax.random.PRNGKey(33), (4, 16), 0, c.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    s1, sh1 = make_sharded_train_step(bare, mesh24_moecp, lr=0.0)
    _, l0 = s1(sh1(params), tokens, targets)
    s2, sh2 = make_sharded_train_step(c, mesh24_moecp, lr=0.0)
    _, l1 = s2(sh2(params), tokens, targets)
    assert np.isfinite(float(l1)) and float(l1) > float(l0)


def test_moe_expert_axis_unwelded_from_dp(moe_cfg):
    """Experts on a DEDICATED ep mesh axis (dp x ep x tp): the batch
    shards over dp x ep, dense grads psum over both, the expert bank
    shards over ep only — one step equals the single-device step."""
    import dataclasses

    from accl_tpu.models.transformer import loss_fn as lf, param_specs

    c = dataclasses.replace(
        moe_cfg, moe_mesh_axis="ep",
        moe_aux_weight=0.0, moe_router_z_weight=0.0,
    )
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("dp", "ep", "tp"))
    # the expert bank must shard over ep, not dp
    sp = param_specs(c)["layers"][0]["moe"]["w1"]
    assert sp[0] == "ep"

    params = init_params(jax.random.PRNGKey(34), c)
    tokens = jax.random.randint(jax.random.PRNGKey(35), (8, 16), 0, c.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    lr = 0.05
    loss0, grads = jax.value_and_grad(lf)(params, tokens, targets,
                                          dataclasses.replace(c))
    expected = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    step, shard = make_sharded_train_step(c, mesh, lr=lr)
    new_params, loss = step(shard(params), tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(expected), jax.tree.leaves(new_params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_moe_ep_axis_zero_step_matches_welded(moe_cfg):
    """The ZeRO-Adam step on a (dp, ep, tp) mesh with experts on ep
    computes the same update as the welded experts-on-dp layout on a
    (dp, tp) mesh — same global batch, same math, different placement.
    Preserves the ZeRO state story: moments shard over dp in both."""
    import dataclasses

    from accl_tpu.parallel.zero import AdamConfig, make_zero_train_step

    base = dataclasses.replace(
        moe_cfg, moe_aux_weight=0.0, moe_router_z_weight=0.0
    )
    unwelded = dataclasses.replace(base, moe_mesh_axis="ep")
    mesh_w = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    mesh_u = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                  ("dp", "ep", "tp"))
    params = init_params(jax.random.PRNGKey(36), base)
    tokens = jax.random.randint(jax.random.PRNGKey(37), (8, 16), 0,
                                base.vocab)
    targets = jnp.roll(tokens, -1, axis=1)
    # eps large enough that first-step Adam doesn't amplify reduction-
    # order noise (sign(g)*lr at tiny eps)
    adam = AdamConfig(lr=0.01, eps=1e-3)

    s_w, sh_w, init_w = make_zero_train_step(base, mesh_w, adam)
    p_w, st_w, l_w = s_w(
        sh_w(params), init_w(sh_w(params)), tokens, targets
    )
    s_u, sh_u, init_u = make_zero_train_step(unwelded, mesh_u, adam)
    p_u, st_u, l_u = s_u(
        sh_u(params), init_u(sh_u(params)), tokens, targets
    )
    np.testing.assert_allclose(float(l_u), float(l_w), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_w), jax.tree.leaves(p_u)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-5
        )


def test_trainer_context_parallelism(tmp_path):
    """The trainer's parallelism='context' mode trains and resumes (cp
    params are replicated over tp — same checkpoint tree as dp_tp)."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=4, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="context",
    )
    assert done == 4 and np.isfinite(loss)
    done, loss = train(
        steps=6, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="context",
    )
    assert done == 6 and np.isfinite(loss)


def test_trainer_moe(tmp_path):
    """--n-experts switches the trainer's blocks to the expert-parallel
    MoE FFN; the ZeRO optimizer state (expert-shard moments) checkpoints
    and resumes."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=4, ckpt_dir=ckpt, save_every=2, log_every=0,
        optimizer="zero_adam", n_experts=8,
    )
    assert done == 4 and np.isfinite(loss)
    done, loss = train(
        steps=6, ckpt_dir=ckpt, save_every=2, log_every=0,
        optimizer="zero_adam", n_experts=8,
    )
    assert done == 6 and np.isfinite(loss)


def test_trainer_moe_dedicated_ep_axis(tmp_path):
    """--ep 2 un-welds experts onto the dedicated axis of a (dp, ep, tp)
    mesh; ZeRO state checkpoints and resumes (moments stay dp-sharded)."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=3, ckpt_dir=ckpt, save_every=2, log_every=0,
        optimizer="zero_adam", n_experts=8, ep=2,
    )
    assert done == 3 and np.isfinite(loss)
    done, loss = train(
        steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
        optimizer="zero_adam", n_experts=8, ep=2,
    )
    assert done == 5 and np.isfinite(loss)
    with pytest.raises(ValueError, match="requires --n-experts"):
        train(steps=1, log_every=0, ep=2)


def test_trainer_ep_exceeding_devices_named_error():
    """--ep larger than the host's devices fails with an error naming
    --ep, not an opaque numpy reshape error out of Mesh construction."""
    from accl_tpu.examples.train import train

    with pytest.raises(ValueError, match="--ep 16 needs"):
        train(steps=1, log_every=0, n_experts=16, ep=16)


def test_dense_config_ignores_ep_axis_unless_opted_in():
    """A caller-built mesh whose axis happens to be named 'ep' must not
    silently shard a dense config's batch (and psum its grads) over it;
    cfg.ep_extends_dp is the explicit opt-in for the one-mesh-serves-
    both-model-kinds layout."""
    import dataclasses

    from accl_tpu.models.transformer import _data_axes

    cfg = TransformerConfig(d_model=32, n_heads=4, d_ff=64, max_seq=16)
    mesh = Mesh(
        np.array(jax.devices()[:8]).reshape(2, 2, 2), ("dp", "ep", "tp")
    )
    assert _data_axes(cfg, mesh) == ("dp",)
    opted = dataclasses.replace(cfg, ep_extends_dp=True)
    assert _data_axes(opted, mesh) == ("dp", "ep")
    # the opted-in dense step still computes the single-device math
    params = init_params(jax.random.PRNGKey(40), opted)
    tokens = jax.random.randint(
        jax.random.PRNGKey(41), (8, 16), 0, opted.vocab
    )
    targets = jnp.roll(tokens, -1, axis=1)
    from accl_tpu.models.transformer import loss_fn as lf

    loss0 = lf(params, tokens, targets, opted)
    step, shard = make_sharded_train_step(opted, mesh, lr=0.0)
    _, loss = step(shard(params), tokens, targets)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-5)


def test_trainer_interleaved_pipeline(tmp_path):
    """--v-stages 2 trains the composed pipeline with interleaved
    virtual stages and resumes from the permuted-stack checkpoint."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=3, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", v_stages=2,
    )
    assert done == 3 and np.isfinite(loss)
    done, loss = train(
        steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", v_stages=2,
    )
    assert done == 5 and np.isfinite(loss)
    with pytest.raises(ValueError, match="requires parallelism"):
        train(steps=1, log_every=0, v_stages=2)


def test_trainer_pipeline_1f1b(tmp_path):
    """--pp-schedule 1f1b trains the composed pipeline with the
    hand-scheduled backward and resumes."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=3, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", pp_schedule="1f1b",
    )
    assert done == 3 and np.isfinite(loss)
    done, loss = train(
        steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", pp_schedule="1f1b",
    )
    assert done == 5 and np.isfinite(loss)
    with pytest.raises(ValueError, match="requires parallelism"):
        train(steps=1, log_every=0, pp_schedule="1f1b")


def test_trainer_moe_with_context_parallelism(tmp_path):
    """Long-context MoE end-to-end in the trainer: --n-experts with
    --parallelism context (expert a2a on dp, K/V ring on tp)."""
    from accl_tpu.examples.train import train

    done, loss = train(
        steps=3, log_every=0, parallelism="context", n_experts=8,
    )
    assert done == 3 and np.isfinite(loss)


def test_trainer_pipeline_zero_adam(tmp_path):
    """optimizer='zero_adam' now composes with parallelism='pipeline':
    the ZeRO state (moments sharded inside the stage layout) checkpoints
    and resumes alongside the stacked params."""
    from accl_tpu.examples.train import train

    ckpt = str(tmp_path / "ckpt")
    done, loss = train(
        steps=3, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", optimizer="zero_adam",
        clip_grad_norm=1.0,
    )
    assert done == 3 and np.isfinite(loss)
    done, loss = train(
        steps=5, ckpt_dir=ckpt, save_every=2, log_every=0,
        parallelism="pipeline", optimizer="zero_adam",
        clip_grad_norm=1.0,
    )
    assert done == 5 and np.isfinite(loss)


def test_auto_attention_f16_never_selects_flash(monkeypatch):
    """Regression (ADVICE r5 medium): Mosaic rejects f16 matmul operands
    (a ValueError at kernel compile), so the ``attention='auto'``
    resolver must gate the flash branch on dtype — an f16 activation at flash-eligible T
    (1024 <= T < 4096) falls through to the XLA blockwise fold instead.
    bf16 keeps selecting the kernel (the VMEM gate alone decides)."""
    from accl_tpu.models.transformer import (
        _attention,
        _auto_flash_fits,
    )
    from accl_tpu.ops import attention as xla_attention

    # the dtype gate itself, at both ends of the flash-eligible window
    for T in (1024, 4095):
        q16 = jnp.zeros((1, 1, T, 64), jnp.float16)
        assert not _auto_flash_fits(q16)
        qbf = jnp.zeros((1, 1, T, 64), jnp.bfloat16)
        assert _auto_flash_fits(qbf)

    # end-to-end on a (pretend-)TPU backend: auto routes f16 through the
    # blockwise fold, never into the flash kernel
    calls = {}
    real_blockwise = xla_attention.blockwise_attention

    def spy(q, k, v, causal=True):
        calls["blockwise"] = True
        return real_blockwise(q, k, v, causal=causal)

    monkeypatch.setattr(xla_attention, "blockwise_attention", spy)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 2, 1024, 16)), jnp.float16)
    out = _attention(q, q, q, impl="auto")
    assert calls.get("blockwise"), "f16 auto must resolve to blockwise"
    assert out.shape == q.shape and out.dtype == jnp.float16
    # numeric sanity against the naive reference in f32
    expect = _attention(
        q.astype(jnp.float32), q.astype(jnp.float32),
        q.astype(jnp.float32), impl="naive",
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect), rtol=2e-2,
        atol=2e-2,
    )
