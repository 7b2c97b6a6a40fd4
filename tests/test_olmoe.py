"""The OLMoE block of ``accl_tpu.models`` (RMSNorm, QK-norm, RoPE,
dropless top-k gated-SiLU experts, untied head, both router losses)
against the plain float32 reference of ``perfbench/reference/olmoe.py``,
at small sizes on the CPU mesh with seeded weights.

Float32 against float32 is held to 1e-4 of the largest value: nothing
rounds differently enough to flip a router near-tie.  The bf16 program
is held to the limits the benchmark's driver writes
(``perfbench/drivers/train_steps_olmoe.py``), and every way of breaking
the reference lands outside them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    TransformerConfig,
    forward,
    generate,
    init_params,
    make_sharded_forward,
    make_sharded_router_probe,
    make_sharded_train_step,
    moe_ffn,
)
from accl_tpu.models.transformer import loss_fn
from perfbench.drivers import train_steps_olmoe as driver
from perfbench.reference import olmoe

CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=32, max_seq=64,
    pos_embedding="rope", norm="rmsnorm", ffn="swiglu", qk_norm=True,
    tie_head=False, n_experts=32, moe_top_k=8, moe_capacity_factor=None,
    moe_norm_topk_prob=False, attention="naive",
)
REF = dict(n_head=CFG.n_heads, top_k=CFG.moe_top_k, q_block=16)
T = 48


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales that are not all one, so that a
    missing norm scale shows."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype
        ) if p.ndim == 1 else p,
        params,
    )


def _batch(batch=1, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, T), 0, CFG.vocab)
    return tok, jnp.roll(tok, -1, axis=-1)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        params = _params()
        tok, tgt = _batch(2)
        # each side ONE compiled function: an eager walk compiles every
        # operation by itself (ROADMAP D14)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, tok, tgt, CFG)
        ))(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda w: olmoe.loss(w, tok, tgt, **REF)
        ))(driver.reference_weights(params))
        return dict(
            params=params, tok=tok, tgt=tgt, loss=loss, want_loss=want_loss,
            grads=driver.reference_weights(grads), want_grads=want_grads,
        )


def test_f32_logits_match_reference(f32):
    got = jax.jit(lambda p, tok: forward(p, tok, CFG))(f32["params"], f32["tok"])
    weights = driver.reference_weights(f32["params"])
    whole = jax.jit(lambda w, tok: olmoe.logits(w, tok, last=T, **REF))
    for b in range(2):
        _close(got[b], whole(weights, f32["tok"][b]))
    # ``last`` and the query block change no value
    _close(jax.jit(lambda w, tok: olmoe.logits(w, tok, last=5, **REF))(
        weights, f32["tok"][0]
    ), got[0, -5:])


def test_f32_loss_matches_reference(f32):
    _close(f32["loss"], f32["want_loss"], 1e-5)


def test_f32_gradient_of_every_parameter_matches_reference(f32):
    flat, _ = jax.tree_util.tree_flatten_with_path(f32["grads"])
    want = jax.tree.leaves(f32["want_grads"])
    assert len(flat) == len(want) == 3 + 12 * CFG.n_layers
    for (path, g), w in zip(flat, want):
        assert np.abs(np.asarray(w)).max() > 0, path
        _close(g, w)


def _check_numbers(got, want):
    err = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    want = np.asarray(want, np.float32)
    return (
        float(np.sqrt((err ** 2).mean() / (want ** 2).mean())),
        float(np.abs(err).max()),
    )


def _inside(rel_rms, max_abs, loss, want_loss):
    return (
        rel_rms <= driver.REL_RMS_LIMIT and max_abs <= driver.MAX_ABS_LIMIT
        and abs(loss - want_loss) / abs(want_loss) <= driver.LOSS_REL_LIMIT
    )


def test_bf16_program_inside_the_written_limits(f32, mesh11):
    """The driver's three checks, its way: tokens an expert against the
    reference's near-ties, logits on the positions without one, the loss.
    Two things differ at this size.  The hidden state's bf16 error (about
    1% here, as there) is 2-3 spacings of a router logit, so a token a
    few spacings clear can still swap an expert; at the published widths
    its error is diluted by a 4,096-token context and the driver's 2
    spacings leave 2.5x of room, here it is a third of all the error, so
    logits are compared on the positions 8 spacings clear.  And the loss
    limit was read over 8,192 tokens; over these 96 an error that
    averages out as 1/sqrt(tokens) is sqrt(8192 / 96) times larger."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), f32["params"])
    tok, tgt = f32["tok"], f32["tgt"]
    weights = driver.reference_weights(params)
    h, router = olmoe.hidden(weights, tok, **REF)
    want = olmoe.head(weights, h)
    facts = [driver.router_facts(r, CFG.moe_top_k) for r in router]
    gaps = np.stack([np.asarray(f[1]) for f in facts])
    near = gaps < driver.NEAR_TIE_SPACINGS
    clean = ~(gaps < 4 * driver.NEAR_TIE_SPACINGS).any(axis=0)
    assert clean.sum() >= driver.MIN_CLEAN_POSITIONS

    got = np.asarray(forward(params, tok, cfg), np.float32).reshape(2 * T, -1)
    rel_rms, max_abs = _check_numbers(
        got[clean], np.asarray(want).reshape(2 * T, -1)[clean]
    )
    assert rel_rms <= driver.REL_RMS_LIMIT and max_abs <= driver.MAX_ABS_LIMIT

    _, shard = make_sharded_forward(cfg, mesh11)
    counters = make_sharded_router_probe(cfg, mesh11)(shard(params), tok)
    counts = np.asarray(counters["expert_tokens"])
    want_counts = np.stack([np.asarray(f[0]) for f in facts])
    moved = np.abs(counts - want_counts).sum(axis=1) // 2
    assert int(np.asarray(counters["dropped"]).sum()) == 0
    assert (moved <= near.sum(axis=1)).all(), (moved, near.sum(axis=1))

    loss = float(loss_fn(params, tok, tgt, cfg))
    want_loss = float(olmoe.loss_from_hidden(weights, h, router, tgt, 8))
    scale = (8192 / (2 * T)) ** 0.5
    assert abs(loss - want_loss) / want_loss <= driver.LOSS_REL_LIMIT * scale


@pytest.mark.parametrize(
    "broken", ["seven_experts", "renormalised", "no_qk_norm", "tied_head",
               "gelu_for_silu"],
)
def test_broken_reference_falls_outside_the_limits(f32, broken, monkeypatch):
    """Each case breaks the reference in ONE way; the float32 program,
    which the whole reference matches to 1e-4, must then miss the limits
    that the bf16 program is held to."""
    weights = driver.reference_weights(f32["params"])
    ref = dict(REF)
    if broken == "seven_experts":
        ref["top_k"] = CFG.moe_top_k - 1
    elif broken == "renormalised":
        ref["norm_topk_prob"] = True
    elif broken == "no_qk_norm":
        monkeypatch.setattr(olmoe, "qk_norm", lambda x, weight: x)
    elif broken == "tied_head":
        weights = dict(weights, lm_head=weights["embed_tokens"].T)
    elif broken == "gelu_for_silu":
        monkeypatch.setattr(olmoe, "silu", jax.nn.gelu)
    tok, tgt = f32["tok"], f32["tgt"]
    want = jnp.stack(
        [olmoe.logits(weights, tok[b], last=T, **ref) for b in range(2)]
    )
    rel_rms, max_abs = _check_numbers(forward(f32["params"], tok, CFG), want)
    want_loss = float(olmoe.loss(weights, tok, tgt, **ref))
    assert not _inside(rel_rms, max_abs, float(f32["loss"]), want_loss), (
        rel_rms, max_abs, float(f32["loss"]), want_loss
    )


@pytest.fixture(scope="module")
def mesh11():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))


def test_one_chip_sharded_step_equals_single_device_step(f32, mesh11):
    params, tok, tgt = f32["params"], f32["tok"], f32["tgt"]
    lr = 0.05
    step, shard = make_sharded_train_step(CFG, mesh11, lr=lr)
    new, loss = step(shard(params), tok, tgt)
    _close(loss, f32["loss"], 1e-6)
    grads = jax.grad(lambda p: loss_fn(p, tok, tgt, CFG))(params)
    want = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    for got, w in zip(jax.tree.leaves(new), jax.tree.leaves(want)):
        _close(got, w, 1e-6)
    fwd, shard = make_sharded_forward(CFG, mesh11)
    _close(fwd(shard(params), tok), forward(params, tok, CFG), 1e-6)


def test_skewed_gate_drops_nothing(mesh11):
    """A gate that sends EVERY token to the same eight experts: a fixed
    capacity of 1.5 would drop five entries in six; dropless keeps all,
    and the result is still the reference's."""
    params = _params(seed=3)
    chosen = np.array([1, 4, 5, 9, 17, 20, 26, 31])
    for lp in params["layers"]:
        gate = np.zeros((CFG.d_model, CFG.n_experts), np.float32)
        gate[:, chosen] = 0.05 + 0.001 * chosen    # ordered, all positive
        lp["moe"]["gate"] = jnp.asarray(gate)
    tok, _ = _batch(2, seed=5)
    # a positive input, so that every token's eight logits are the largest
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, T, CFG.d_model)))
    moe = params["layers"][0]["moe"]
    y, aux = moe_ffn(x, moe, capacity_factor=None, k=8, return_aux=True,
                     renormalize=False)
    counts = np.asarray(aux["expert_tokens"])
    assert int(aux["dropped"]) == 0
    assert counts[chosen].tolist() == [2 * T] * 8 and counts.sum() == 16 * T
    _, capped = moe_ffn(x, moe, capacity_factor=1.5, k=8, return_aux=True,
                        renormalize=False)
    assert int(capped["dropped"]) == 16 * T - 8 * int(1.5 * 2 * T * 8 / 32)
    want, _ = olmoe.moe(
        x.reshape(2 * T, -1),
        driver.reference_weights(params)["layers"][0], 8, False,
    )
    _close(y.reshape(2 * T, -1), want)
    # and through the whole program, by its own probe (there the sign of
    # a token's summed input picks one of two sets of eight)
    probe = make_sharded_router_probe(CFG, mesh11)
    _, shard = make_sharded_forward(CFG, mesh11)
    got = probe(shard(params), tok)
    assert np.asarray(got["dropped"]).tolist() == [0] * CFG.n_layers
    assert np.asarray(got["expert_tokens"]).sum(axis=1).tolist() == (
        [16 * T] * CFG.n_layers
    )


def test_dropless_with_an_expert_axis_of_two_raises():
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "tp"))
    with pytest.raises(NotImplementedError, match=r"R2\(b\)"):
        make_sharded_train_step(CFG, mesh)
    with pytest.raises(NotImplementedError, match=r"R2\(b\)"):
        make_sharded_router_probe(CFG, mesh)
    # a capacity keeps the expert axis, as every multi-chip MoE test has it
    make_sharded_train_step(
        dataclasses.replace(CFG, moe_capacity_factor=2.0), mesh
    )


def test_tp2_heads_sharded_under_qk_norm_match_single_device(f32):
    """QK-norm is over the WHOLE projection: with the heads sharded over
    tp the mean square crosses the axis.  dp stays 1 (dropless)."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    params, tok, tgt = f32["params"], f32["tok"], f32["tgt"]
    fwd, shard = make_sharded_forward(CFG, mesh)
    _close(fwd(shard(params), tok), forward(params, tok, CFG), 1e-5)
    step, shard = make_sharded_train_step(CFG, mesh, lr=0.05)
    _, loss = step(shard(params), tok, tgt)
    _close(loss, f32["loss"], 1e-5)


def test_decode_through_the_cache_matches_the_full_forward(f32):
    params = f32["params"]
    prompt = f32["tok"][:, :12]
    got = generate(params, prompt, 5, CFG)
    cur = prompt
    for _ in range(5):
        nxt = forward(params, cur, CFG)[:, -1].argmax(-1)[:, None]
        cur = jnp.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(cur[:, 12:]))


def test_encoder_and_pipeline_refuse_the_block():
    from accl_tpu.models import encoder_forward

    params = init_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match="default block"):
        encoder_forward(params, _batch()[0], dataclasses.replace(CFG, n_experts=0))
    with pytest.raises(ValueError, match="unknown norm"):
        init_and_specs = dataclasses.replace(CFG, norm="batchnorm")
        make_sharded_forward(
            init_and_specs,
            Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp")),
        )


@pytest.mark.parametrize("seq_parallel", [False, True])
def test_dense_gated_ffn_and_rmsnorm_shard_like_the_default_block(seq_parallel):
    """The kinds are the dense FFN's too: ``w3`` column-split over tp as
    ``w1`` is, under plain tp and under sequence parallelism."""
    cfg = TransformerConfig(
        vocab=128, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=32,
        pos_embedding="rope", norm="rmsnorm", ffn="swiglu", qk_norm=True,
        tie_head=False, attention="naive",
    )
    params = _params(cfg, seed=4)
    assert params["layers"][0]["w3"].shape == (32, 64)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, cfg.vocab)
    # the block by hand, one sequence: silu(x w1) * (x w3) through w2
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 32))
    from accl_tpu.models.transformer import _mlp, _rmsnorm

    h = olmoe.rms_norm(x, lp["ln2"])
    want = x + (jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])) @ lp["w2"]
    _close(_mlp(x, lp, None, norm=_rmsnorm), want, 1e-5)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    sharded = dataclasses.replace(cfg, seq_parallel=seq_parallel)
    fwd, shard = make_sharded_forward(sharded, mesh)
    _close(fwd(shard(params), tok), forward(params, tok, cfg), 1e-5)
    tgt = jnp.roll(tok, -1, axis=-1)
    step, shard = make_sharded_train_step(sharded, mesh, lr=0.05)
    _, loss = step(shard(params), tok, tgt)
    _close(loss, loss_fn(params, tok, tgt, cfg), 1e-5)
