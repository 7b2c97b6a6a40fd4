"""The DeepSeek-V2 block of ``accl_tpu.models`` (a latent mixer: q and k
heads of two parts beside narrower v heads, one shared rope key head, YaRN
frequencies; group-limited top-k with shared experts, a held routing
group, the three balance losses) against the plain float32 reference of
``perfbench/reference/deepseek_v2.py``, at small sizes on the CPU mesh
with seeded weights.  Float32 against float32 is held to 1e-4 of the
largest value."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    LatentAttention,
    LayerKind,
    TransformerConfig,
    YarnScaling,
    encoder_forward,
    generate,
    init_moe_params,
    init_params,
    make_pp_train_step,
    make_sharded_forward,
    make_sharded_generate,
    make_sharded_router_probe,
    make_sharded_train_step,
    moe_ffn,
)
from accl_tpu.models.transformer import (
    _auto_flash_fits,
    loss_fn,
    param_specs,
    yarn_inv_freq,
)
from accl_tpu.ops.pallas.attention import _flash_bwd_vmem_bytes
from perfbench.drivers import train_steps_deepseek_v2 as driver
from perfbench.reference import deepseek_v2 as reference

T = 48
YARN = dict(factor=40.0, original_max_position_embeddings=16, beta_fast=32.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
ALPHAS = (0.003, 0.05, 0.02)
ULP = 5e-7
#: heads of 32 + 16 beside v heads of 24; a dense layer first; 16 experts in
#: 4 groups of 4, 2 groups kept, top 3, the second group held
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_layers=3,
    layers=(LayerKind(ffn="dense", d_ff=96), LayerKind(ffn="moe", d_ff=32),
            LayerKind(ffn="moe", d_ff=32)),
    d_ff=32, max_seq=64, pos_embedding="rope",
    rope_yarn=YarnScaling(40.0, 16, 32.0, 1.0, 0.707, 0.707),
    norm="rmsnorm", norm_eps=1e-6, ffn="swiglu", tie_head=False,
    latent=LatentAttention(q_rank=24, kv_rank=16, nope_dim=32, rope_dim=16,
                           v_dim=24),
    n_experts=4, moe_top_k=3, moe_capacity_factor=None,
    moe_norm_topk_prob=False, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_route_scale=16.0, moe_n_group=4, moe_topk_group=2,
    moe_balance_weights=ALPHAS, moe_shared_d_ff=64, moe_router_experts=16,
    moe_first_expert=4, moe_held_row_factor=8.0, attention="naive",
)
REF = dict(
    n_head=4, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=24,
    kv_lora_rank=16, rope_theta=10000.0, rope_scaling=YARN, top_k=3,
    n_group=4, topk_group=2, routed_scaling_factor=16.0, first_expert=4,
    q_block=16,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales that are not all one, so that a
    missing scale shows, and larger than the init's, so that routing is
    decided and the mixer's parts matter."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(
        lambda p: p * 3.0 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype
        ) if p.ndim == 1 else p * 3.0,
        params,
    )


def _batch(B=2, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, CFG.vocab)
    return tok, jnp.roll(tok, -1, axis=-1)


def _close(got, want, tol=1e-4, atol=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= (
        tol * max(np.abs(want).max(), 1e-6) + atol
    )


def _mesh(tp):
    return Mesh(np.array(jax.devices()[:tp]).reshape(1, tp), ("dp", "tp"))


# -- YaRN ---------------------------------------------------------------------


def test_yarn_inv_freq_at_the_published_keys():
    yarn = YarnScaling(40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    got = yarn_inv_freq(64, 10000.0, yarn)
    extra = 10000.0 ** (-2.0 * np.arange(32) / 64)
    # corr(32) = 10.78 -> low 10; corr(1) = 22.83 -> high 23
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    _close(got, extra / 40 * ramp + extra * (1 - ramp), 1e-6)
    # pairs up to 10 keep their frequency, from 23 on are divided by 40
    assert got[0] == 1.0
    np.testing.assert_allclose(got[10], 10000.0 ** (-20 / 64), rtol=1e-6)
    np.testing.assert_allclose(got[16], 0.01 * (7 / 13 + 6 / 13 / 40), rtol=1e-5)
    np.testing.assert_allclose(got[23], 10000.0 ** (-46 / 64) / 40, rtol=1e-6)
    np.testing.assert_allclose(got[31], 10000.0 ** (-62 / 64) / 40, rtol=1e-6)
    _close(got, reference.yarn_inv_freq(64, 10000.0, 40.0, 32.0, 1.0, 4096), 1e-6)
    published = dataclasses.replace(
        CFG, rope_yarn=yarn,
        latent=LatentAttention(1536, 512, 128, 64, 128),
    )
    # 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2
    assert published.attn_scale() == pytest.approx(0.11472, abs=2e-5)
    assert published.rope_table_scale() == 1.0
    assert published.head_size() == 192 and published.rope_width() == 64


def test_auto_resolves_to_flash_at_the_cell_shapes():
    q = jax.ShapeDtypeStruct((1, 128, 4096, 192), jnp.bfloat16)
    assert _auto_flash_fits(q)
    # the backward's residents at 128 + 64 | 128, T = 4096 (23 MiB) and
    # at one width, where the sum is what it always was
    assert _flash_bwd_vmem_bytes(4096, 128, 512, 2, 128, 128) == 24_117_248
    assert _flash_bwd_vmem_bytes(8192, 128, 512, 2) == (
        2 * (3 * 8192 * 128 * 2 + 4 * 512 * 128 * 2 + 2 * 16 * 8 * 512 * 4)
        + 8192 * 128 * 4 + 7 * 512 * 512 * 4
    )


# -- the whole model ------------------------------------------------------------


@functools.cache
def _seeded_reference_logits():
    """The reference's logits of ``_params()`` on ``_batch()``, ONE compiled
    function run once for the three lowerings' cases (an eager walk compiles
    every operation by itself: ROADMAP D14)."""

    @jax.jit
    def logits(weights, tok):
        h, _, _ = reference.hidden(weights, tok, **REF)
        return reference.head(weights, h)

    with jax.default_matmul_precision("highest"):
        params, (tok, _) = _params(), _batch()
        return logits(driver.reference_weights(params), tok)


@functools.cache
def _seeded_reference_grads():
    """The reference's loss and gradients of ``_params()`` on ``_batch()``,
    ONE compiled function run once for the cases that compare with it (an
    eager walk compiles every operation by itself: ROADMAP D14)."""
    with jax.default_matmul_precision("highest"):
        params, (tok, tgt) = _params(), _batch()
        return _reference_grads(params, tok, tgt)


def _reference_grads(params, tok, tgt):
    weights = driver.reference_weights(params)
    return jax.jit(jax.value_and_grad(
        lambda w: reference.loss(w, tok, tgt, alphas=ALPHAS, **REF)
    ))(weights)


@pytest.mark.parametrize("attention", ["naive", "blockwise", "flash"])
def test_logits_against_the_reference(attention):
    cfg = dataclasses.replace(CFG, attention=attention)
    params, (tok, _) = _params(), _batch()
    fwd, shard = make_sharded_forward(cfg, _mesh(1))
    got = fwd(shard(params), tok)
    _close(got, _seeded_reference_logits())


@pytest.mark.parametrize("tp", [1, 2])
def test_loss_and_gradients_against_the_reference(tp):
    """Through ``make_sharded_train_step`` itself: at lr 1 the step's
    update IS the gradient (to the float32 spacing of a weight of about
    3, which ``ULP`` allows for)."""
    params, (tok, tgt) = _params(), _batch()
    step, shard = make_sharded_train_step(CFG, _mesh(tp), lr=1.0)
    new, loss = step(shard(params), tok, tgt)
    want_loss, want = _seeded_reference_grads()
    _close(loss, want_loss, 1e-5)
    got = driver.reference_weights(
        jax.tree.map(lambda p, n: p - n, params, jax.device_get(new))
    )
    for name in ("embed_tokens", "norm", "lm_head"):
        _close(got[name], want[name], 2e-4, ULP)
    for got_l, want_l in zip(got["layers"], want["layers"]):
        assert set(got_l) == set(want_l)
        for name in want_l:
            _close(got_l[name], want_l[name], 2e-4, ULP)


@pytest.mark.parametrize("how,where", [
    (dict(attn_how=dict(scale_without_mscale=True)), "softmax scale"),
    (dict(moe_how=dict(renormalise=True)), "renormalised weights"),
])
def test_a_broken_reference_is_told_apart(how, where):
    params, (tok, _) = _params(), _batch()
    fwd, shard = make_sharded_forward(CFG, _mesh(1))
    got = np.asarray(fwd(shard(params), tok))
    weights = driver.reference_weights(params)
    h = reference.embed(weights, tok)
    for lp in weights["layers"]:
        h, _, _ = reference.layer(h, lp, **REF, **how)
    broken = np.asarray(reference.head(weights, h))
    assert np.abs(got - broken).max() > 1e-2 * np.abs(broken).max(), where


def test_the_rope_key_is_one_head_and_the_tree_is_the_latent_mixers():
    specs = param_specs(CFG)["layers"][1]
    shapes = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.PRNGKey(0))
    layer = shapes["layers"][1]
    assert set(layer) == set(specs) == {
        "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo",
        "ln1", "ln2", "moe",
    }
    assert layer["wkv_a"].shape == (64, 16 + 16)      # latent | ONE rope key
    assert layer["wq_b"].shape == (24, 4 * 48)
    assert layer["wkv_b"].shape == (16, 4 * (32 + 24))
    assert layer["wo"].shape == (4 * 24, 64)


# -- routing --------------------------------------------------------------------


def _bank(held=16, first=0, shared=True, seed=3):
    """A bank of ``held`` of 16 experts, cut from ONE seeded whole."""
    whole = init_moe_params(
        jax.random.PRNGKey(seed), 64, 32, 16, gated=True, shared_d_ff=64,
    )
    whole["gate"] = whole["gate"] * 8.0     # decided routing
    bank = {k: whole[k][first:first + held] for k in ("w1", "w2", "w3")}
    bank["gate"] = whole["gate"]
    if shared:
        bank["shared"] = whole["shared"]
    return bank


def _as_reference(bank):
    lp = {"gate": bank["gate"], "experts.gate_proj": bank["w1"],
          "experts.up_proj": bank["w3"], "experts.down_proj": bank["w2"]}
    if "shared" in bank:
        lp.update({"shared_experts.gate_proj": bank["shared"]["w1"],
                   "shared_experts.up_proj": bank["shared"]["w3"],
                   "shared_experts.down_proj": bank["shared"]["w2"]})
    return lp


ROUTE = dict(capacity_factor=None, k=3, renormalize=False, route_scale=16.0,
             n_group=4, topk_group=2)
REF_ROUTE = dict(seqs=2, top_k=3, n_group=4, topk_group=2,
                 routed_scaling_factor=16.0)


def _x(seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 64))


def test_grouped_top_k_against_the_reference():
    bank, x = _bank(), _x()
    got, aux = moe_ffn(x, bank, return_aux=True, **ROUTE)
    want, logits, _ = reference.moe(
        x.reshape(-1, 64), _as_reference(bank), **REF_ROUTE
    )
    _close(got.reshape(-1, 64), want)
    counts, hits, _ = reference.routing_facts(logits, 3, 4, 2)
    assert np.array_equal(aux["expert_tokens"], counts)
    assert np.array_equal(aux["group_tokens"], hits)
    assert int(hits.sum()) == 2 * 2 * T          # two groups a token
    # the limit binds: plain top-3 picks other experts for some tokens
    plain = moe_ffn(x, bank, **{**ROUTE, "n_group": 1, "topk_group": 1})
    assert np.abs(np.asarray(plain - got)).max() > 1e-3


def test_one_group_is_todays_routing_bit_for_bit():
    bank, x = _bank(), _x()
    today = moe_ffn(x, bank, capacity_factor=None, k=3, renormalize=False)
    one = moe_ffn(x, bank, capacity_factor=None, k=3, renormalize=False,
                  n_group=1, topk_group=1, route_scale=1.0)
    assert np.array_equal(np.asarray(today), np.asarray(one))
    # every group kept is no limit either
    all_kept = moe_ffn(x, bank, capacity_factor=None, k=3, renormalize=False,
                       n_group=4, topk_group=4)
    assert np.array_equal(np.asarray(today), np.asarray(all_kept))


def test_the_shares_add_up_to_the_uncut_layer():
    """The four groups' held parts, the shared experts counted once, sum
    to what the uncut reference gives for the whole layer."""
    x = _x()
    total = 0.0
    for g in range(4):
        bank = _bank(held=4, first=4 * g, shared=g == 0)
        y, aux = moe_ffn(x, bank, return_aux=True, first_expert=4 * g,
                         held_row_factor=8.0, **ROUTE)
        assert int(aux["dropped"]) == 0
        total = total + y
    want, _, _ = reference.moe(
        x.reshape(-1, 64), _as_reference(_bank()), **REF_ROUTE
    )
    _close(total.reshape(-1, 64), want)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_balance_losses_against_the_reference(held, first):
    bank, x = _bank(held, first), _x()
    _, aux = moe_ffn(x, bank, return_aux=True, first_expert=first,
                     held_row_factor=8.0, balance_groups=4, **ROUTE)
    _, _, want = reference.moe(
        x.reshape(-1, 64), _as_reference(bank), first_expert=first, **REF_ROUTE
    )
    names = ("balance_expert", "balance_device", "balance_comm")
    for name, w in zip(names, want):
        _close(aux[name], w, 1e-5)
    # balanced routing reads 1, 1 and n_group / topk_group x the share of
    # tokens a group gets: 1; this seeded router is off it
    assert all(float(aux[n]) > 0.9 for n in names)

    def penalty(gate):
        _, a = moe_ffn(x, {**bank, "gate": gate}, return_aux=True,
                       first_expert=first, held_row_factor=8.0,
                       balance_groups=4, **ROUTE)
        return sum(w * a[n] for w, n in zip(ALPHAS, names))

    def ref_penalty(gate):
        _, _, b = reference.moe(
            x.reshape(-1, 64), {**_as_reference(bank), "gate": gate},
            first_expert=first, **REF_ROUTE,
        )
        return reference.weighted(b, ALPHAS)

    _close(jax.grad(penalty)(bank["gate"]), jax.grad(ref_penalty)(bank["gate"]),
           1e-4)


@pytest.mark.parametrize("router", ["softmax", "sigmoid"])
def test_zero_balance_weights_leave_a_loss_as_it_was(router):
    """OLMoE's and Trinity's kind of config: no balance term is computed
    and the loss is the one the fields before this PR describe."""
    cfg = TransformerConfig(
        vocab=256, d_model=64, n_heads=4, n_layers=2, d_ff=32, max_seq=64,
        pos_embedding="rope", norm="rmsnorm", ffn="swiglu", tie_head=False,
        n_experts=8, moe_top_k=2, moe_capacity_factor=None,
        moe_router=router, attention="naive",
        **({} if router == "softmax" else dict(
            moe_aux_weight=0.0, moe_router_z_weight=0.0, moe_shared_d_ff=32)),
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = _batch()
    loss, aux = loss_fn(params, tok, tgt, cfg, with_aux=True)
    assert not any(k.startswith("balance_") for k in aux)
    assert "group_tokens" not in aux
    explicit = dataclasses.replace(cfg, moe_balance_weights=(0.0, 0.0, 0.0),
                                   moe_n_group=1, moe_topk_group=1)
    assert float(loss_fn(params, tok, tgt, explicit)) == float(loss)
    weighed = dataclasses.replace(cfg, moe_balance_weights=ALPHAS)
    if router == "softmax":
        assert float(loss_fn(params, tok, tgt, weighed)) > float(loss)


def test_router_probe_counts_against_the_reference():
    params, (tok, _) = _params(), _batch()
    probe = make_sharded_router_probe(CFG, _mesh(1))
    _, shard = make_sharded_forward(CFG, _mesh(1))
    got = probe(shard(params), tok)
    weights = driver.reference_weights(params)
    _, logits, _ = reference.hidden(weights, tok, **REF)
    facts = [reference.routing_facts(l, 3, 4, 2) for l in logits]
    counts = np.stack([np.asarray(f[0]) for f in facts])
    assert np.array_equal(got["expert_tokens"], counts)
    assert np.array_equal(got["group_tokens"], np.stack([f[1] for f in facts]))
    assert np.array_equal(got["held_entries"], counts[:, 4:8].sum(axis=1))
    assert int(np.asarray(got["dropped"]).sum()) == 0


def test_grouped_top_k_is_refused_where_it_cannot_hold():
    with pytest.raises(ValueError, match="grouped top-k"):
        dataclasses.replace(CFG, moe_n_group=3)          # 16 % 3
    with pytest.raises(ValueError, match="grouped top-k"):
        dataclasses.replace(CFG, moe_n_group=8, moe_topk_group=1)  # 2 < top 3
    with pytest.raises(ValueError, match="dropless"):
        dataclasses.replace(CFG, moe_capacity_factor=1.5)
    with pytest.raises(ValueError, match="latent mixer"):
        dataclasses.replace(CFG, n_kv_heads=2)


# -- the refusals, by name --------------------------------------------------------


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_latent_mixer_refuse_it_by_name(path):
    dense = dataclasses.replace(
        CFG, layers=None, n_experts=0, moe_router_experts=None,
        moe_first_expert=0, moe_shared_d_ff=0, moe_route_scale=1.0,
        moe_n_group=1, moe_topk_group=1, moe_balance_weights=(0.0, 0.0, 0.0),
        moe_capacity_factor=1.5, n_layers=2,
    )
    params = init_params(jax.random.PRNGKey(0), dense)
    tok, _ = _batch()
    with pytest.raises(ValueError, match="latent mixer"):
        if path == "generate":
            generate(params, tok, 2, dense)
        elif path == "make_sharded_generate":
            make_sharded_generate(dense, _mesh(1), 2)
        elif path == "encoder":
            encoder_forward(params, tok, dense)
        elif path == "pipeline":
            mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                        ("pp", "dp", "tp"))
            make_pp_train_step(dense, mesh, num_microbatches=2)
        else:
            param_specs(dataclasses.replace(dense, **{path: True}))
