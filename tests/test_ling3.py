"""The Ling-3.0 hybrid block of ``accl_tpu.models`` (KDA linear-attention
layers, a chunked gated delta rule with a decay a channel, beside a latent
layer whose q has no latent and whose heads are gated; a sigmoid router
with group-limited top-k, a group's score the sum of its two best) against
the plain float32 reference of ``perfbench/reference/bailing_hybrid.py``
(KDA as the token-by-token recurrence), at small sizes on the CPU mesh with
seeded weights.  Float32 against float32 is held to 1e-4 of the largest
value."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    DeltaAttention,
    LatentAttention,
    LayerKind,
    TransformerConfig,
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
from accl_tpu.models.mixers.latent import _latent_attn_partial
from accl_tpu.models.transformer import (
    _auto_flash_fits,
    param_specs,
    resolve_attention,
)
from accl_tpu.ops import kda
from accl_tpu.ops.pallas.attention import _flash_bwd_vmem_bytes
from accl_tpu.utils import profiling
from perfbench import manifest, scope_ops
from perfbench.drivers import train_steps_ling3 as driver
from perfbench.reference import bailing_hybrid as reference

T = 80          # a chunk of 64 and a tail of 16
ULP = 5e-7
KDA, MLA = LayerKind(mixer="kda", rope=False), LayerKind(mixer="latent")
#: four heads of 16 (KDA: q, k and v alike; latent: 16 + 8 beside v of 16);
#: a dense KDA layer, then a period of two KDA layers and the latent one,
#: expert layers: 16 experts in 4 groups of 4, 2 groups kept, top 3, the
#: second group held
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_layers=4,
    layers=(
        dataclasses.replace(KDA, ffn="dense", d_ff=96),
        dataclasses.replace(KDA, ffn="moe", d_ff=32),
        dataclasses.replace(KDA, ffn="moe", d_ff=32),
        dataclasses.replace(MLA, ffn="moe", d_ff=32),
    ),
    d_ff=32, max_seq=128, pos_embedding="rope", rope_base=10000.0,
    norm="rmsnorm", norm_eps=1e-6, ffn="swiglu", tie_head=False,
    attn_gate="head",
    latent=LatentAttention(q_rank=None, kv_rank=16, nope_dim=16, rope_dim=8,
                           v_dim=16),
    kda=DeltaAttention(head_dim=16, conv=4, lower_bound=-5.0),
    n_experts=4, moe_top_k=3, moe_capacity_factor=None,
    moe_norm_topk_prob=True, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_router="sigmoid", moe_route_scale=2.5, moe_n_group=4,
    moe_topk_group=2, moe_bias_rate=0.001, moe_shared_d_ff=32,
    moe_router_experts=16, moe_first_expert=4, moe_held_row_factor=8.0,
    attention="naive",
)
REF = dict(
    n_head=4, kda_lower_bound=-5.0, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=16, rope_theta=10000.0, top_k=3, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, first_expert=4, q_block=32,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales, ``dt_bias`` and matrices larger
    than the init's and not all alike, so that a missing scale shows,
    routing is decided and the mixers' parts matter (the taps and the
    scalars a head stay the init's)."""

    def larger(p):
        if p.ndim == 1 and p.shape[0] > cfg.n_heads:
            return p * 3.0 + 0.1 * jax.random.normal(
                jax.random.PRNGKey(p.size), p.shape, p.dtype
            )
        return p * 3.0 if p.ndim == 2 and p.shape[0] > 4 else p

    return jax.tree.map(larger, init_params(jax.random.PRNGKey(seed), cfg))


def _batch(B=2, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, CFG.vocab)
    return tok, jnp.roll(tok, -1, axis=-1)


def _close(got, want, tol=1e-4, atol=0.0):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= (
        tol * max(np.abs(want).max(), 1e-6) + atol
    )


def _mesh(tp):
    return Mesh(np.array(jax.devices()[:tp]).reshape(1, tp), ("dp", "tp"))


def _dense(layers):
    """``CFG`` with ``layers``' mixers and no experts."""
    return dataclasses.replace(
        CFG, layers=tuple(
            dataclasses.replace(k, ffn="dense", d_ff=96) for k in layers
        ),
        n_layers=len(layers), n_experts=0, moe_router="softmax",
        moe_router_experts=None, moe_first_expert=0, moe_shared_d_ff=0,
        moe_route_scale=1.0, moe_n_group=1, moe_topk_group=1,
        moe_bias_rate=0.0, moe_capacity_factor=1.5,
    )


# -- the KDA core ----------------------------------------------------------------


def _core_inputs(T, H=2, dk=16, dv=24, seed=0, at_bound=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = reference.l2_norm(jax.random.normal(ks[0], (2, H, T, dk))) * dk ** -0.5
    k = reference.l2_norm(jax.random.normal(ks[1], (2, H, T, dk)))
    v = jax.random.normal(ks[2], (2, H, T, dv))
    g = -5.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], (2, H, T, dk)))
    if at_bound:
        g = jnp.full_like(g, -5.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, H, T)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule on (B, H, T, .) arrays."""
    tokens_first = lambda x: x.transpose(1, 0, 2)
    return jnp.stack([
        reference.kda_recurrence(
            *(tokens_first(x[b]) for x in (q, k, v, g)), beta[b].T
        ).transpose(1, 0, 2)
        for b in range(q.shape[0])
    ])


@pytest.mark.parametrize("at_bound", [False, True])
@pytest.mark.parametrize("length", [64, 100, 192])
def test_chunked_core_against_the_recurrence(length, at_bound):
    """Forward and the gradient by every input, at lengths that are and
    are not whole chunks; with every gate at the lower bound (a chunk's
    decay sums to -320: ``exp`` of it is 0 in float32) nothing overflows."""
    x = _core_inputs(length, at_bound=at_bound)
    co = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    # each side ONE compiled function (an eager walk compiles every
    # operation by itself: ROADMAP D14)
    both = lambda f: jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *b: jnp.sum(f(*b) * co), argnums=(0, 1, 2, 3, 4)
    )(*a)))(*x)
    (got, got_grads), (want, want_grads) = both(kda.kda_chunked), both(_recurrence)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    _close(got, want, 2e-5)
    for name, a, b in zip("q k v g beta".split(), got_grads, want_grads):
        # at the bound a gate's gradient is a difference of terms of e^-5
        _close(a, b, 2e-4 if at_bound and name == "g" else 5e-5), name


def test_chunks_and_sub_blocks_are_the_callers_to_choose():
    x = _core_inputs(96)
    want = jax.jit(_recurrence)(*x)
    for chunk, sub in ((32, 16), (64, 32), (16, 2)):
        core = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk, sub=sub))
        _close(core(*x), want, 2e-5)
    with pytest.raises(ValueError, match="sub-blocks"):
        kda.kda_chunked(*x, chunk=64, sub=24)
    assert (kda.CHUNK, kda.SUB) == (64, 16)


def test_unit_lower_inverse_and_its_cotangent():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64)), -1) * 0.3
    want = np.linalg.inv(np.eye(64) + np.asarray(a, np.float64))
    _close(kda._unit_lower_inverse(a), want, 1e-5)
    co = jax.random.normal(jax.random.PRNGKey(1), a.shape)
    got = jax.grad(lambda a: jnp.sum(kda._unit_lower_inverse(a) * co))(a)
    plain = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(64) + jnp.tril(a, -1)) * co
    ))(a)
    _close(got, plain, 1e-4)
    assert not np.asarray(got)[:, np.triu_indices(64)[0],
                               np.triu_indices(64)[1]].any()


# -- the whole model ------------------------------------------------------------


@functools.cache
def _seeded_reference_logits():
    """The reference's logits of ``_params()`` on ``_batch()``, ONE compiled
    function run once for the three lowerings' cases (an eager walk compiles
    every operation by itself: ROADMAP D14)."""

    @jax.jit
    def logits(weights, tok):
        h, _ = reference.hidden(weights, tok, **REF)
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
        lambda w: reference.loss(w, tok, tgt, **REF)
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
    3, which ``ULP`` allows for).  tp splits the KDA heads as it splits
    the latent mixer's: it falls out of the specs."""
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
            if name == "expert_bias":
                continue    # outside the gradient: moved by its own rule
            _close(got_l[name], want_l[name], 2e-4, ULP), name


def test_remat_recomputes_the_same_step():
    """On dense layers: off the TPU the held experts' Pallas kernels run
    interpreted, through host callbacks, which ``jax.checkpoint`` refuses."""
    cfg = _dense(CFG.layers[1:])
    params, (tok, tgt) = _params(cfg), _batch()
    step, shard = make_sharded_train_step(cfg, _mesh(1), lr=1.0)
    again, _ = make_sharded_train_step(
        dataclasses.replace(cfg, remat=True), _mesh(1), lr=1.0
    )
    (new, loss), (new_r, loss_r) = (
        s(shard(params), tok, tgt) for s in (step, again)
    )
    _close(loss_r, loss, 1e-6)
    for a, b in zip(jax.tree.leaves(new_r), jax.tree.leaves(new)):
        _close(a, b, 1e-5, ULP)


@pytest.mark.parametrize("how,where", [
    (dict(kda_how=dict(no_decay=True)), "the decay left out"),
    (dict(kda_how=dict(no_conv=True)), "the convolutions left out"),
    (dict(latent_how=dict(no_gate=True)), "the head-wise gate left out"),
    (dict(moe_how=dict(group_max=True)), "a group's score its largest"),
    (dict(moe_how=dict(biased_weights=True)), "the bias in the weights"),
])
def test_a_broken_reference_is_told_apart(how, where):
    params, (tok, _) = _params(), _batch()
    for lp in params["layers"]:
        if "moe" in lp:     # a bias that decides some choices
            lp["moe"]["bias"] = 0.2 * jax.random.normal(
                jax.random.PRNGKey(7), lp["moe"]["bias"].shape
            )
    fwd, shard = make_sharded_forward(CFG, _mesh(1))
    got = np.asarray(fwd(shard(params), tok))
    weights = driver.reference_weights(params)
    right, _ = reference.hidden(weights, tok, **REF)
    _close(got, reference.head(weights, right))
    h = reference.embed(weights, tok)
    for lp in weights["layers"]:
        h, _ = reference.layer(h, lp, **REF, **how)
    broken = np.asarray(reference.head(weights, h))
    # ten times what ``_close`` allows the right one
    assert np.abs(got - broken).max() > 1e-3 * np.abs(broken).max(), where


def test_the_trees_are_the_two_mixers():
    specs = param_specs(CFG)["layers"]
    shapes = jax.eval_shape(
        lambda k: init_params(k, CFG), jax.random.PRNGKey(0)
    )["layers"]
    assert set(shapes[0]) == set(specs[0]) == {
        "wq", "wk", "wv", "wf", "wg", "wbeta", "conv_q", "conv_k", "conv_v",
        "a_log", "dt_bias", "o_norm", "wo", "ln1", "ln2", "w1", "w2", "w3",
    }
    assert shapes[0]["wf"].shape == shapes[0]["wg"].shape == (64, 4 * 16)
    assert shapes[0]["wbeta"].shape == (64, 4)
    assert shapes[0]["conv_k"].shape == (4, 4 * 16)
    assert shapes[0]["a_log"].shape == (4,)
    assert shapes[0]["dt_bias"].shape == (4 * 16,)
    assert shapes[0]["o_norm"].shape == (16,)
    # the latent layer: q straight from the hidden state, a gate a head
    assert set(shapes[3]) == set(specs[3]) == {
        "wq", "wkv_a", "kv_a_norm", "wkv_b", "wg", "wo", "ln1", "ln2", "moe",
    }
    assert shapes[3]["wq"].shape == (64, 4 * (16 + 8))
    assert shapes[3]["wg"].shape == (64, 4)
    assert shapes[3]["wkv_a"].shape == (64, 16 + 8)     # latent | ONE rope key


@pytest.mark.parametrize("gated", [True, False])
def test_q_without_a_latent_against_the_references_direct_q(gated):
    """The latent mixer alone: ``wq`` in the tree is q straight from the
    hidden state, with and without the head-wise gate; with a ``wq_a`` in
    the tree it is DeepSeek-V2's (``tests/test_deepseek_v2.py``)."""
    cfg = dataclasses.replace(
        CFG, layers=None, kda=None, n_layers=1, n_experts=0,
        moe_router="softmax", moe_n_group=1, moe_topk_group=1,
        moe_route_scale=1.0, moe_shared_d_ff=0, moe_router_experts=None,
        moe_first_expert=0, moe_bias_rate=0.0, moe_capacity_factor=1.5,
        attn_gate="head" if gated else False,
    )
    lp = init_params(jax.random.PRNGKey(2), cfg)["layers"][0]
    lp = {k: v * 3.0 if v.ndim == 2 else v for k, v in lp.items()}
    assert ("wg" in lp) == gated and "wq_a" not in lp
    h = jax.random.normal(jax.random.PRNGKey(3), (2, T, 64))
    latent = {"scale": cfg.attn_scale(), "inv_freq": cfg.rope_inv_freq(),
              "table_scale": cfg.rope_table_scale(), "eps": cfg.norm_eps}
    got = _latent_attn_partial(
        h, lp, 4, "naive", True, cfg.rope_base, None, latent
    )
    names = {"q_proj": "wq", "kv_a_proj_with_mqa": "wkv_a", "o_proj": "wo",
             "kv_a_layernorm": "kv_a_norm", "kv_b_proj": "wkv_b"}
    ref_lp = {k: lp[v] for k, v in names.items()}
    if gated:
        ref_lp["g_proj"] = lp["wg"]
    want = jnp.stack([
        reference.latent_attention(
            h[b], ref_lp, n_head=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=16, rope_theta=10000.0, q_block=32,
            no_gate=not gated,
        ) for b in range(2)
    ])
    _close(got, want)


# -- the layer rule and the configuration file -------------------------------------


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_layer_rule_from_layer_group_size(rehearse):
    cell = manifest.cell(
        manifest.load(), "train_ling3_t8192_b2", rehearse=rehearse
    )
    config = cell["config"]
    cfg = driver.program_config(config)
    mixers = [cfg.mixer(kind) for kind in cfg.layers]
    group, dense = config["layer_group_size"], config["first_k_dense_replace"]
    assert mixers == [
        "latent" if (i + 1) % group == 0 else "kda" for i in config["layers_kept"]
    ]
    assert [kind.ffn for kind in cfg.layers] == [
        "dense" if i < dense else "moe" for i in config["layers_kept"]
    ]
    assert all(kind.rope == (m == "latent") for kind, m in zip(cfg.layers, mixers))
    if not rehearse:
        # published layer 1, then one whole period: five KDA to one latent
        assert mixers == ["kda"] + ["kda"] * 5 + ["latent"]
        assert [k.d_ff for k in cfg.layers] == [6144] + [768] * 6
        assert cfg.latent == LatentAttention(None, 512, 128, 64, 128)
        assert cfg.kda == DeltaAttention(128, 4, -5.0)
        assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_top_k) == (8, 4, 8)
        assert (cfg.n_experts, cfg.router_experts()) == (64, 512)
        assert cfg.remat and cfg.attn_gate == "head"
        # over all 42 published layers the rule gives 7 latent layers
        whole = dict(config, layers_kept=list(range(42)), num_hidden_layers=42)
        kinds = driver.layer_kinds(whole)
        assert [m for m, _ in kinds].count("latent") == 7
        assert [f for _, f in kinds].count("dense") == 2


def test_the_configuration_file_says_what_was_cut_and_assumed():
    with open(os.path.join(
        manifest.CHECKOUT, "perfbench/configs/ling3_flash_train.json"
    )) as f:
        config = json.load(f)
    assert config["published"] == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184,
        "num_nextn_predict_layers": 1,
    }
    assert set(config["reduced"]) == set(config["published"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        7, 64, 19648, 0)
    assert config["layers_kept"] == [1, 6, 7, 8, 9, 10, 11]
    for item in (
        "layer_rule", "kda_heads", "kda_mixer", "kda_gate", "use_qk_norm",
        "group_norm_size", "rotary", "rope_interleave", "latent_mixer",
        "router", "shared_expert", "swiglu_limits", "initializer_range",
    ):
        assert config["assumed"][item], item
    assert "8 chips share each layer" in config["deployment"]
    assert "memory_analysis" in config["memory"]
    assert config["program"]["_remat_why"]
    # the widths are the published ones
    assert (config["hidden_size"], config["head_dim"], config["kv_lora_rank"],
            config["moe_intermediate_size"], config["intermediate_size"],
            config["num_experts_per_tok"]) == (2560, 128, 512, 768, 6144, 8)


def test_auto_resolves_to_flash_at_the_cell_shapes():
    """``auto`` is decided on the width of q's FIRST part, which the
    kernels hold as they hold a head without a second part: 2 x 8192 at
    128 + 64 columns resolves as 2 x 8192 at 128 does."""
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    assert _auto_flash_fits(q)
    assert resolve_attention("auto", q) == "blockwise"      # off the TPU
    assert resolve_attention("flash", q) == "flash"
    # the backward's residents at 128 + 64 | 128, T = 8192: 37.5 MiB
    assert _flash_bwd_vmem_bytes(8192, 128, 512, 2, 128, 128) == 39_321_600


# -- routing --------------------------------------------------------------------


def _bank(held=16, first=0, shared=True, seed=3):
    """A bank of ``held`` of 16 experts, cut from ONE seeded whole."""
    whole = init_moe_params(
        jax.random.PRNGKey(seed), 64, 32, 16, gated=True, shared_d_ff=32,
    )
    whole["gate"] = whole["gate"] * 8.0     # decided routing
    bank = {k: whole[k][first:first + held] for k in ("w1", "w2", "w3")}
    bank["gate"] = whole["gate"]
    bank["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (16,))
    if shared:
        bank["shared"] = whole["shared"]
    return bank


def _as_reference(bank):
    return {
        "gate": bank["gate"], "expert_bias": bank["bias"],
        "experts.gate_proj": bank["w1"], "experts.up_proj": bank["w3"],
        "experts.down_proj": bank["w2"],
        "shared_experts.gate_proj": bank["shared"]["w1"],
        "shared_experts.up_proj": bank["shared"]["w3"],
        "shared_experts.down_proj": bank["shared"]["w2"],
    }


ROUTE = dict(capacity_factor=None, k=3, renormalize=True, route_scale=2.5,
             n_group=4, topk_group=2, router="sigmoid")
REF_ROUTE = dict(top_k=3, n_group=4, topk_group=2, routed_scaling_factor=2.5)


def _x(seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 64))


def test_sigmoid_grouped_top_k_against_the_reference():
    bank, x = _bank(), _x()
    got, aux = moe_ffn(x, bank, return_aux=True, **ROUTE)
    want, picked = reference.moe(
        x.reshape(-1, 64), _as_reference(bank), **REF_ROUTE
    )
    _close(got.reshape(-1, 64), want)
    counts, hits, _ = reference.routing_facts(picked, 3, 4, 2)
    assert np.array_equal(aux["expert_tokens"], counts)
    assert np.array_equal(aux["group_tokens"], hits)
    assert int(hits.sum()) == 2 * 2 * T          # two groups a token
    # the limit binds, and so does the rule: plain top-3 and the largest
    # member as a group's score both pick other experts for some tokens
    plain = moe_ffn(x, bank, **{**ROUTE, "n_group": 1, "topk_group": 1})
    assert np.abs(np.asarray(plain - got)).max() > 1e-3
    by_max, _ = reference.moe(
        x.reshape(-1, 64), _as_reference(bank), group_max=True, **REF_ROUTE
    )
    assert np.abs(np.asarray(by_max - want)).max() > 1e-3


def test_every_group_kept_is_the_sigmoid_routers_plain_top_k():
    bank, x = _bank(), _x()
    plain = moe_ffn(x, bank, **{**ROUTE, "n_group": 1, "topk_group": 1})
    all_kept = moe_ffn(x, bank, **{**ROUTE, "topk_group": 4})
    assert np.array_equal(np.asarray(plain), np.asarray(all_kept))


def test_the_shares_add_up_to_the_uncut_layer():
    """The four groups' held parts, the shared expert counted once, sum to
    what the uncut reference gives for the whole layer (the published
    model's eight shares of 64 are four of 4 here)."""
    x = _x()
    total, held = 0.0, 0
    for g in range(4):
        bank = _bank(held=4, first=4 * g)
        if g:
            del bank["shared"]
        y, aux = moe_ffn(x, bank, return_aux=True, first_expert=4 * g,
                         held_row_factor=8.0, **ROUTE)
        assert int(aux["dropped"]) == 0
        held += int(aux["held_entries"])
        total = total + y
    assert held == 2 * T * 3            # every entry is held by one share
    want, _ = reference.moe(
        x.reshape(-1, 64), _as_reference(_bank()), **REF_ROUTE
    )
    _close(total.reshape(-1, 64), want)


def test_router_probe_counts_against_the_reference():
    params, (tok, _) = _params(), _batch()
    probe = make_sharded_router_probe(CFG, _mesh(1))
    _, shard = make_sharded_forward(CFG, _mesh(1))
    got = probe(shard(params), tok)
    weights = driver.reference_weights(params)
    _, picked = reference.hidden(weights, tok, **REF)
    assert len(picked) == 3                      # the three expert layers
    facts = [reference.routing_facts(p, 3, 4, 2) for p in picked]
    counts = np.stack([np.asarray(f[0]) for f in facts])
    assert np.array_equal(got["expert_tokens"], counts)
    assert np.array_equal(got["group_tokens"], np.stack([f[1] for f in facts]))
    assert np.array_equal(got["held_entries"], counts[:, 4:8].sum(axis=1))
    assert int(np.asarray(got["dropped"]).sum()) == 0


# -- the scopes ---------------------------------------------------------------------


def test_the_mixers_run_under_their_device_scopes():
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::latent",
                  "accl.attn::mla"):
        assert f"``{scope}``" in profiling.__doc__, scope
    params, (tok, tgt) = _params(), _batch()
    step, shard = make_sharded_train_step(CFG, _mesh(1), lr=1.0)
    text = step.lower(shard(params), tok, tgt).compile().as_text()
    # every computation of the step's text: the core's scan over the
    # chunks is a loop, which the entry computation alone does not show
    found = driver.scoped_instructions(text)
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::latent",
                  "accl.attn::mla", "accl.moe::route", "accl.moe::experts"):
        assert found.get(scope), scope
    entry = scope_ops.scopes_of(text)
    assert set(entry.get("accl.attn::kda", ())) < set(found["accl.attn::kda"])


# -- the refusals, by name --------------------------------------------------------


def _dense_kda():
    return dataclasses.replace(
        _dense((KDA, KDA)), latent=None, attn_gate=False
    )


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_kda_mixer_refuse_it_by_name(path):
    dense = _dense_kda()
    params = init_params(jax.random.PRNGKey(0), dense)
    tok, _ = _batch()
    with pytest.raises(ValueError, match="KDA mixer"):
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


@pytest.mark.parametrize("change,match", [
    (dict(layers=(MLA,) * 4), "KDA mixer"),              # sizes, no layer
    (dict(kda=DeltaAttention(16, 4, -6.0)), "KDA mixer"),    # past -80 / SUB
    (dict(kda=DeltaAttention(16, 4, 0.0)), "KDA mixer"),
    (dict(kda=None), "needs TransformerConfig.kda"),
    (dict(layers=(dataclasses.replace(KDA, window=8),) + (MLA,) * 3),
     "has no window"),
    (dict(layers=(KDA, LayerKind(mixer="attention")) + (MLA,) * 2),
     "attention mixer in a stack"),
    (dict(layers=(KDA, LayerKind(mixer="mamba")) + (MLA,) * 2), "unknown mixer"),
    (dict(latent=None), "attn_gate='head' is the latent mixer's"),
    (dict(attn_gate=True), "latent mixer"),
    (dict(attn_gate="channel"), "unknown attn_gate"),
    # the sigmoid router's group score is of two experts
    (dict(moe_n_group=16, moe_topk_group=4), "grouped top-k"),
])
def test_a_configuration_that_cannot_hold_is_refused(change, match):
    def moe_free(layers):
        return tuple(dataclasses.replace(k, ffn="moe", d_ff=32) for k in layers)

    if "layers" in change:
        change = dict(change, layers=moe_free(change["layers"]))
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


def test_block_diffusion_refuses_the_kda_mixer():
    from accl_tpu.models import BlockDiffusion

    with pytest.raises(ValueError, match="KDA mixer"):
        dataclasses.replace(
            _dense_kda(), diffusion=BlockDiffusion(block=4, mask_id=255)
        )
