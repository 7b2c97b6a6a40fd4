"""The Solar Open 2 hybrid block of ``accl_tpu.models`` (KDA layers under
the PUBLISHED decay gate, ``-exp(a_log) softplus(.)`` with no lower bound,
gate projections through a rank and a write strength in (0, 2), beside a
gated grouped-query layer without position; a sigmoid router over a width
that is no power of two) against the plain float32 reference of
``perfbench/reference/solar_open2.py`` (KDA as the token-by-token
recurrence), at small sizes on the CPU mesh with seeded weights; and the
chunked core for ANY ``g <= 0`` (the split by halving) in both lowerings
against that recurrence.  Float32 against float32 is held to 1e-4 of the
largest value."""

import dataclasses
import functools
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    BlockDiffusion,
    DeltaAttention,
    LayerKind,
    TransformerConfig,
    encoder_forward,
    generate,
    init_moe_params,
    init_params,
    make_pp_train_step,
    make_sharded_forward,
    make_sharded_generate,
    make_sharded_train_step,
    moe_ffn,
)
from accl_tpu.models.transformer import param_specs
from accl_tpu.ops import kda
from accl_tpu.ops.pallas import kda as kda_kernels
from accl_tpu.ops.pallas import kda_mixer
from accl_tpu.utils import profiling
from perfbench import flops_solar2, manifest
from perfbench.drivers import train_steps_solar2 as driver
from perfbench.reference import solar_open2 as reference

T = 80          # a chunk of 64 and a tail of 16
ULP = 5e-7
GQA = LayerKind(mixer="attention", rope=False, ffn="moe", d_ff=32)
KDA = LayerKind(mixer="kda", rope=False, ffn="moe", d_ff=32)
#: four query heads of 16 on two KV heads; one period ``G K K K``; ten router
#: outputs (no power of two) with experts 5..9 held, top 3
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=4,
    layers=(GQA, KDA, KDA, KDA), d_ff=32, max_seq=128, pos_embedding="rope",
    rope_base=10000.0, norm="rmsnorm", norm_eps=1e-5, ffn="swiglu",
    tie_head=False, attn_gate=True,
    kda=DeltaAttention(head_dim=16, conv=4, lower_bound=None, beta_scale=2.0,
                       gate_rank=8),
    n_experts=5, moe_top_k=3, moe_capacity_factor=None,
    moe_norm_topk_prob=True, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_router="sigmoid", moe_route_scale=1.0, moe_bias_rate=0.001,
    moe_shared_d_ff=32, moe_router_experts=10, moe_first_expert=5,
    moe_held_row_factor=8.0, attention="naive",
)
REF = dict(n_head=4, n_kv_head=2, top_k=3, routed_scaling_factor=1.0,
           first_expert=5, q_block=32)

#: sha256 of the traced programs of the BOUNDED gate's paths, taken on the
#: parent commit (42dead0, PR 47): this PR edits the files they live in, and
#: the bounded gate is chosen statically, so its programs (and with them its
#: results, to the bit) are what they were.  ``xla_*``: the XLA form of the
#: core and of the decay chain at Ling-3.0's rehearsal head width; the rest:
#: the kernels at the Ling-3.0 cell's shapes (a kernel's jaxpr, without the
#: file's line numbers).  ``core`` was re-recorded IN PLACE in PR 53, by
#: intent, on its own tree: the bounded gate's forward takes the inverse by
#: halving (level 1 a subtraction, levels 2 and 4 on the VPU, the upper
#: levels' lower rows alone through ``highest`` products) where it squared
#: powers of ``A`` (ten products), so its kernel's body changed at the same
#: precision (PR 47's read e7f55e04...d7b0d9dc); the other six did not move.
PARENT_PROGRAMS = {
    "xla_core": "483dc4107869af5d8cd11b16005aea6c5187d1bc99abf3f26738d920737b3627",
    "xla_decay": "1d1c4b298cf92921b2acf709f7ac303c088cfb6f9ad8c5148868dc7eda68e33d",
    "core": "a4f385f0b93a5b9ccbfd3687d035865dc4694b6955afa7faa5e61d0c4294bdd3",
    "q": "de344cee8ecae1f41dee1c2c47e0eac28e7fef07cd2375ddfaebca1ab2f7385e",
    "v": "3fe8b1457422fac9d3192cd1b7c43bc591d6aac144e3ae4762e4bbfd02e7b631",
    "decay": "fad34474a5d3b69fdfd5f5da9bc5525ecd52e08a9c1250067a85bff487699a5a",
    "out": "3ea0525c365faab4dc091485963384626c0a1c37b56dc45a80a5eeeaf6e85db4",
}


@pytest.fixture(autouse=True)
def _highest(request):
    if "programs_are_the_parents" in request.node.name:
        yield           # a program's text, as the parent traced it
        return
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales and matrices larger than the init's
    and not all alike, so that a missing scale shows, routing is decided and
    the mixers' parts matter (the taps, ``dt_bias`` and the scalars a head
    stay the init's: ``dt_bias`` already spreads the gate over both sides of
    a bound of -5)."""

    def larger(path, p):
        if path[-1].key == "dt_bias":
            return p
        if p.ndim == 1 and p.shape[0] > cfg.n_heads:
            return p * 3.0 + 0.1 * jax.random.normal(
                jax.random.PRNGKey(p.size), p.shape, p.dtype
            )
        return p * 3.0 if p.ndim == 2 and p.shape[0] > 4 else p

    return jax.tree_util.tree_map_with_path(
        larger, init_params(jax.random.PRNGKey(seed), cfg)
    )


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


# -- the core for any g <= 0 -----------------------------------------------------


def _core_inputs(T, B=1, H=2, dk=16, dv=16, seed=0):
    """``g`` at -200, -50, -5 and -0.001, mixed over tokens on half the
    channels and fixed a channel on the other half (a channel that forgets
    at once beside one that remembers across chunks); beta up to 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = reference.l2_norm(jax.random.normal(ks[0], (B, H, T, dk))) * dk ** -0.5
    k = reference.l2_norm(jax.random.normal(ks[1], (B, H, T, dk)))
    v = jax.random.normal(ks[2], (B, H, T, dv))
    levels = jnp.array([-200.0, -50.0, -5.0, -0.001])
    g = levels[jax.random.randint(ks[3], (B, H, T, dk), 0, 4)]
    fixed = levels[jax.random.randint(ks[4], (1, H, 1, dk), 0, 4)]
    g = jnp.where(jax.random.bernoulli(ks[5], 0.5, (1, 1, 1, dk)), fixed, g)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[6], (B, H, T)))
    return (q, k, v, g, beta), jax.random.normal(ks[7], (B, H, T, dv))


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule on (B, H, T, .) arrays."""
    tokens_first = lambda x: x.transpose(1, 0, 2)
    return jnp.stack([
        reference.kda_recurrence(
            *(tokens_first(x[b]) for x in (q, k, v, g)), beta[b].T
        ).transpose(1, 0, 2)
        for b in range(q.shape[0])
    ])


def _core_case(core, length, **shape):
    """``core`` against the recurrence at ``length`` tokens: ``o`` and all
    five gradients, each finite and within 1e-4 of its largest value."""
    inputs, w = _core_inputs(length, **shape)
    assert float(inputs[3].min()) == -200.0 and float(inputs[4].max()) > 1.9
    both = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2, 3, 4), has_aux=False
    ))
    _close(jax.jit(core)(*inputs), jax.jit(_recurrence)(*inputs))
    (_, got), (_, want) = both(core)(*inputs), both(_recurrence)(*inputs)
    for name, a, b in zip("qkvgb", got, want):
        _close(a, b), name


@pytest.mark.parametrize("length", [64, 100, 192])
def test_xla_form_for_any_decay_against_the_recurrence(length):
    """Lengths that are and are not whole chunks; the bounded form on the
    same inputs overflows (that is what the split by halving is for)."""
    _core_case(lambda *a: kda.kda_chunked(*a, safe=True), length)
    inputs, _ = _core_inputs(length)
    assert not kda_kernels.takes(inputs[0].shape, inputs[2].shape)
    assert not np.isfinite(np.asarray(kda.kda_chunked(*inputs))).all()


@pytest.mark.parametrize("length", [128, 200])
def test_kernels_for_any_decay_against_the_recurrence(length, monkeypatch):
    """``kda_fwd`` / ``kda_bwd`` interpreted, whole-lane heads, products in
    float32 (the chip's one bfloat16 pass is the XLA form's there too); a
    whole block and one that is not."""
    monkeypatch.setattr(kda_kernels, "_ONE_PASS", jnp.float32)
    inputs, _ = _core_inputs(length, H=1, dk=128, dv=128)
    assert kda_kernels.takes(inputs[0].shape, inputs[2].shape)
    _core_case(
        lambda *a: kda.kda_chunked(*a, safe=True), length, H=1, dk=128, dv=128
    )


def test_the_two_forms_agree_under_a_bound():
    """Where both are right (``g`` in [-5, 0)) the split by halving gives
    what the split at a sub-block's middle gives."""
    (q, k, v, g, beta), _ = _core_inputs(100)
    g = -5.0 * jax.nn.sigmoid(g + 5.0)
    _close(kda.kda_chunked(q, k, v, g, beta, safe=True),
           kda.kda_chunked(q, k, v, g, beta), 1e-5)
    with pytest.raises(ValueError, match="2\\^n"):
        kda.kda_chunked(q, k, v, g, beta, chunk=48, sub=16, safe=True)


def _program(fn, *shapes):
    """sha256 of the gradient's jaxpr, without a kernel's file and line."""
    args = [jax.ShapeDtypeStruct(s, t) for s, t in shapes]
    grad = jax.grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(len(args))),
    )
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(grad)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("name", list(PARENT_PROGRAMS))
def test_the_bounded_gates_programs_are_the_parents(name):
    f32, bf16 = jnp.float32, jnp.bfloat16
    B, H, L, d = 2, 32, 8192, 128
    rows, flat, taps = ((B, H, L, d), f32), ((B, L, H * d), bf16), ((4, H * d), bf16)
    small = ((1, 4, 128, 32), f32)
    fn, shapes = {
        "xla_core": (lambda *a: kda._xla_form(*a),
                     [small] * 4 + [((1, 4, 128), f32)]),
        "xla_decay": (lambda *a: kda._xla_decay_in(*a, -5.0),
                      [((1, 128, 128), f32), ((128,), f32), ((4,), f32)]),
        "core": (lambda *a: kda_kernels.kda(*a, interpret=False),
                 [rows] * 4 + [((B, H, L), f32)]),
        "q": (lambda x, t: kda_mixer.conv_in(
            x, t, H, unit=True, scale=d ** -0.5, interpret=False), [flat, taps]),
        "v": (lambda x, t: kda_mixer.conv_in(
            x, t, H, unit=False, interpret=False), [flat, taps]),
        "decay": (lambda *a: kda_mixer.decay_in(*a, -5.0, interpret=False),
                  [flat, ((H * d,), f32), ((H,), f32)]),
        "out": (lambda *a: kda_mixer.gated_out(*a, 1e-6, bf16, interpret=False),
                [rows, flat, ((d,), bf16)]),
    }[name]
    assert _program(fn, *shapes) == PARENT_PROGRAMS[name]


@pytest.mark.parametrize("kernel", [False, True])
def test_the_softplus_decay_chain(kernel):
    """``-exp(a_log) softplus(x + dt_bias)`` and its three gradients, XLA's
    form and the interpreted kernels (whole-lane heads)."""
    H, d = 2, 128 if kernel else 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = 3.0 * jax.random.normal(ks[0], (2, 70, H * d))
    bias = jax.random.normal(ks[1], (H * d,))
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0))
    w = jax.random.normal(ks[3], (2, H, 70, d))
    assert kda_mixer.takes(H * d, H) == kernel

    def plain(x, bias, a_log):
        f = (x + bias).reshape(2, 70, H, d).transpose(0, 2, 1, 3)
        return -jnp.exp(a_log)[None, :, None, None] * jnp.log1p(jnp.exp(f))

    chain = lambda *a: kda.decay_in(*a, None)
    got, want = chain(x, bias, a_log), plain(x, bias, a_log)
    assert float(got.max()) < 0.0 and float(got.min()) < -50.0
    _close(got, want, 1e-5)
    loss = lambda f: lambda *a: (f(*a) * w).sum()
    for a, b in zip(jax.grad(loss(chain), argnums=(0, 1, 2))(x, bias, a_log),
                    jax.grad(loss(plain), argnums=(0, 1, 2))(x, bias, a_log)):
        _close(a, b, 1e-5)


# -- the whole model ------------------------------------------------------------


@functools.cache
def _seeded_reference_grads():
    """``_reference_grads`` of ``_params()`` on ``_batch()``, run once for
    the tp 1 and the tp 2 case (ROADMAP D14)."""
    with jax.default_matmul_precision("highest"):
        params, (tok, tgt) = _params(), _batch()
        return _reference_grads(params, tok, tgt)


def _reference_grads(params, tok, tgt):
    weights = driver.reference_weights(params)
    return jax.jit(jax.value_and_grad(
        lambda w: reference.loss(w, tok, tgt, **REF)
    ))(weights)


def _reference_logits(weights, tok, **how):
    """The reference's logits, its layers broken by ``how``."""

    @jax.jit
    def logits(weights):
        h = reference.embed(weights, tok)
        for lp in weights["layers"]:
            h, _ = reference.layer(h, lp, **REF, **how)
        return reference.head(weights, h)

    return logits(weights)


@pytest.fixture(scope="module")
def decided():
    """Weights whose expert bias decides some choices, a batch, and the
    program's logits of it."""
    params, (tok, _) = _params(), _batch()
    for lp in params["layers"]:
        lp["moe"]["bias"] = 0.2 * jax.random.normal(
            jax.random.PRNGKey(7), lp["moe"]["bias"].shape
        )
    with jax.default_matmul_precision("highest"):
        fwd, shard = make_sharded_forward(CFG, _mesh(1))
        got = np.asarray(fwd(shard(params), tok))
    return driver.reference_weights(params), tok, got


def test_the_seeded_gate_lies_on_both_sides_of_a_bound():
    """What the test model's batch works: log-decays past Ling-3.0's -5 and
    runs of 16 tokens past float32's range, beside channels that remember
    across a chunk."""
    params, (tok, _) = _params(), _batch()
    weights = driver.reference_weights(params)

    @jax.jit
    def first_kda_layers(weights):
        h = reference.embed(weights, tok)
        h, _ = reference.layer(h, weights["layers"][0], **REF)
        return reference.layer_gate_facts(h, weights["layers"][1], n_head=4)

    facts = first_kda_layers(weights)
    assert float(facts["under_bound"]) > 0.1
    assert float(facts["sub_blocks_past_float32"]) > 0.05
    assert float(facts["chunks_remembered"]) > 0.05


@pytest.mark.parametrize("attention", ["naive", "flash"])
def test_logits_against_the_reference(attention):
    cfg = dataclasses.replace(CFG, attention=attention)
    params, (tok, _) = _params(), _batch()
    fwd, shard = make_sharded_forward(cfg, _mesh(1))
    got = fwd(shard(params), tok)
    _close(got, _reference_logits(driver.reference_weights(params), tok))


@pytest.fixture(scope="module")
def compiled_step():
    """``CFG``'s train step at lr 1 on one device, compiled once: the
    gradients' case runs it, the scopes' case reads its text."""
    with jax.default_matmul_precision("highest"):
        params, (tok, tgt) = _params(), _batch()
        step, shard = make_sharded_train_step(CFG, _mesh(1), lr=1.0)
        return step.lower(shard(params), tok, tgt).compile(), shard


@pytest.mark.parametrize("tp", [1, 2])
def test_loss_and_gradients_against_the_reference(tp, compiled_step):
    """Through ``make_sharded_train_step`` itself: at lr 1 the step's update
    IS the gradient (to the float32 spacing of a weight of about 3, which
    ``ULP`` allows for).  tp splits the KDA heads and with them the second
    matrix of each gate projection; the first is every chip's."""
    params, (tok, tgt) = _params(), _batch()
    if tp == 1:
        step, shard = compiled_step
    else:
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


def _dense(layers=(GQA, KDA)):
    """``CFG`` with ``layers``' mixers and no experts."""
    return dataclasses.replace(
        CFG, layers=tuple(
            dataclasses.replace(k, ffn="dense", d_ff=96) for k in layers
        ),
        n_layers=len(layers), n_experts=0, moe_router="softmax",
        moe_router_experts=None, moe_first_expert=0, moe_shared_d_ff=0,
        moe_bias_rate=0.0, moe_capacity_factor=1.5,
    )


def test_remat_recomputes_the_same_step():
    """On dense layers: off the TPU the held experts' Pallas kernels run
    interpreted, through host callbacks, which ``jax.checkpoint`` refuses."""
    cfg = _dense()
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
    (dict(kda_how=dict(bounded_gate=-5.0)), "Ling-3.0's bounded gate"),
    (dict(kda_how=dict(beta_scale=1.0)), "beta without its 2"),
    (dict(kda_how=dict(no_decay=True)), "the decay left out"),
    (dict(kda_how=dict(no_conv=True)), "the convolutions left out"),
    (dict(gqa_how=dict(no_gate=True)), "the GQA gate left out"),
    (dict(gqa_how=dict(rope_theta=10000.0)), "rope on the GQA layer"),
    (dict(moe_how=dict(biased_weights=True)), "the bias in the weights"),
])
def test_a_broken_reference_is_told_apart(how, where, decided):
    """(Gate projections at full rank cannot be told apart from two
    matrices' product and are no control.)"""
    weights, tok, got = decided
    if "bounded_gate" in how.get("kda_how", {}):    # the first: the right one too
        _close(got, _reference_logits(weights, tok))
    broken = np.asarray(_reference_logits(weights, tok, **how))
    # ten times what ``_close`` allows the right one
    assert np.abs(got - broken).max() > 1e-3 * np.abs(broken).max(), where


def test_the_trees_are_the_two_mixers():
    specs = param_specs(CFG)["layers"]
    shapes = jax.eval_shape(
        lambda k: init_params(k, CFG), jax.random.PRNGKey(0)
    )["layers"]
    gqa, kda_layer = shapes[0], shapes[1]
    assert set(gqa) == {"wq", "wk", "wv", "wo", "wg", "ln1", "ln2", "moe"}
    assert gqa["wg"].shape == (64, 64)          # a gate a CHANNEL
    assert (gqa["wq"].shape, gqa["wk"].shape) == ((64, 64), (64, 32))
    assert set(kda_layer) == {
        "wq", "wk", "wv", "wo", "wf_a", "wf_b", "wg_a", "wg_b", "wbeta",
        "conv_q", "conv_k", "conv_v", "a_log", "dt_bias", "o_norm", "ln1",
        "ln2", "moe",
    }
    assert "wkv_a" not in kda_layer and "wf" not in kda_layer
    assert kda_layer["wf_a"].shape == (64, 8) == kda_layer["wg_a"].shape
    assert kda_layer["wf_b"].shape == (8, 64) == kda_layer["wg_b"].shape
    for s, layer in zip(specs, shapes):
        assert set(s) == set(layer)
    # tp splits the way up with the heads; the way down is every chip's
    assert tuple(specs[1]["wf_a"]) == (None, None) == tuple(specs[1]["wg_a"])
    assert tuple(specs[1]["wf_b"]) == (None, "tp") == tuple(specs[1]["wg_b"])
    assert "pos" not in jax.eval_shape(
        lambda k: init_params(k, CFG), jax.random.PRNGKey(0)
    )


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_layer_rule_from_gqa_layers(rehearse):
    cell = manifest.cell(
        manifest.load(), "train_solar2_t8192_b1", rehearse=rehearse
    )
    config = cell["config"]
    cfg = driver.program_config(config)
    mixers = [cfg.mixer(kind) for kind in cfg.layers]
    assert mixers == ["attention", "kda", "kda", "kda"][:len(mixers)]
    assert mixers == [
        "attention" if i in config["gqa_layers"] else "kda"
        for i in config["layers_kept"]
    ]
    assert all(not k.rope and k.ffn == "moe" for k in cfg.layers)
    assert cfg.kda.lower_bound is None and cfg.kda.beta_scale == 2.0
    assert cfg.kda.gate_rank == cfg.kda.head_dim and cfg.attn_gate is True
    assert cfg.router_experts() & (cfg.router_experts() - 1)   # no 2^n
    if not rehearse:
        assert len(mixers) == 4         # one whole period
        assert cfg.kda == DeltaAttention(128, 4, None, 2.0, 128)
        assert (cfg.n_heads, cfg.kv_heads(), cfg.head_size()) == (64, 8, 128)
        assert (cfg.n_experts, cfg.router_experts(), cfg.moe_top_k) == (40, 320, 8)
        assert [k.d_ff for k in cfg.layers] == [1280] * 4
        assert cfg.remat and cfg.moe_shared_d_ff == 1280 and cfg.moe_n_group == 1
        # over all 48 published layers: 12 softmax layers, 1 : 3
        whole = dict(config, layers_kept=list(range(48)), num_hidden_layers=48)
        assert driver.layer_mixers(whole).count("gqa") == 12


def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        return next(
            row for row in map(json.loads, f) if row["name"] == "Solar-Open2-250B"
        )


def _config_file():
    with open(os.path.join(
        manifest.CHECKOUT, "perfbench/configs/solar_open2_train.json"
    )) as f:
        return json.load(f)


def test_the_configuration_file_says_what_was_cut_and_assumed():
    config = _config_file()
    published = {k: v for k, v in config["published"].items() if k != "parameters"}
    assert published == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608,
    }
    assert set(config["reduced"]) == set(published)
    entry = next(
        c for c in manifest.load()["configs"] if c["name"] == "solar_open2_train"
    )
    assert sorted(entry["reduced"]) == sorted(published)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 40, 24576)
    assert config["layers_kept"] == [0, 1, 2, 3]
    for item in (
        "layer_rule", "kda_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
        "kda_heads", "kda_mixer", "gqa_mixer", "router", "expert_bias_update",
        "shared_expert", "experts", "intermediate_size", "rope", "norms",
        "torch_dtype", "initializer_range",
    ):
        assert config["assumed"][item], item
    assert "8 chips share each layer" in config["deployment"]
    assert "memory_analysis" in config["memory"]
    assert "sub-blocks" in config["gate_spread"]
    assert config["program"]["_remat_why"]
    # every number of the catalog's row under the same key, but the cuts
    row = _catalog_row()
    assert config["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        assert config[key] == published.get(key, value) or key in published, key
        if key in published:
            assert value == published[key], key


def test_the_whole_models_count_is_the_published_250b_a15b():
    """The widths read into the keys (a shared expert of 1,280, the GQA
    gate a value a channel, the two gate projections through rank 128) give
    the published 250B-A15B, and the cut the issue's 3,308,353,344."""
    config = _config_file()
    whole = dict(config, layers_kept=list(range(48)))
    count = lambda **how: flops_solar2.parameter_count(
        whole, experts=320, vocab=196608, **how
    )
    assert round(count() / 1e9, 2) == 250.29
    assert round(count(active=True) / 1e9, 2) == 14.74
    assert flops_solar2.parameter_count(config) == 3_308_353_344
    cfg = driver.program_config(config)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
    ) == 3_308_353_344
    assert (flops_solar2.kda_matmul_params(config),
            flops_solar2.gqa_matmul_params(config)) == (137_625_600, 109_051_904)


# -- routing --------------------------------------------------------------------


def _bank(held=10, first=0, shared=True, seed=3):
    """A bank of ``held`` of 10 experts, cut from ONE seeded whole."""
    whole = init_moe_params(
        jax.random.PRNGKey(seed), 64, 32, 10, gated=True, shared_d_ff=32,
    )
    whole["gate"] = whole["gate"] * 8.0     # decided routing
    bank = {k: whole[k][first:first + held] for k in ("w1", "w2", "w3")}
    bank["gate"] = whole["gate"]
    bank["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (10,))
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


ROUTE = dict(capacity_factor=None, k=3, renormalize=True, route_scale=1.0,
             router="sigmoid")


def test_the_shares_add_up_to_the_uncut_layer():
    """The five shares' held parts, the shared expert counted once, sum to
    what the uncut reference gives for the whole layer (the published
    model's eight shares of 40 are five of 2 here, over a router width that
    is no power of two)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 64))
    total, held = 0.0, 0
    for g in range(5):
        bank = _bank(held=2, first=2 * g)
        if g:
            del bank["shared"]
        y, aux = moe_ffn(x, bank, return_aux=True, first_expert=2 * g,
                         held_row_factor=8.0, **ROUTE)
        assert int(aux["dropped"]) == 0
        held += int(aux["held_entries"])
        total = total + y
    assert held == 2 * T * 3            # every entry is held by one share
    want, picked = reference.moe(
        x.reshape(-1, 64), _as_reference(_bank()), top_k=3,
        routed_scaling_factor=1.0,
    )
    _close(total.reshape(-1, 64), want)
    counts, _ = reference.routing_facts(picked, 3)
    assert int(counts.sum()) == 2 * T * 3


# -- the scopes ---------------------------------------------------------------------


def test_the_mixers_run_under_their_device_scopes(compiled_step):
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::core",
                  "accl.attn::gqa_proj"):
        assert f"``{scope}``" in profiling.__doc__, scope
    found = driver.scoped_instructions(compiled_step[0].as_text())
    for scope in ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::core",
                  "accl.attn::gqa_proj", "accl.moe::route", "accl.moe::experts",
                  "accl.moe::shared"):
        assert found.get(scope), scope


# -- the refusals, by name --------------------------------------------------------


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_mixer_refuse_it_by_name(path):
    dense = _dense((KDA, KDA))
    dense = dataclasses.replace(dense, attn_gate=False)
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
    (dict(kda=DeltaAttention(16, 4, None, 1.5)), "beta_scale of 1 or 2"),
    (dict(kda=DeltaAttention(16, 4, None, 2.0, 0)), "gate_rank"),
    (dict(kda=DeltaAttention(16, 4, -6.0, 2.0, 8)), "or None"),
    (dict(kda=None), "needs TransformerConfig.kda"),
    (dict(attn_gate="head"), "latent mixer's gate"),
    (dict(layers=(dataclasses.replace(GQA, rope=True), KDA, KDA, KDA),
          pos_embedding="learned"), "rotates but pos_embedding"),
    (dict(diffusion=BlockDiffusion(block=4, mask_id=255)), "KDA mixer"),
])
def test_a_configuration_that_cannot_hold_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)
