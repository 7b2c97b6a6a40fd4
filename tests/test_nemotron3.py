"""The Nemotron-H hybrid block of ``accl_tpu.models`` (blocks of ONE
sub-layer: Mamba-2 mixers, the chunked selective state-space recurrence with
a scalar decay a head; LatentMoE layers, a sigmoid router's plain top-k over
non-gated relu2 experts that work in a latent; a grouped-query attention
block without position) against the plain float32 reference of
``perfbench/reference/nemotron_h.py`` (Mamba-2 as the token-by-token
recurrence), at small sizes on the CPU mesh with seeded weights.  Float32
against float32 is held to 1e-4 of the largest value."""

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
    BlockDiffusion,
    LayerKind,
    Mamba2,
    TransformerConfig,
    encoder_forward,
    generate,
    hybrid_layers,
    init_moe_params,
    init_params,
    make_pp_train_step,
    make_sharded_forward,
    make_sharded_generate,
    make_sharded_router_probe,
    make_sharded_train_step,
    moe_ffn,
)
from accl_tpu.models.transformer import param_specs
from accl_tpu.ops import ssd
from accl_tpu.utils import profiling
from perfbench import flops_nemotron3, manifest, scope_ops
from perfbench.drivers import train_steps_nemotron3 as driver
from perfbench.drivers.train_steps_ling3 import scoped_instructions
from perfbench.reference import nemotron_h as reference

T = 80          # two chunks of 32 and a tail of 16
ULP = 5e-7
#: eight Mamba-2 heads of 8 in two groups, a state of 16; four query heads
#: of 16 on two KV heads; 16 experts of 48 in a latent of 32, top 3, the
#: second four held; a shared expert of 80 on the hidden state
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, n_layers=5,
    layers=hybrid_layers("MEM*E", moe_d_ff=48),
    d_ff=48, max_seq=128, pos_embedding="rope", norm="rmsnorm",
    norm_eps=1e-5, ffn="relu2", tie_head=False,
    mamba=Mamba2(n_heads=8, head_dim=8, state=16, groups=2, conv=4, chunk=32),
    n_experts=4, moe_top_k=3, moe_capacity_factor=None,
    moe_norm_topk_prob=True, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_router="sigmoid", moe_route_scale=5.0, moe_bias_rate=0.001,
    moe_shared_d_ff=80, moe_latent=32, moe_router_experts=16,
    moe_first_expert=4, moe_held_row_factor=8.0, attention="naive",
)
REF = dict(
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    n_head=4, n_kv_head=2, top_k=3, routed_scaling_factor=5.0,
    first_expert=4, q_block=32,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales, biases and matrices larger than the
    init's and not all alike, so that a missing scale shows, routing is
    decided and the mixers' parts matter (the taps and the scalars a head
    stay the init's, but ``d_skip``, which is drawn)."""

    def larger(path, p):
        name = path[-1].key
        if name == "d_skip":
            return p + jax.random.normal(jax.random.PRNGKey(5), p.shape)
        if p.ndim == 1 and name not in ("a_log", "dt_bias", "bias"):
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


def _no_experts(pattern):
    """``CFG`` with ``pattern``'s blocks and no expert bank."""
    return dataclasses.replace(
        CFG, layers=hybrid_layers(pattern, d_ff=96), n_layers=len(pattern),
        mamba=CFG.mamba if "M" in pattern else None, n_experts=0,
        moe_router="softmax", moe_router_experts=None, moe_first_expert=0, moe_shared_d_ff=0, moe_route_scale=1.0,
        moe_bias_rate=0.0, moe_capacity_factor=1.5, moe_latent=0,
    )


# -- the SSD core ----------------------------------------------------------------


def _core_inputs(T, H=4, G=2, P=8, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (2, H, T, P))
    b = jax.random.normal(ks[1], (2, G, T, N))
    c = jax.random.normal(ks[2], (2, G, T, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (2, H, T)) - 2.0)
    a = -jax.random.uniform(ks[4], (H,), minval=1.0, maxval=16.0)
    d = jax.random.normal(ks[5], (H,))
    return x, b, c, dt, a, d


def _recurrence(x, b, c, dt, a, d):
    """The reference's token-by-token rule on (B, H, T, .) arrays."""
    tokens_first = lambda v: v.transpose(1, 0, 2)
    return jnp.stack([
        reference.ssm_recurrence(
            tokens_first(x[i]), tokens_first(b[i]), tokens_first(c[i]),
            dt[i].T, a, d,
        ).transpose(1, 0, 2)
        for i in range(x.shape[0])
    ])


@pytest.mark.parametrize("groups", [1, 2, 4])
@pytest.mark.parametrize("length", [128, 200, 384])
def test_chunked_core_against_the_recurrence(length, groups):
    """Forward and the gradient by every input, at lengths that are and are
    not whole chunks, with four, two and one head a group."""
    v = _core_inputs(length, G=groups)
    co = jax.random.normal(jax.random.PRNGKey(9), v[0].shape)
    # each side ONE compiled function (an eager walk compiles every
    # operation by itself: ROADMAP D14)
    both = lambda f: jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *b: jnp.sum(f(*b) * co), argnums=tuple(range(6))
    )(*a)))(*v)
    (got, got_grads), (want, want_grads) = both(ssd.ssd_chunked), both(_recurrence)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    _close(got, want, 2e-5)
    for name, a, b in zip("x B C dt A D".split(), got_grads, want_grads):
        _close(a, b, 1e-4), name


def test_the_chunk_is_the_callers_and_a_long_decay_stays_finite():
    v = _core_inputs(96)
    want = jax.jit(_recurrence)(*v)
    for chunk in (16, 32, 96, 128):
        core = jax.jit(lambda *a: ssd.ssd_chunked(*a, chunk=chunk))
        _close(core(*v), want, 2e-5)
    assert ssd.CHUNK == 128
    # steps of 40 at a rate of -16: a chunk's decay sums to -81,920, whose
    # exponential is 0 in float32, forward and backward
    x, b, c, dt, a, d = v
    hard = (x, b, c, jnp.full_like(dt, 40.0), jnp.full_like(a, -16.0), d)
    got = ssd.ssd_chunked(*hard)
    _close(got, _recurrence(*hard), 2e-5)
    grads = jax.grad(lambda *a: jnp.sum(ssd.ssd_chunked(*a)), argnums=(0, 3, 4))(
        *hard
    )
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    with pytest.raises(ValueError, match="whole groups"):
        ssd.ssd_chunked(x[:, :3], b, c, dt[:, :3], a[:3], d[:3])


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
    update IS the gradient (to the float32 spacing of a weight of about 5,
    which ``ULP`` allows for).  tp splits the Mamba-2 heads AND their B/C
    groups together, and the two KV heads: it falls out of the specs."""
    params, (tok, tgt) = _params(), _batch()
    step, shard = make_sharded_train_step(CFG, _mesh(tp), lr=1.0)
    new, loss = step(shard(params), tok, tgt)
    want_loss, want = _seeded_reference_grads()
    _close(loss, want_loss, 1e-5)
    got = driver.reference_weights(
        jax.tree.map(lambda p, n: p - n, params, jax.device_get(new))
    )
    for name in ("embeddings", "norm_f", "lm_head"):
        _close(got[name], want[name], 2e-4, ULP)
    for got_l, want_l in zip(got["layers"], want["layers"]):
        assert set(got_l) == set(want_l)
        for name in want_l:
            if name == "expert_bias":
                continue    # outside the gradient: moved by its own rule
            _close(got_l[name], want_l[name], 2e-4, ULP), name


def test_remat_recomputes_the_same_step():
    """On the mixers and a dense relu2 block: off the TPU the held experts'
    Pallas kernels run interpreted, through host callbacks, which
    ``jax.checkpoint`` refuses."""
    cfg = _no_experts("M*M-")
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


def test_a_dense_relu2_block_is_relu_squared():
    cfg = _no_experts("-")
    params = _params(cfg)
    tok, _ = _batch()
    fwd, shard = make_sharded_forward(cfg, _mesh(1))
    lp = params["layers"][0]
    assert set(lp) == {"ln2", "w1", "w2"}
    h = reference.embed({"embeddings": params["embed"]}, tok)
    h = h + reference.relu2(
        reference.rms_norm(h, lp["ln2"]) @ lp["w1"]
    ) @ lp["w2"]
    want = reference.rms_norm(h, params["ln_f"]) @ params["head"]
    _close(fwd(shard(params), tok), want)


@pytest.mark.parametrize("how,where", [
    (dict(mamba_how=dict(no_decay=True)), "the decay left out"),
    (dict(mamba_how=dict(no_skip=True)), "D left out"),
    (dict(mamba_how=dict(no_conv=True)), "the convolution left out"),
    (dict(mamba_how=dict(norm_before_gate=True)), "the norm before the gate"),
    (dict(moe_how=dict(plain_relu=True)), "relu for its square"),
    (dict(moe_how=dict(no_latent=True)), "the latent left out"),
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


def test_a_block_of_one_sub_layer_has_one_norm_in_the_tree():
    specs = param_specs(CFG)["layers"]
    shapes = jax.eval_shape(
        lambda k: init_params(k, CFG), jax.random.PRNGKey(0)
    )["layers"]
    mamba = {
        "wz", "wx", "wb", "wc", "wdt", "conv_x", "conv_b", "conv_c",
        "bias_x", "bias_b", "bias_c", "dt_bias", "a_log", "d_skip", "y_norm",
        "wo", "ln1",
    }
    assert set(shapes[0]) == set(specs[0]) == mamba              # M
    assert set(shapes[1]) == set(specs[1]) == {"ln2", "moe"}     # E
    assert set(shapes[3]) == set(specs[3]) == {"wq", "wk", "wv", "wo", "ln1"}
    assert shapes[0]["wx"].shape == shapes[0]["wz"].shape == (64, 8 * 8)
    assert shapes[0]["wb"].shape == shapes[0]["wc"].shape == (64, 2 * 16)
    assert shapes[0]["wdt"].shape == (64, 8)
    assert shapes[0]["conv_b"].shape == (4, 2 * 16)
    assert shapes[0]["y_norm"].shape == shapes[0]["bias_x"].shape == (64,)
    assert shapes[0]["d_skip"].shape == shapes[0]["a_log"].shape == (8,)
    assert shapes[3]["wk"].shape == (64, 2 * 16)     # two KV heads of 16
    moe = shapes[1]["moe"]
    assert set(moe) == set(specs[1]["moe"]) == {
        "gate", "w1", "w2", "bias", "shared", "w_down", "w_up",
    }
    # the routed experts in the latent, two matrices; the shared expert on
    # the hidden state, two matrices
    assert moe["w1"].shape == (4, 32, 48) and moe["w2"].shape == (4, 48, 32)
    assert moe["w_down"].shape == (64, 32) and moe["w_up"].shape == (32, 64)
    assert set(moe["shared"]) == {"w1", "w2"}
    assert moe["shared"]["w1"].shape == (64, 80)
    assert moe["gate"].shape == (64, 16)
    # the family's initialisation: the step between dt_min and dt_max
    lp = init_params(jax.random.PRNGKey(0), CFG)["layers"][0]
    dt = np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert (dt >= 0.001 - 1e-6).all() and (dt <= 0.1 + 1e-6).all()
    assert np.array_equal(np.asarray(lp["d_skip"]), np.ones(8, np.float32))


# -- the pattern and the configuration file -------------------------------------


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_pattern_from_hybrid_override_pattern(rehearse):
    cell = manifest.cell(
        manifest.load(), "train_nemotron3_t8192_b1", rehearse=rehearse
    )
    config = cell["config"]
    cfg = driver.program_config(config)
    letters = driver.layer_letters(config)
    assert letters == "".join(
        config["hybrid_override_pattern"][i] for i in config["layers_kept"]
    )
    want = {"M": ("mamba2", "none"), "E": ("none", "moe"), "*": ("attention", "none")}
    assert [(cfg.mixer(k), k.ffn) for k in cfg.layers] == [want[c] for c in letters]
    assert not any(k.rope for k in cfg.layers)
    assert "pos" not in param_specs(cfg)
    if rehearse:
        assert letters == "MEM*E"
        return
    # published blocks 27..37: one whole period, 5 : 5 : 1
    assert letters == "MEMEMEMEM*E" and config["layers_kept"] == list(range(27, 38))
    whole = config["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"), whole.count("*")) == (
        88, 40, 40, 8
    )
    assert whole[0] == "M"
    assert cfg.mamba == Mamba2(128, 64, 128, 8, 4, 128, 0.001, 0.1, 1e-4)
    assert (cfg.n_heads, cfg.kv_heads(), cfg.head_size()) == (32, 2, 128)
    assert (cfg.moe_top_k, cfg.n_experts, cfg.router_experts()) == (22, 64, 512)
    assert (cfg.moe_latent, cfg.moe_shared_d_ff, cfg.d_ff) == (1024, 5376, 2688)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_route_scale) == (1, 1, 5.0)
    assert cfg.ffn == "relu2" and cfg.remat and not cfg.tie_head
    with pytest.raises(ValueError, match="unknown block"):
        hybrid_layers("MEX")
    with pytest.raises(ValueError, match="layers_kept"):
        driver.layer_letters(dict(config, num_hidden_layers=12))


def test_the_configuration_file_against_the_catalog():
    """Every number of the catalog's row is in the file under its key, but
    the four keys of ``reduced``, whose published values the file states."""
    with open(os.path.join(
        manifest.CHECKOUT, "perfbench/configs/nemotron3_super_train.json"
    )) as f:
        config = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(
                r for r in map(json.loads, f)
                if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
            )
        assert config["source"] == row["source_url"]
        differ = {
            k for k, v in row["config"].items() if config.get(k, "absent") != v
        }
        assert differ == set(config["reduced"])
        assert config["published"] == {k: row["config"][k] for k in differ}
    assert config["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072, "num_nextn_predict_layers": 1,
    }
    assert set(config["reduced"]) == set(config["published"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        11, 64, 16384, 0)
    for item in (
        "block_rule", "mamba_mixer", "dt_clamp", "gate_before_norm", "groups",
        "attention", "router", "latent_moe", "rescale_prenorm_residual",
        "initializer_range",
    ):
        assert config["assumed"][item], item
    assert "8 chips share each layer" in config["deployment"]
    assert "memory_analysis" in config["memory"]
    assert config["program"]["_remat_why"]
    # the widths are the published ones
    assert (config["hidden_size"], config["mamba_num_heads"],
            config["mamba_head_dim"], config["ssm_state_size"],
            config["n_groups"], config["moe_latent_size"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"], config["head_dim"]) == (
        4096, 128, 64, 128, 8, 1024, 2688, 5376, 22, 128)
    # the issue's count: one period with 64 experts and 16,384 rows
    shapes = jax.eval_shape(
        lambda k: init_params(k, driver.program_config(config)),
        jax.random.PRNGKey(0),
    )
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    matmul = flops_nemotron3.resident_matmul_params(config) + 5 * 64 * (
        flops_nemotron3.expert_params(config)
    )
    assert n == 2_752_338_304 and matmul == 2_684_878_848
    # the rest: the embedding, and 63,872 a Mamba-2 block (taps, biases,
    # scalars, the two norms), 4,608 an expert block (its norm, the bias),
    # the attention block's norm and the final one
    assert n - matmul - 4096 * 16384 == 5 * 63_872 + 5 * 4_608 + 2 * 4_096


# -- routing --------------------------------------------------------------------


def _bank(held=16, first=0, shared=True, seed=3):
    """A latent bank of ``held`` of 16 experts, cut from ONE seeded whole."""
    whole = init_moe_params(
        jax.random.PRNGKey(seed), 64, 48, 16, shared_d_ff=80, latent=32,
    )
    whole["gate"] = whole["gate"] * 8.0     # decided routing
    bank = {k: whole[k][first:first + held] for k in ("w1", "w2")}
    bank.update({k: whole[k] for k in ("gate", "w_down", "w_up")})
    bank["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(seed + 1), (16,))
    if shared:
        bank["shared"] = whole["shared"]
    return bank


def _as_reference(bank):
    return {
        "gate": bank["gate"], "expert_bias": bank["bias"],
        "fc1_latent_proj": bank["w_down"], "fc2_latent_proj": bank["w_up"],
        "experts.up_proj": bank["w1"], "experts.down_proj": bank["w2"],
        "shared_experts.up_proj": bank["shared"]["w1"],
        "shared_experts.down_proj": bank["shared"]["w2"],
    }


ROUTE = dict(capacity_factor=None, k=3, renormalize=True, route_scale=5.0,
             router="sigmoid", relu2=True)
REF_ROUTE = dict(top_k=3, routed_scaling_factor=5.0)


def _x(seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 64))


def test_latent_bank_against_the_reference():
    bank, x = _bank(), _x()
    got, aux = moe_ffn(x, bank, return_aux=True, **ROUTE)
    want, picked = reference.latent_moe(
        x.reshape(-1, 64), _as_reference(bank), **REF_ROUTE
    )
    _close(got.reshape(-1, 64), want)
    counts, _ = reference.routing_facts(picked, 3)
    assert np.array_equal(aux["expert_tokens"], counts)
    # relu2 is no GELU, and the latent is no fixed capacity's
    gelu = moe_ffn(x, bank, **{**ROUTE, "relu2": False})
    assert np.abs(np.asarray(gelu - got)).max() > 1e-3
    with pytest.raises(ValueError, match="latent bank"):
        moe_ffn(x, bank, **{**ROUTE, "capacity_factor": 1.5})


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' held parts sum to what the uncut reference gives for
    the whole layer, the shared expert counted once; ``W_up`` is linear, so
    the parts add up after it as they do before it (the published model's
    eight shares of 64 are four of 4 here)."""
    x = _x()
    total, latent_total, held = 0.0, 0.0, 0
    for g in range(4):
        bank = _bank(held=4, first=4 * g)
        if g:
            del bank["shared"]
        y, aux = moe_ffn(x, bank, return_aux=True, first_expert=4 * g,
                         held_row_factor=8.0, **ROUTE)
        assert int(aux["dropped"]) == 0
        held += int(aux["held_entries"])
        total = total + y
        part, _ = reference.latent_moe(
            x.reshape(-1, 64), _as_reference(_bank(held=4, first=4 * g)),
            first_expert=4 * g, shared=False, up=False, **REF_ROUTE
        )
        latent_total = latent_total + part
    assert held == 2 * T * 3            # every entry is held by one share
    whole = _as_reference(_bank())
    want, _ = reference.latent_moe(x.reshape(-1, 64), whole, **REF_ROUTE)
    _close(total.reshape(-1, 64), want)
    routed, _ = reference.latent_moe(
        x.reshape(-1, 64), whole, shared=False, **REF_ROUTE
    )
    _close(latent_total @ whole["fc2_latent_proj"], routed)


def test_router_probe_counts_against_the_reference():
    params, (tok, _) = _params(), _batch()
    probe = make_sharded_router_probe(CFG, _mesh(1))
    _, shard = make_sharded_forward(CFG, _mesh(1))
    got = probe(shard(params), tok)
    weights = driver.reference_weights(params)
    _, picked = reference.hidden(weights, tok, **REF)
    assert len(picked) == 2                      # the two expert blocks
    counts = np.stack([
        np.asarray(reference.routing_facts(p, 3)[0]) for p in picked
    ])
    assert np.array_equal(got["expert_tokens"], counts)
    assert np.array_equal(got["held_entries"], counts[:, 4:8].sum(axis=1))
    assert int(np.asarray(got["dropped"]).sum()) == 0
    assert "group_tokens" not in got             # no group limit


# -- the scopes ---------------------------------------------------------------------


def test_the_new_mechanisms_run_under_their_device_scopes():
    new = ("accl.attn::ssd", "accl.attn::mamba_proj", "accl.moe::latent")
    for scope in new:
        assert f"``{scope}``" in profiling.__doc__, scope
    params, (tok, tgt) = _params(), _batch()
    step, shard = make_sharded_train_step(CFG, _mesh(1), lr=1.0)
    text = step.lower(shard(params), tok, tgt).compile().as_text()
    # every computation of the step's text: the core's scan over the chunks
    # is a loop, which the entry computation alone does not show
    found = scoped_instructions(text)
    for scope in new + ("accl.attn::core", "accl.moe::route",
                        "accl.moe::experts", "accl.moe::shared"):
        assert found.get(scope), scope
    entry = scope_ops.scopes_of(text)
    assert set(entry.get("accl.attn::ssd", ())) < set(found["accl.attn::ssd"])


# -- the refusals, by name --------------------------------------------------------


@pytest.mark.parametrize("pattern,match", [
    ("MM", "Mamba-2 mixer"), ("*-", "block of one sub-layer"),
])
@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_block_refuse_it_by_name(
    path, pattern, match
):
    dense = _no_experts(pattern)
    params = init_params(jax.random.PRNGKey(0), dense)
    tok, _ = _batch()
    with pytest.raises(ValueError, match=match):
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


M, E = hybrid_layers("ME", moe_d_ff=48)


@pytest.mark.parametrize("change,match", [
    (dict(layers=hybrid_layers("*E*E*", moe_d_ff=48)), "Mamba-2 mixer"),
    (dict(mamba=Mamba2(8, 8, 16, 3)), "Mamba-2 mixer"),     # 8 heads, 3 groups
    (dict(mamba=Mamba2(8, 8, 16, 2, dt_min=0.2)), "Mamba-2 mixer"),
    (dict(mamba=None), "needs TransformerConfig.mamba"),
    (dict(layers=(dataclasses.replace(M, window=8), E, M, M, E)),
     "has no window"),
    (dict(layers=(LayerKind(mixer="none", ffn="none"), E, M, M, E)),
     "a mixer, an FFN or both"),
    (dict(layers=(LayerKind(mixer="mamba"), E, M, M, E)), "unknown mixer"),
    (dict(layers=(dataclasses.replace(M, ffn="relu2"), E, M, M, E)),
     "unknown ffn"),
    (dict(ffn="relu"), "unknown ffn"),
    (dict(moe_capacity_factor=1.5), "latent expert bank"),
])
def test_a_configuration_that_cannot_hold_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


def test_tp_must_take_whole_groups():
    cfg = _no_experts("MM")
    odd = dataclasses.replace(cfg, mamba=Mamba2(6, 8, 16, 3, chunk=32))
    step, shard = make_sharded_train_step(odd, _mesh(2), lr=1.0)
    tok, tgt = _batch()
    with pytest.raises(ValueError, match="whole groups of heads"):
        step.lower(
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), odd)),
            tok, tgt,
        )


def test_block_diffusion_refuses_the_block():
    for pattern in ("MM", "*-"):
        cfg = _no_experts(pattern)
        with pytest.raises(ValueError, match="Mamba-2 mixer or block of one"):
            dataclasses.replace(
                cfg, diffusion=BlockDiffusion(block=4, mask_id=255)
            )


# -- the benchmark's check of the update ------------------------------------------


def _moved(before, after, probed, grads, lr=0.5):
    """``Driver._moved`` on one leaf: elements in play, the share of them
    the timed step left where no rounding of the update puts them, and the
    probe step's update off the gradient's."""
    import types

    play, off, far, size = driver.Driver._moved(
        types.SimpleNamespace(traffic={"lr": lr}), "",
        {"w": before}, {"w": after}, {"w": probed}, {"w": grads},
    )["w"]
    return play, off / play if play else None, (far / size) ** 0.5


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_an_update_is_judged_as_far_as_the_weights_spacing_lets_it_show(dtype):
    """SGD on bf16 weights rounds an update under half a spacing of the
    weight away.  The timed step: an element is IN PLAY where it changed or
    where ``-lr grad``, a tenth more or less, takes it to another value of
    its type; the step's own update leaves none of them where that
    rounding cannot, a state left unchanged most, a gradient of the other
    sign all that changed.  The probe step, at a rate that makes the update
    the gradient: off by the type's rounding, by 1 unchanged, by 2 with the
    other sign."""
    key = jax.random.PRNGKey(0)
    rate = driver.UPDATE_PROBE_RATE
    grads = jax.random.normal(key, (4096,), jnp.float32)
    # a quarter at each of four sizes: an update of 0.5 is 2^15, 2^9, 2^3
    # and 2^-3 bf16 spacings of them
    w = (jnp.repeat(2.0 ** -jnp.arange(8, -16, -6), 1024)
         * (1 + jax.random.uniform(key, (4096,)) / 2)).astype(dtype)
    step = lambda rate, g: (w - rate * g.astype(dtype)).astype(dtype)
    play, off, far = _moved(w, step(0.5, grads), step(rate, grads), grads)
    assert off == 0.0 and far < 2.0 ** -7
    if dtype == jnp.float32:
        assert play >= 4000
    else:
        # the large quarter's update is lost whole, and of the next quarter
        # those of a sixteenth of a spacing or less: not in play
        assert 2900 <= play <= 3072
    unchanged = _moved(w, w, w, grads)
    assert unchanged[0] <= play and unchanged[1] > 0.9 and unchanged[2] == 1.0
    other = _moved(w, step(0.5, -grads), step(rate, -grads), grads)
    assert other[1] > 0.9 and abs(other[2] - 2.0) < 0.01
    # a gradient a fifth too large is past the tenth; a twentieth is inside
    assert _moved(w, step(0.5, 1.2 * grads), w, grads)[1] > 0.05
    assert _moved(w, step(0.5, 1.05 * grads), w, grads)[1] < 0.01


def test_fp8_weights_keep_the_expert_bias():
    tree = {"moe": {"bias": jnp.float32(0.3), "w1": jnp.float32(0.3)},
            "bias_x": jnp.bfloat16(0.3)}
    got = driver.fp8(tree)
    assert got["moe"]["bias"] == tree["moe"]["bias"]
    assert got["moe"]["w1"] == 0.3125 and got["bias_x"] == 0.3125
    assert got["bias_x"].dtype == jnp.bfloat16
