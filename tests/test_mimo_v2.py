"""The mimo_v2 block of ``accl_tpu.models`` (MiMo-V2.5: sliding layers whose
softmax has a learned sink a query head beside full layers with half their
KV heads; heads of which the FIRST columns rotate, v heads of another width
times a value scale; a rope base by layer kind; sigmoid top-k with a held
share) against the plain float32 reference of ``perfbench/reference/
mimo_v2.py``, at small sizes on the CPU mesh with seeded weights, in all
three attention lowerings (the flash kernels interpreted, with the second
score part and a window narrower than their tile); and the sink in the flash
kernels against autodiff of the naive form.  Float32 against float32 is held
to 1e-4 of the largest value."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    BlockDiffusion,
    HeadGeometry,
    LayerKind,
    TransformerConfig,
    encoder_forward,
    generate,
    init_params,
    make_pp_train_step,
    make_sharded_forward,
    make_sharded_generate,
    make_sharded_train_step,
)
from accl_tpu.models.moe import init_moe_params, moe_ffn
from accl_tpu.models.transformer import _attention, param_specs
from accl_tpu.ops.attention import blockwise_attention
from accl_tpu.ops.pallas.attention import flash_attention
from accl_tpu.utils import profiling
from perfbench import flops_mimo, manifest
from perfbench.drivers import train_steps_mimo as driver
from perfbench.drivers.train_steps_ling3 import scoped_instructions
from perfbench.reference import mimo_v2 as reference

T, WINDOW = 80, 16      # the flash tile is the sequence: the window is under it
ULP = 5e-7
GEOMETRY = HeadGeometry(rope_dim=8, v_dim=16, v_scale=0.707)
FULL = dict(window=None, kv_heads=2, rope_base=1e7, sink=False, heads=GEOMETRY)
SWA = dict(window=WINDOW, kv_heads=4, rope_base=1e4, sink=True, heads=GEOMETRY)
PATTERN = (0, 1, 1, 0)
#: four heads of 24 (8 rotate) | 16 on 2 (full) and 4 (sliding) KV heads; a
#: dense layer first; 4 of 16 experts held (the second of four shares), top 4
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=24, n_layers=4,
    layers=(
        LayerKind(ffn="dense", d_ff=96, **FULL),
        LayerKind(ffn="moe", d_ff=32, **SWA),
        LayerKind(ffn="moe", d_ff=32, **SWA),
        LayerKind(ffn="moe", d_ff=32, **FULL),
    ),
    d_ff=32, max_seq=128, pos_embedding="rope", rope_base=1e7, norm="rmsnorm",
    ffn="swiglu", tie_head=False, n_experts=4, moe_top_k=4,
    moe_capacity_factor=None, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_router="sigmoid", moe_bias_rate=0.001, moe_router_experts=16,
    moe_first_expert=4, moe_held_row_factor=4.0, attention="naive",
)
REF = dict(
    n_head=4, head_dim=24, rotary=8, thetas=(1e7, 1e4), window=WINDOW,
    v_scale=0.707, top_k=4, first_expert=4, q_block=32,
)
LOWERINGS = ("naive", "blockwise", "flash")
#: the same mixers on dense MLPs (for what the held experts' interpreted
#: kernels cannot run under, and for the refusals)
DENSE = dataclasses.replace(
    CFG, n_experts=0, moe_router="softmax", moe_bias_rate=0.0,
    moe_router_experts=None, moe_first_expert=0, moe_capacity_factor=1.5,
    layers=tuple(
        dataclasses.replace(k, ffn="dense", d_ff=96) for k in CFG.layers
    ),
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with matrices larger than the init's (so that the
    scores are no longer near 0 and the window, the rotation and the sink
    matter), norm scales not all one, a selection bias that is not zero and
    sinks spread over [-1, 3]."""

    def larger(path, p):
        name = path[-1].key
        if name == "sink":
            return jnp.linspace(-1.0, 3.0, p.shape[0]).astype(p.dtype)
        if p.ndim == 1:
            return p + 0.1 * jax.random.normal(
                jax.random.PRNGKey(p.size), p.shape, p.dtype
            )
        return p * 4.0 if name in ("wq", "wk", "wv", "wo") else p

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


# -- the sink in the three lowerings ---------------------------------------------


def _core_inputs(seed=0, B=1, H=4, Hkv=2, length=96, dn=16, dr=8, dv=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = lambda h, d: (B, h, length, d)
    return (
        jax.random.normal(ks[0], shape(H, dn)),
        jax.random.normal(ks[1], shape(Hkv, dn)),
        jax.random.normal(ks[2], shape(Hkv, dv)),
        jax.random.normal(ks[3], shape(H, dr)),
        jax.random.normal(ks[4], shape(Hkv, dr)),
    ), jax.random.normal(ks[5], shape(H, dv))


@pytest.mark.parametrize("window", [None, 1, 31, 32, 33])
def test_the_sink_in_the_flash_kernels_against_autodiff_of_the_naive_form(window):
    """Tiles of 32 in a sequence of 96: a window of 1 key, of ``b - 1``,
    ``b`` and ``b + 1``, and none; the second score part on the KV heads; a
    sink at -30 (out of every row's softmax), 0 and +8 (most of it).  The
    forward only starts its fold from another carry and the backward kernel
    is the parent's: ``o`` and all six gradients, ``d sink`` among them,
    against ``jax.grad`` of the materialised softmax with one more column;
    the blockwise fold beside them."""
    (q, k, v, qr, kr), w = _core_inputs()

    def both(attend):
        return jax.jit(jax.value_and_grad(
            lambda *a: (attend(*a) * w).sum(), argnums=tuple(range(6))
        ))

    def lowered(impl):
        return lambda q, k, v, qr, kr, sink: _attention(
            q, k, v, impl=impl, window=window, q_rope=qr, k_rope=kr, sink=sink
        )

    kernels = lambda q, k, v, qr, kr, sink: flash_attention(
        q, k, v, window=window, q_rope=qr, k_rope=kr, sink=sink, block=32,
        interpret=True,
    )
    naive, others = both(lowered("naive")), [
        both(kernels), both(lowered("blockwise"))
    ]
    for level in (-30.0, 0.0, 8.0):
        sink = level + 0.25 * jnp.arange(4.0)
        want_o, want = naive(q, k, v, qr, kr, sink)
        for attend in others:
            got_o, got = attend(q, k, v, qr, kr, sink)
            _close(got_o, want_o, 1e-5)
            for name, a, b in zip(("q", "k", "v", "qr", "kr", "sink"), got, want):
                _close(a, b, 2e-5, 1e-6), (name, level)
        if level == -30.0:
            # a sink that far down is no sink at all
            none = jax.jit(lambda *a: flash_attention(
                *a[:3], window=window, q_rope=a[3], k_rope=a[4], block=32,
                interpret=True,
            ))(q, k, v, qr, kr)
            _close(jax.jit(kernels)(q, k, v, qr, kr, sink), none, 1e-5)


def test_a_lowering_refuses_a_sink_it_cannot_hold():
    (q, k, v, _, _), _ = _core_inputs(length=32)
    for attend in (flash_attention, blockwise_attention):
        with pytest.raises(ValueError, match="one scalar a query head"):
            attend(q, k, v, sink=jnp.zeros(2))
        with pytest.raises(ValueError, match="causal"):
            attend(q, k, v, causal=False, sink=jnp.zeros(4))
    with pytest.raises(ValueError, match="block-diffusion"):
        flash_attention(q, k, v, block_diffusion=(16, 4), sink=jnp.zeros(4))


# -- the whole model ------------------------------------------------------------


def _reference_logits(weights, tok, **how):
    """The reference's logits, its layers broken by ``how``."""

    @jax.jit
    def logits(weights):
        h, _ = reference.hidden(
            weights, tok, pattern=PATTERN, **dict(REF, **how)
        )
        return reference.head(weights, h)

    return logits(weights)


@pytest.fixture(scope="module")
def reference_side():
    """Seeded weights, a batch, and the reference's logits, loss and
    gradients of it, each ONE compiled function."""
    with jax.default_matmul_precision("highest"):
        params, (tok, tgt) = _params(), _batch()
        weights = driver.reference_weights(params)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda w: reference.loss(w, tok, tgt, pattern=PATTERN, **REF)
        ))(weights)
        return dict(
            params=params, tok=tok, tgt=tgt, weights=weights, loss=loss,
            grads=grads, logits=np.asarray(_reference_logits(weights, tok)),
        )


@pytest.fixture(scope="module")
def forward(reference_side):
    """The program's logits of the batch, by lowering."""
    out = {}
    with jax.default_matmul_precision("highest"):
        for impl in LOWERINGS:
            fwd, shard = make_sharded_forward(
                dataclasses.replace(CFG, attention=impl), _mesh(1)
            )
            out[impl] = np.asarray(
                fwd(shard(reference_side["params"]), reference_side["tok"])
            )
    return out


@pytest.mark.parametrize("impl", LOWERINGS)
def test_logits_against_the_reference(impl, forward, reference_side):
    _close(forward[impl], reference_side["logits"])


def test_the_batch_works_the_sink(reference_side):
    """A sink that holds nothing would let a program without one pass."""
    weights, tok = reference_side["weights"], reference_side["tok"]
    h = reference.embed(weights, tok)
    h, _, _ = reference.layer(h, weights["layers"][0], swa=False, **REF)
    _, _, p_sink = reference.layer(h, weights["layers"][1], swa=True, **REF)
    facts = reference.sink_facts(p_sink, WINDOW)
    assert float(facts["quantiles"][0]) > 0.01
    assert float(facts["quantiles"][-1]) > 0.5
    assert float(facts["mean_window_filling"]) > float(facts["mean_window_full"])


@pytest.mark.parametrize("impl", LOWERINGS)
@pytest.mark.parametrize("tp", [1, 2])
def test_loss_and_gradients_against_the_reference(tp, impl, reference_side):
    """Through ``make_sharded_train_step`` itself: at lr 1 the step's update
    IS the gradient (to the float32 spacing of a weight, which ``ULP``
    allows for), the sinks' among them.  tp 2 splits the query heads two and
    two, the sliding layers' KV heads two and two, the full layers' one and
    one, and the sinks with their heads."""
    r = reference_side
    step, shard = make_sharded_train_step(
        dataclasses.replace(CFG, attention=impl), _mesh(tp), lr=1.0
    )
    new, loss = step(shard(r["params"]), r["tok"], r["tgt"])
    _close(loss, r["loss"], 1e-5)
    got = driver.reference_weights(
        jax.tree.map(lambda p, n: p - n, r["params"], jax.device_get(new))
    )
    want = r["grads"]
    for name in ("embed_tokens", "norm", "lm_head"):
        _close(got[name], want[name], 2e-4, ULP)
    assert any("attention_sink_bias" in layer for layer in want["layers"])
    for got_l, want_l in zip(got["layers"], want["layers"]):
        assert set(got_l) == set(want_l)
        for name in want_l:
            if name == "e_score_correction_bias":
                continue    # outside the gradient: moved by its own rule
            _close(got_l[name], want_l[name], 2e-4, ULP), name


def test_remat_recomputes_the_same_step(reference_side):
    """On the mixers and dense MLPs: off the TPU the held experts' Pallas
    kernels run interpreted, through host callbacks, which
    ``jax.checkpoint`` refuses (the cell's ``rehearsal`` block says so)."""
    r, cfg = reference_side, DENSE
    params = _params(cfg)
    step, shard = make_sharded_train_step(cfg, _mesh(1), lr=1.0)
    again, _ = make_sharded_train_step(
        dataclasses.replace(cfg, remat=True), _mesh(1), lr=1.0
    )
    (new, loss), (new_r, loss_r) = (
        s(shard(params), r["tok"], r["tgt"]) for s in (step, again)
    )
    _close(loss_r, loss, 1e-6)
    for a, b in zip(jax.tree.leaves(new_r), jax.tree.leaves(new)):
        _close(a, b, 1e-5, ULP)


@pytest.mark.parametrize("how,where", [
    (dict(sink="none"), "no sink"),
    (dict(sink="full_too"), "a sink on the full layers"),
    (dict(sink="valued"), "the sink with a value"),
    (dict(window=WINDOW - 1), "a window one key short"),
    (dict(window=WINDOW + 1), "a window one key long"),
    (dict(rotate="all"), "every column rotating"),
    (dict(rotate="last"), "the last columns rotating"),
    (dict(thetas=(1e4, 1e7)), "the thetas swapped"),
    (dict(v_scale=1.0), "no value scale"),
    (dict(pair_kv=True), "the full layers' KV heads in both kinds"),
])
def test_a_broken_reference_is_told_apart(how, where, forward, reference_side):
    broken = np.asarray(_reference_logits(
        reference_side["weights"], reference_side["tok"], **how
    ))
    # ten times what ``_close`` allows the right one
    got = forward["naive"]
    assert np.abs(got - broken).max() > 1e-3 * np.abs(broken).max(), where


def test_the_shares_of_all_sixteen_chips_add_up_to_the_layer():
    """THE SHARE TEST.  32 experts in sixteen shares of two: each share's
    part (the program's ``moe_ffn`` on a bank of two with the whole router,
    and the reference given the same range; no shared expert to count once)
    adds up to the uncut 32-expert reference of the whole layer."""
    d, f, E, k = 64, 32, 32, 8
    bank = init_moe_params(jax.random.PRNGKey(3), d, f, E, gated=True, bias=True)
    bank["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (E,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, d))
    m = x.reshape(2 * T, d)

    def names(moe):
        return {
            "router": moe["gate"], "e_score_correction_bias": moe["bias"],
            "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
            "experts.down_proj": moe["w2"],
        }

    whole, _ = jax.jit(lambda m: reference.moe(m, names(bank), top_k=k))(m)

    program, summed, counts = 0.0, 0.0, 0
    for r in range(16):
        share = {key: bank[key][2 * r:2 * r + 2] for key in ("w1", "w2", "w3")}
        share.update(gate=bank["gate"], bias=bank["bias"])
        y, aux = moe_ffn(
            x, share, capacity_factor=None, k=k, return_aux=True,
            router="sigmoid", first_expert=2 * r, held_row_factor=16.0,
        )
        assert int(aux["dropped"]) == 0
        counts += int(aux["held_entries"])
        program = program + y.reshape(2 * T, d)
        part, _ = reference.moe(m, names(share), top_k=k, first_expert=2 * r)
        _close(y.reshape(2 * T, d), part)
        summed = summed + part
    assert counts == 2 * T * k      # every entry is held by exactly one share
    _close(summed, whole, 1e-5)
    _close(program, whole)


# -- the tree, the file, the counts -------------------------------------------------


def test_the_trees_are_the_two_kinds():
    specs = param_specs(CFG)["layers"]
    shapes = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.PRNGKey(0))
    full, swa = shapes["layers"][0], shapes["layers"][1]
    mixer = {"wq", "wk", "wv", "wo", "ln1", "ln2"}
    assert set(full) == mixer | {"w1", "w2", "w3"}
    assert set(swa) == mixer | {"sink", "moe"}
    assert "sink" not in shapes["layers"][3]
    assert swa["sink"].shape == (4,) and swa["sink"].dtype == jnp.float32
    # K/V heads by kind, v of its own width, wo from heads of that width
    assert (full["wq"].shape, swa["wq"].shape) == ((64, 96), (64, 96))
    assert (full["wk"].shape, swa["wk"].shape) == ((64, 48), (64, 96))
    assert (full["wv"].shape, swa["wv"].shape) == ((64, 32), (64, 64))
    assert full["wo"].shape == (64, 64) == swa["wo"].shape
    for s, layer in zip(specs, shapes["layers"]):
        assert set(s) == set(layer)
    assert tuple(specs[1]["sink"]) == ("tp",)      # with its heads
    # nothing of this on the config: what differs by kind is the kind's
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert not fields & {"sink", "kv_heads", "rope_dim", "v_dim", "v_scale"}
    assert len(fields) == 49


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_driver_maps_every_published_key(rehearse):
    cell = manifest.cell(
        manifest.load(), "train_mimo_t8192_b1", rehearse=rehearse
    )
    config = cell["config"]
    cfg = driver.program_config(config)
    kinds = flops_mimo.layer_kinds(config)
    assert [k.window is not None for k in cfg.layers] == [s for s, _ in kinds]
    assert [k.ffn == "moe" for k in cfg.layers] == [m for _, m in kinds]
    assert kinds[0] == (False, False) and kinds[-1] == (False, True)
    for kind in cfg.layers:
        swa = kind.window is not None
        assert kind.sink == swa and kind.rope and kind.mixer is None
        assert kind.rope_base == (1e4 if swa else 1e7)
        assert kind.kv_heads == config[
            "swa_num_key_value_heads" if swa else "num_key_value_heads"
        ]
        assert kind.heads == cfg.layers[0].heads
    assert cfg.moe_router == "sigmoid" and cfg.moe_norm_topk_prob
    assert cfg.moe_route_scale == 1.0 and not cfg.moe_shared_d_ff
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (1, 1)
    assert not cfg.qk_norm and not cfg.attn_gate and not cfg.tie_head
    assert cfg.remat == (not rehearse)
    if not rehearse:
        assert [s for s, _ in kinds] == [False] + [True] * 5 + [False]
        assert cfg.layers[0].heads == HeadGeometry(64, 128, 0.707)
        assert (cfg.n_heads, cfg.head_size(), cfg.d_model) == (64, 192, 4096)
        assert [k.kv_heads for k in cfg.layers] == [4, 8, 8, 8, 8, 8, 4]
        assert [k.window for k in cfg.layers] == [None] + [128] * 5 + [None]
        assert [k.d_ff for k in cfg.layers] == [16384] + [2048] * 6
        assert (cfg.n_experts, cfg.router_experts(), cfg.moe_top_k) == (16, 256, 8)
        assert (cfg.vocab, cfg.norm_eps) == (19072, 1e-5)
        shapes = jax.eval_shape(
            lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
        )["layers"]
        assert shapes[1]["wk"].shape == (4096, 8 * 192)
        assert shapes[1]["wv"].shape == (4096, 8 * 128)
        assert shapes[6]["wk"].shape == (4096, 4 * 192)
        assert shapes[6]["wo"].shape == (64 * 128, 4096)
        assert shapes[1]["sink"].shape == (64,) and "sink" not in shapes[6]


def _config_file():
    with open(os.path.join(
        manifest.CHECKOUT, "perfbench/configs/mimo_v2_5_train.json"
    )) as f:
        return json.load(f)


def test_the_configuration_file_says_what_was_cut_and_assumed():
    config = _config_file()
    assert set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    }
    entry = next(
        c for c in manifest.load()["configs"] if c["name"] == "mimo_v2_5_train"
    )
    assert set(entry["reduced"]) == set(config["reduced"])
    # layer 0 and ONE whole 5 : 1 period, taken where the pattern has one
    kept = config["layers_kept"]
    assert kept == [0, 6, 7, 8, 9, 10, 11] and len(kept) == config["num_hidden_layers"]
    assert [config["hybrid_layer_pattern"][i] for i in kept] == [0, 1, 1, 1, 1, 1, 0]
    assert [config["moe_layer_freq"][i] for i in kept] == [0] + [1] * 6
    for item in (
        "layer_rule", "kv_heads", "fused_qkv", "partial_rotary", "value_scale",
        "sink", "window", "attention_chunk_size", "no_qk_norm", "norms",
        "router", "bias_update_speed", "left_out", "torch_dtype",
    ):
        assert config["assumed"][item], item
    assert "16 chips share each layer" in config["deployment"]
    assert "memory_analysis" in config["memory"]
    assert "15.443 GB" in config["memory"] and "10.175 GB" in config["memory"]
    assert config["program"]["remat"] and config["program"]["_remat_why"]
    assert any("uniform in [2, 6]" in d for d in config["departures"])
    # every key of the catalog's row under the same key, but the three cut
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert config["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert value == config["published"][key], key
        else:
            assert config[key] == value, key


def test_the_whole_models_count_is_the_published_309b():
    config = _config_file()
    whole = flops_mimo.whole_model(config)
    assert flops_mimo.parameter_count(config, **whole) == 308_778_780_864
    assert flops_mimo.matmul_params(config) == 3_429_892_096
    assert flops_mimo.mixer_params(config, False) == 89_128_960
    assert flops_mimo.mixer_params(config, True) == 94_371_840
    assert flops_mimo.attended_pairs(8192, 128) == 1_040_448
    cfg = driver.program_config(config)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
    ) == flops_mimo.parameter_count(config)
    # active a token: the mixers, layer 0's MLP, 47 x (router + 8 experts),
    # the head (the embedding is a lookup): 14.8 G, the family's A15B
    active = (
        flops_mimo.matmul_params(config, **dict(whole, experts=8))
        - config["published"]["vocab_size"] * config["hidden_size"]
    )
    assert 14.5e9 < active < 15.5e9


# -- the scopes ---------------------------------------------------------------------


def test_the_cores_run_under_their_device_scopes(reference_side):
    scopes = ("accl.attn::window", "accl.attn::core", "accl.attn::gqa_proj")
    for scope in scopes:
        assert f"``{scope}``" in profiling.__doc__, scope
    assert "sink" in profiling.__doc__
    r = reference_side
    step, shard = make_sharded_train_step(CFG, _mesh(1), lr=1.0)
    text = step.lower(shard(r["params"]), r["tok"], r["tgt"]).compile().as_text()
    found = scoped_instructions(text)
    for scope in scopes:
        assert found.get(scope), scope


# -- the refusals, by name --------------------------------------------------------


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_kind_refuse_it_by_name(path):
    cfg = dataclasses.replace(DENSE, layers=DENSE.layers[1:3], n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok, _ = _batch()
    with pytest.raises(ValueError, match="sink"):
        if path == "generate":
            generate(params, tok, 2, cfg)
        elif path == "make_sharded_generate":
            make_sharded_generate(cfg, _mesh(1), 2)
        elif path == "encoder":
            encoder_forward(params, tok, cfg)
        elif path == "pipeline":
            mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                        ("pp", "dp", "tp"))
            make_pp_train_step(cfg, mesh, num_microbatches=2)
        else:
            param_specs(dataclasses.replace(cfg, **{path: True}))


@pytest.mark.parametrize("change,match", [
    (dict(diffusion=BlockDiffusion(block=4, mask_id=255)), "block diffusion"),
    (dict(qk_norm="head"), "not built beside"),
    (dict(layers=(LayerKind(ffn="dense", d_ff=96, **dict(SWA, kv_heads=3)),) * 4),
     "must divide n_heads"),
    (dict(layers=(LayerKind(ffn="dense", d_ff=96, **dict(
        SWA, heads=HeadGeometry(rope_dim=7))),) * 4), "even number"),
    (dict(layers=(LayerKind(ffn="dense", d_ff=96, **dict(
        SWA, heads=HeadGeometry(rope_dim=32))),) * 4), "even number"),
    (dict(layers=(LayerKind(ffn="dense", d_ff=96, **dict(
        SWA, heads=HeadGeometry(v_scale=0.0))),) * 4), "v_scale"),
])
def test_a_configuration_that_cannot_hold_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(DENSE, **change)
