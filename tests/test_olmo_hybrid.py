"""The Olmo Hybrid block of ``accl_tpu.models`` (Gated DeltaNet layers: the
chunked gated delta rule with a decay a HEAD, key heads beside value heads
of twice their width, a write strength in (0, 2), a SiLU output gate; a
full-attention layer without position under QK-norm; a norm AFTER each
sub-layer and none before) against the plain float32 reference of
``perfbench/reference/olmo_hybrid.py`` (the delta rule as the token-by-token
recurrence), at small sizes on the CPU mesh with seeded weights; and the
chunked core at a scalar ``g`` down to -200 in both lowerings against that
recurrence.  Float32 against float32 is held to 1e-4 of the largest value."""

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
    DeltaAttention,
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
from accl_tpu.models.transformer import param_specs
from accl_tpu.ops import kda
from accl_tpu.ops.pallas import kda as kda_kernels
from accl_tpu.utils import profiling
from perfbench import flops_olmoh, manifest
from perfbench.drivers import train_steps_olmoh as driver
from perfbench.reference import olmo_hybrid as reference

T = 80          # a chunk of 64 and a tail of 16
ULP = 5e-7
GDN = LayerKind(mixer="kda", rope=False, ffn="dense", d_ff=96)
FULL = LayerKind(mixer="attention", rope=False, ffn="dense", d_ff=96)
#: four heads: keys of 8 beside values of 16 in the delta layers, heads of 16
#: in the full layer; two delta layers to one full one
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=4, n_layers=3,
    layers=(GDN, GDN, FULL), d_ff=96, max_seq=128, pos_embedding="rope",
    norm="rmsnorm", norm_eps=1e-6, ffn="swiglu", qk_norm=True,
    post_norm="only", tie_head=False,
    kda=DeltaAttention(head_dim=8, v_dim=16, conv=4, lower_bound=None,
                       head_decay=True, beta_scale=2.0, out_gate="silu"),
    attention="naive",
)
REF = dict(n_head=4, q_block=32)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with matrices larger than the init's and norm scales
    not all alike, so that a missing scale shows and the mixers' parts
    matter (the scales stay round 1: after a norm that follows the
    sub-layer, a scale of 3 a layer compounds into float32's last digits);
    ``dt_bias`` spread so that heads forget at different rates (the init's
    range leaves every head remembering)."""

    def larger(path, p):
        if path[-1].key == "dt_bias":
            return jnp.linspace(-4.0, 3.0, p.shape[0]).astype(p.dtype)
        if p.ndim == 1 and p.shape[0] > cfg.n_heads:
            return p + 0.1 * jax.random.normal(
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


# -- the core at a decay a head ------------------------------------------------


def _core_inputs(T, B=1, H=3, dk=8, dv=16, seed=0):
    """``g`` ONE value a head a token, (B, H, T, 1), at -200, -50 and
    -0.001: mixed over the tokens of the first head, fixed on the others (a
    head that forgets at once beside one that remembers across chunks); beta
    up to 2."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = reference.l2_norm(jax.random.normal(ks[0], (B, H, T, dk))) * dk ** -0.5
    k = reference.l2_norm(jax.random.normal(ks[1], (B, H, T, dk)))
    v = jax.random.normal(ks[2], (B, H, T, dv))
    levels = jnp.array([-200.0, -50.0, -0.001])
    g = levels[jax.random.randint(ks[3], (B, H, T, 1), 0, 3)]
    fixed = levels[(jnp.arange(H) % 3)].reshape(1, H, 1, 1)
    g = jnp.where(jnp.arange(H).reshape(1, H, 1, 1) == 0, g, fixed)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, H, T)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, H, T, dv))


def _recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule on (B, H, T, .) arrays."""
    tokens_first = lambda x: x.transpose(1, 0, 2)
    return jnp.stack([
        reference.delta_recurrence(
            *(tokens_first(x[b]) for x in (q, k, v)),
            tokens_first(g[b])[..., 0], beta[b].T,
        ).transpose(1, 0, 2)
        for b in range(q.shape[0])
    ])


def _core_case(length, **shape):
    """``kda_chunked`` against the recurrence at ``length`` tokens: ``o``
    and all five gradients, each finite and within 1e-4 of its largest."""
    inputs, w = _core_inputs(length, **shape)
    assert float(inputs[3].min()) == -200.0 and float(inputs[4].max()) > 1.9
    assert inputs[3].shape[-1] == 1
    core = lambda *a: kda.kda_chunked(*a)
    both = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2, 3, 4)
    ))
    _close(jax.jit(core)(*inputs), jax.jit(_recurrence)(*inputs))
    (_, got), (_, want) = both(core)(*inputs), both(_recurrence)(*inputs)
    for name, a, b in zip("qkvgb", got, want):
        _close(a, b), name


@pytest.mark.parametrize("length", [64, 100, 192])
def test_xla_form_at_a_decay_a_head_against_the_recurrence(length):
    """Keys of 8 beside values of 16 (nowhere near whole lanes: the XLA
    form); lengths that are and are not whole chunks."""
    assert not kda_kernels.takes_padded(8, 16)
    _core_case(length)


def test_kernels_at_padded_heads_against_the_recurrence(monkeypatch):
    """The shipped path: keys of 96 and values of 192 padded to 128 and 256
    for ``kda_fwd`` / ``kda_bwd``, interpreted, products in float32 (the
    chip's one bfloat16 pass is the XLA form's there too); 100 tokens, no
    whole chunk."""
    monkeypatch.setattr(kda_kernels, "_ONE_PASS", jnp.float32)
    assert kda_kernels.takes_padded(96, 192)
    assert not kda_kernels.takes((1, 1, 100, 96), (1, 1, 100, 192))
    seen = []
    run = kda_kernels.kda
    monkeypatch.setattr(
        kda_kernels, "kda",
        lambda q, k, v, g, beta, **kw: seen.append((q.shape, v.shape, g.shape, kw))
        or run(q, k, v, g, beta, **kw),
    )
    _core_case(100, H=1, dk=96, dv=192)
    assert seen and all(
        s == ((1, 1, 100, 128), (1, 1, 100, 256), (1, 1, 100, 128), {"safe": True})
        for s in seen
    )


def test_the_shape_rule_leaves_the_other_cells_where_they_were():
    """A gate of one column a head picks the padded path; a decay a channel
    at whole lanes (Ling-3.0's, Solar Open 2's) the kernels as before, at
    their rehearsals' heads of 32 the XLA form as before."""
    assert kda_kernels.takes((2, 32, 8192, 128), (2, 32, 8192, 128))
    assert not kda_kernels.takes((1, 4, 128, 32), (1, 4, 128, 32))
    assert not kda_kernels.takes_padded(32, 32)       # 4 x wider: not worth it
    assert not kda_kernels.takes_padded(24, 48)       # this cell's rehearsal
    assert kda_kernels.takes_padded(128, 128)
    (q, k, v, g, beta), _ = _core_inputs(64)
    with pytest.raises(ValueError, match="2\\^n"):
        kda.kda_chunked(q, k, v, g, beta, chunk=48, sub=16)


# -- the whole model ------------------------------------------------------------


def _reference_logits(weights, tok, **how):
    """The reference's logits, its layers broken by ``how``."""

    @jax.jit
    def logits(weights):
        h = reference.embed(weights, tok)
        for lp in weights["layers"]:
            h = reference.layer(h, lp, **REF, **how)
        return reference.head(weights, h)

    return logits(weights)


@pytest.fixture(scope="module")
def forward():
    """Seeded weights, a batch, and the program's logits of it."""
    params, (tok, _) = _params(), _batch()
    with jax.default_matmul_precision("highest"):
        fwd, shard = make_sharded_forward(CFG, _mesh(1))
        got = np.asarray(fwd(shard(params), tok))
    return driver.reference_weights(params), tok, got


def test_logits_against_the_reference(forward):
    weights, tok, got = forward
    _close(got, _reference_logits(weights, tok))
    # the batch works the gate: heads that forget inside a chunk beside
    # heads that remember across it
    h = reference.embed(weights, tok)
    g = np.asarray(reference.log_decay(h[0], weights["layers"][0]))
    assert g.min() < -20.0 and g.max() > -0.5


@pytest.fixture(scope="module")
def compiled_step():
    """``CFG``'s train step UNDER REMAT at lr 1 on one device, compiled
    once: the gradients' case runs it, the scopes' case reads its text."""
    with jax.default_matmul_precision("highest"):
        params, (tok, tgt) = _params(), _batch()
        step, shard = make_sharded_train_step(
            dataclasses.replace(CFG, remat=True), _mesh(1), lr=1.0
        )
        return step.lower(shard(params), tok, tgt).compile(), shard


@pytest.mark.parametrize("tp", [1, 2])
def test_loss_and_gradients_against_the_reference(tp, compiled_step):
    """Through ``make_sharded_train_step`` itself: at lr 1 the step's update
    IS the gradient (to the float32 spacing of a weight of about 3, which
    ``ULP`` allows for).  tp 1 under ``remat`` (as the cell runs), tp 2
    without: tp splits the heads two and two, and ``wa`` and ``wbeta`` with
    them."""
    params, (tok, tgt) = _params(), _batch()
    if tp == 1:
        step, shard = compiled_step
    else:
        step, shard = make_sharded_train_step(CFG, _mesh(tp), lr=1.0)
    new, loss = step(shard(params), tok, tgt)
    weights = driver.reference_weights(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference.loss(w, tok, tgt, **REF)
    ))(weights)
    _close(loss, want_loss, 1e-5)
    got = driver.reference_weights(
        jax.tree.map(lambda p, n: p - n, params, jax.device_get(new))
    )
    for name in ("embed_tokens", "norm", "lm_head"):
        _close(got[name], want[name], 2e-4, ULP)
    for got_l, want_l in zip(got["layers"], want["layers"]):
        assert set(got_l) == set(want_l)
        for name in want_l:
            _close(got_l[name], want_l[name], 2e-4, ULP), name


@pytest.mark.parametrize("how,where", [
    (dict(pre_norm=True), "a norm before the sub-layers"),
    (dict(delta_how=dict(beta_scale=1.0)), "beta without its 2"),
    (dict(delta_how=dict(sigmoid_gate=True)), "a sigmoid output gate"),
    (dict(delta_how=dict(channel_spread=0.5)), "a decay a channel, unequal"),
    (dict(delta_how=dict(no_decay=True)), "the decay left out"),
    (dict(delta_how=dict(no_conv=True)), "the convolutions left out"),
    (dict(full_how=dict(rope_theta=10000.0)), "rope on the full layer"),
    (dict(full_how=dict(no_qk_norm=True)), "QK-norm left out"),
])
def test_a_broken_reference_is_told_apart(how, where, forward):
    weights, tok, got = forward
    broken = np.asarray(_reference_logits(weights, tok, **how))
    # ten times what ``_close`` allows the right one
    assert np.abs(got - broken).max() > 1e-3 * np.abs(broken).max(), where


def test_the_trees_are_the_two_mixers():
    specs = param_specs(CFG)["layers"]
    shapes = jax.eval_shape(
        lambda k: init_params(k, CFG), jax.random.PRNGKey(0)
    )
    assert "pos" not in shapes
    delta, full = shapes["layers"][0], shapes["layers"][2]
    ffn = {"w1", "w2", "w3", "ln1_post", "ln2_post"}      # no ln1, no ln2
    assert set(full) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"} | ffn
    assert full["q_norm"].shape == (64,) == full["k_norm"].shape
    assert set(delta) == {
        "wq", "wk", "wv", "wo", "wa", "wg", "wbeta", "conv_q", "conv_k",
        "conv_v", "a_log", "dt_bias", "o_norm",
    } | ffn
    assert "wf" not in delta and "ln1" not in delta
    assert (delta["wq"].shape, delta["wk"].shape) == ((64, 32), (64, 32))
    assert (delta["wv"].shape, delta["wg"].shape) == ((64, 64), (64, 64))
    assert (delta["wo"].shape, delta["o_norm"].shape) == ((64, 64), (16,))
    assert delta["wa"].shape == (64, 4) == delta["wbeta"].shape
    assert delta["dt_bias"].shape == (4,) == delta["a_log"].shape
    assert delta["conv_v"].shape == (4, 64) and delta["conv_k"].shape == (4, 32)
    for s, layer in zip(specs, shapes["layers"]):
        assert set(s) == set(layer)
    # tp splits wa and wbeta with the heads
    assert tuple(specs[0]["wa"]) == (None, "tp") == tuple(specs[0]["wbeta"])
    assert tuple(specs[0]["dt_bias"]) == ("tp",)
    # the file's shapes: the decay's matrix is 3840 x 30
    cell = manifest.cell(manifest.load(), "train_olmoh_t8192_b1")
    cfg = driver.program_config(cell["config"])
    layer = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )["layers"][0]
    assert layer["wa"].shape == (3840, 30) and "wf" not in layer
    assert (layer["wk"].shape, layer["wv"].shape) == ((3840, 2880), (3840, 5760))
    assert layer["wo"].shape == (5760, 3840) and layer["o_norm"].shape == (192,)


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_layer_rule_from_layer_types(rehearse):
    cell = manifest.cell(
        manifest.load(), "train_olmoh_t8192_b1", rehearse=rehearse
    )
    config = cell["config"]
    cfg = driver.program_config(config)
    mixers = [cfg.mixer(kind) for kind in cfg.layers]
    assert mixers == [
        "kda" if config["layer_types"][i] == "linear_attention" else "attention"
        for i in config["layers_kept"]
    ]
    assert "kda" in mixers and mixers[-1] == "attention"
    assert all(not k.rope and k.ffn == "dense" for k in cfg.layers)
    assert cfg.post_norm == "only" and cfg.qk_norm is True and not cfg.tie_head
    assert cfg.kda.head_decay and cfg.kda.out_gate == "silu"
    assert cfg.kda.beta_scale == 2.0 and cfg.kda.lower_bound is None
    assert cfg.kda.value_dim() == 2 * cfg.kda.head_dim and cfg.remat
    if not rehearse:
        assert mixers == ["kda", "kda", "kda", "attention"]   # a whole period
        assert cfg.kda == DeltaAttention(
            96, 4, None, 2.0, None, v_dim=192, head_decay=True, out_gate="silu"
        )
        assert (cfg.n_heads, cfg.kv_heads(), cfg.head_size()) == (30, 30, 128)
        assert (cfg.vocab, cfg.d_model, cfg.norm_eps) == (100352, 3840, 1e-6)
        assert [k.d_ff for k in cfg.layers] == [11008] * 4
        # over all 32 published layers: 8 full layers, 3 : 1
        whole = dict(config, layers_kept=list(range(32)), num_hidden_layers=32)
        assert driver.layer_mixers(whole).count("full") == 8


def _config_file():
    with open(os.path.join(
        manifest.CHECKOUT, "perfbench/configs/olmo_hybrid_7b_train.json"
    )) as f:
        return json.load(f)


def test_the_configuration_file_says_what_was_cut_and_assumed():
    config = _config_file()
    assert config["published"]["num_hidden_layers"] == 32
    assert set(config["reduced"]) == {"num_hidden_layers"}
    entry = next(
        c for c in manifest.load()["configs"] if c["name"] == "olmo_hybrid_7b_train"
    )
    assert entry["reduced"] == ["num_hidden_layers"]
    assert config["layers_kept"] == list(range(config["num_hidden_layers"]))
    for item in (
        "layer_rule", "norms", "qk_norm", "rope", "full_attention", "gdn_heads",
        "gdn_mixer", "gdn_gate", "linear_allow_neg_eigval", "gdn_output", "mlp",
        "gdn_init", "torch_dtype", "initializer_range",
    ):
        assert config["assumed"][item], item
    assert "data parallelism" in config["deployment"]
    assert "memory_analysis" in config["memory"]
    assert "8 layers" in config["memory"] and "4 layers" in config["memory"]
    assert config["program"]["_remat_why"]
    # every key of the catalog's row under the same key, but the cut
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B"
        )
    assert config["source"] == row["source_url"] == entry["source"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert value == config["published"][key]
        else:
            assert config[key] == value, key


def test_the_whole_models_count_is_the_published_7b():
    config = _config_file()
    count = lambda layers: flops_olmoh.parameter_count(config, layers=layers)
    assert count(range(32)) == 7_430_870_688
    assert round(count(range(32)) / 1e9, 3) == 7.431
    assert count([0]) - count([]) == 215_570_172
    assert count([3]) - count([]) == 185_809_920
    assert count([]) == 770_707_200
    assert count(range(4)) == 1_603_227_636
    assert flops_olmoh.matmul_params(dict(config, layers_kept=[0, 1, 2, 3])) == (
        1_217_694_720
    )
    cfg = driver.program_config(config)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)
    ) == flops_olmoh.parameter_count(config)


# -- the scopes ---------------------------------------------------------------------


def test_the_mixers_run_under_their_device_scopes(compiled_step):
    scopes = ("accl.attn::kda", "accl.attn::kda_proj", "accl.attn::core",
              "accl.attn::gqa_proj")
    for scope in scopes:
        assert f"``{scope}``" in profiling.__doc__, scope
    assert "a decay a head" in profiling.__doc__
    found = driver.scoped_instructions(compiled_step[0].as_text())
    for scope in scopes:
        assert found.get(scope), scope


# -- the refusals, by name --------------------------------------------------------


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "encoder", "pipeline",
])
def test_paths_that_do_not_honour_the_mixer_refuse_it_by_name(path):
    cfg = dataclasses.replace(CFG, layers=(GDN, GDN), n_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok, _ = _batch()
    with pytest.raises(ValueError, match="KDA mixer"):
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
    (dict(kda=DeltaAttention(8, 4, -5.0, 2.0, head_decay=True)), "head_decay"),
    (dict(kda=DeltaAttention(8, 4, None, 2.0, 4, head_decay=True)), "head_decay"),
    (dict(kda=DeltaAttention(8, 4, None, 2.0, v_dim=0)), "v_dim"),
    (dict(kda=DeltaAttention(8, 4, None, 2.0, out_gate="tanh")), "out_gate"),
    (dict(post_norm="before"), "unknown post_norm"),
    (dict(diffusion=BlockDiffusion(block=4, mask_id=255)), "KDA mixer"),
])
def test_a_configuration_that_cannot_hold_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(CFG, **change)


def test_a_norm_after_the_sub_layer_alone_is_no_plain_block():
    """``post_norm="only"`` without a delta layer: the paths that take a
    plain block refuse it as they refuse the doubled post-norm."""
    cfg = dataclasses.replace(
        CFG, layers=None, n_layers=1, kda=None, qk_norm=False,
    )
    assert not cfg.plain()
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    assert {"ln1_post", "ln2_post"} <= set(shapes["layers"][0])
    assert not {"ln1", "ln2"} & set(shapes["layers"][0])
