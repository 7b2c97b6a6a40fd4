"""The afmoe block of ``accl_tpu.models`` (Trinity-Mini: a layer pattern
of sliding-window and full attention with a leading dense layer, a head
width of its own, gated attention, QK-norm a head, four norms a layer, a
scaled embedding, a sigmoid router with a selection bias, a shared expert,
and a held share of the experts) against the plain float32 reference of
``perfbench/reference/afmoe.py``, at small sizes on the CPU mesh with
seeded weights.

Float32 against float32 is held to 1e-4 of the largest value.  The bf16
program is held to the limits the benchmark's driver writes
(``perfbench/drivers/train_steps_trinity.py``), and every way of breaking
the reference lands outside them.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    LayerKind,
    TransformerConfig,
    forward,
    generate,
    init_moe_params,
    init_params,
    make_sharded_forward,
    make_sharded_generate,
    make_sharded_router_probe,
    make_sharded_train_step,
    moe_ffn,
)
from accl_tpu.models.moe import held_rows
from accl_tpu.models.transformer import loss_fn
from perfbench.drivers import train_steps_trinity as driver
from perfbench.drivers.train_steps_olmoe import router_facts
from perfbench.reference import afmoe

WINDOW, T = 16, 48
TYPES = ("sliding_attention", "sliding_attention", "full_attention")
#: 4 of 16 experts held (the second of four shares), top 4, a dense layer
#: first, two sliding layers to one full one, heads of 32 on a model of 64
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, n_layers=3,
    layers=(
        LayerKind(WINDOW, True, "dense", 96),
        LayerKind(WINDOW, True, "moe", 32),
        LayerKind(None, False, "moe", 32),
    ),
    d_ff=32, max_seq=64, pos_embedding="rope", norm="rmsnorm", ffn="swiglu",
    qk_norm="head", tie_head=False, attn_gate=True, post_norm=True,
    embed_scale=8.0, n_experts=4, moe_top_k=4, moe_capacity_factor=None,
    moe_norm_topk_prob=True, moe_aux_weight=0.0, moe_router_z_weight=0.0,
    moe_router="sigmoid", moe_route_scale=2.826, moe_bias_rate=0.001,
    moe_shared_d_ff=32, moe_router_experts=16, moe_first_expert=4,
    moe_held_row_factor=4.0, attention="naive",
)
REF = dict(
    n_head=4, n_kv_head=2, layer_types=TYPES, sliding_window=WINDOW, top_k=4,
    route_norm=True, route_scale=2.826, first_expert=4, q_block=16,
)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales that are not all one and an expert
    bias that is not zero, so that a missing scale or bias shows."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype
        ) if p.ndim == 1 else p,
        params,
    )


def _batch(batch=2, seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, T), 0, CFG.vocab)
    return tok, jnp.roll(tok, -1, axis=-1)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.fixture(scope="module")
def mesh11():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        params = _params()
        tok, tgt = _batch()
        # each side ONE compiled function: an eager walk compiles every
        # operation by itself (ROADMAP D14)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, tok, tgt, CFG)
        ))(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda w: afmoe.loss(w, tok, tgt, **REF)
        ))(driver.reference_weights(params))
        return dict(
            params=params, tok=tok, tgt=tgt, loss=loss, want_loss=want_loss,
            grads=grads, want_grads=want_grads,
        )


def test_the_driver_maps_every_published_key():
    from perfbench import manifest

    cell = manifest.cell(manifest.load(), "train_trinity_t8192_b2")
    cfg = driver.program_config(cell["config"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads(), cfg.head_size()) == (
        2048, 32, 4, 128)
    assert [(k.window, k.rope, k.ffn, k.d_ff) for k in cfg.pattern()] == [
        (2048, True, "dense", 6144), (2048, True, "moe", 1024),
        (2048, True, "moe", 1024), (2048, True, "moe", 1024),
        (None, False, "moe", 1024),
    ]
    assert (cfg.n_experts, cfg.router_experts(), cfg.moe_top_k) == (16, 128, 8)
    assert (cfg.moe_router, cfg.moe_route_scale, cfg.moe_bias_rate) == (
        "sigmoid", 2.826, 0.001)
    assert (cfg.qk_norm, cfg.attn_gate, cfg.post_norm, cfg.tie_head) == (
        "head", True, True, False)
    assert cfg.embed_scale == 2048 ** 0.5 and cfg.vocab == 25024
    assert cfg.moe_shared_d_ff == 1024 and not cfg.plain()
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    expert_layer = shapes["layers"][1]
    assert expert_layer["wq"].shape == expert_layer["wg"].shape == (2048, 4096)
    assert expert_layer["wo"].shape == (4096, 2048)
    assert expert_layer["wk"].shape == (2048, 512)
    assert expert_layer["q_norm"].shape == (128,)
    assert expert_layer["moe"]["gate"].shape == (2048, 128)
    assert expert_layer["moe"]["w1"].shape == (16, 2048, 1024)
    assert expert_layer["moe"]["bias"].dtype == jnp.float32
    assert shapes["layers"][0]["w1"].shape == (2048, 6144)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 700e6 < n < 710e6    # the issue's 705 M


def test_configs_without_a_pattern_build_the_one_they_had():
    dense = TransformerConfig(n_layers=3, d_ff=96)
    assert dense.pattern() == (LayerKind(None, False, "dense", 96),) * 3
    sparse = TransformerConfig(n_layers=2, d_ff=32, n_experts=4,
                               pos_embedding="rope")
    assert sparse.pattern() == (LayerKind(None, True, "moe", 32),) * 2
    assert dense.plain() and sparse.plain() and dense.head_size() == 32


@pytest.mark.parametrize("bad", [
    dict(layers=(LayerKind(),)),                         # 1 kind, 3 layers
    dict(layers=(LayerKind(ffn="conv"),) * 3),
    dict(layers=(LayerKind(window=0, rope=False),) * 3),
    dict(layers=(LayerKind(rope=True),) * 3, pos_embedding="learned"),
    dict(layers=(LayerKind(rope=False, ffn="moe"),) * 3, n_experts=0),
    dict(qk_norm="tail"),
    dict(moe_router="tanh"),
    dict(moe_router="sigmoid", moe_capacity_factor=1.5),  # dropless only
    dict(moe_first_expert=13),                            # 13..17 of 16
])
def test_unknown_kinds_fail_in_post_init(bad):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **bad)


def test_f32_logits_match_reference(f32):
    got = jax.jit(lambda p, tok: forward(p, tok, CFG))(f32["params"], f32["tok"])
    weights = driver.reference_weights(f32["params"])
    whole = jax.jit(lambda w, tok: afmoe.logits(w, tok, last=T, **REF))
    for b in range(2):
        _close(got[b], whole(weights, f32["tok"][b]))
    # ``last`` and the query block change no value
    _close(
        jax.jit(lambda w, tok: afmoe.logits(
            w, tok, last=5, **{**REF, "q_block": 48}
        ))(weights, f32["tok"][0]),
        got[0, -5:],
    )


def test_f32_loss_matches_reference(f32):
    _close(f32["loss"], f32["want_loss"], 1e-5)


def test_f32_gradient_of_every_parameter_matches_reference(f32):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        driver.reference_weights(f32["grads"])
    )
    want = jax.tree.leaves(f32["want_grads"])
    # head, final norm, embedding; 11 of the attention half and its norms a
    # layer; 3 dense matrices; 8 of an expert layer
    assert len(flat) == len(want) == 3 + 11 * 3 + 3 + 2 * 8
    for (path, g), w in zip(flat, want):
        if "expert_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.abs(np.asarray(w)).max() > 0, path
        _close(g, w)


def test_one_train_step_moves_parameters_and_bias_as_the_reference(f32, mesh11):
    params, tok, tgt = f32["params"], f32["tok"], f32["tgt"]
    lr = 0.05
    step, shard = make_sharded_train_step(CFG, mesh11, lr=lr)
    new, loss = step(shard(params), tok, tgt)
    _close(loss, f32["want_loss"], 1e-5)
    weights = driver.reference_weights(params)
    want = jax.tree.map(lambda p, g: p - lr * g, weights, f32["want_grads"])
    _, picked = afmoe.hidden(weights, tok, **REF)
    for lw, scores in zip(want["layers"][1:], picked):
        lw["expert_bias"] = afmoe.moved_bias(
            lw["expert_bias"], afmoe.expert_tokens(scores, CFG.moe_top_k)
        )
    got = driver.reference_weights(new)
    for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)
    ):
        _close(g, w, 1e-5)
    # every bias moved by the rate, up or down, and not by a gradient
    for old, lp in zip(params["layers"][1:], new["layers"][1:]):
        moved = np.asarray(lp["moe"]["bias"] - old["moe"]["bias"])
        steps = np.round(moved / 0.001, 3)      # sign(mean load - load)
        assert np.isin(steps, (-1.0, 0.0, 1.0)).all() and (steps != 0).any()


def test_one_chip_sharded_forward_and_probe_match_the_reference(f32, mesh11):
    params, tok = f32["params"], f32["tok"]
    fwd, shard = make_sharded_forward(CFG, mesh11)
    _close(fwd(shard(params), tok), forward(params, tok, CFG), 1e-6)
    counters = make_sharded_router_probe(CFG, mesh11)(shard(params), tok)
    _, picked = afmoe.hidden(driver.reference_weights(params), tok, **REF)
    want = np.stack([
        np.asarray(afmoe.expert_tokens(p, CFG.moe_top_k)) for p in picked
    ])
    got = np.asarray(counters["expert_tokens"])
    assert got.shape == (2, 16) and (got == want).all()
    assert got.sum(axis=1).tolist() == [2 * T * 4] * 2
    assert np.asarray(counters["held_entries"]).tolist() == (
        want[:, 4:8].sum(axis=1).tolist()
    )
    assert np.asarray(counters["dropped"]).tolist() == [0, 0]


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
    """The driver's three checks, its way: tokens an expert and the entries
    held against the reference's near-ties, logits on the positions without
    one, the loss.  As in ``tests/test_olmoe.py``, two things differ at
    this size: the hidden state's bf16 error is a larger share of a score's
    spacing than at the published widths, so logits are compared on the
    positions 8 spacings clear; and the loss limit was read over 16,384
    tokens, where an error that averages out as 1/sqrt(tokens) is
    sqrt(16384 / 96) times smaller than over these 96."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32
        and p.shape != (16,) else p,
        f32["params"],
    )
    tok, tgt = f32["tok"], f32["tgt"]
    weights = driver.reference_weights(params)
    h, picked = afmoe.hidden(weights, tok, **REF)
    want = afmoe.head(weights, h)
    facts = [router_facts(p, CFG.moe_top_k) for p in picked]
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
    allowed = near.sum(axis=1)
    assert int(np.asarray(counters["dropped"]).sum()) == 0
    assert (moved <= allowed).all(), (moved, allowed)
    here = np.asarray(counters["held_entries"])
    assert (np.abs(here - driver.held_entries(want_counts, 4, 4))
            <= allowed).all()

    loss = float(loss_fn(params, tok, tgt, cfg))
    want_loss = float(afmoe.loss_from_hidden(weights, h, tgt))
    scale = (16384 / (2 * T)) ** 0.5
    assert abs(loss - want_loss) / want_loss <= driver.LOSS_REL_LIMIT * scale


def _whole_qk_norm(x, weight):
    """QK-norm as OLMoE has it: over the whole projection, every head at
    once (the head-wide scale repeated)."""
    flat = x.reshape(x.shape[0], -1)
    return afmoe.rms_norm(flat, jnp.tile(weight, x.shape[1])).reshape(x.shape)


def _rope_everywhere(attention):
    def broken(a, lp, *, sliding, window, **kw):
        # a full layer that rotates: the mask of a window no query reaches
        return attention(a, lp, sliding=True,
                         window=window if sliding else 10 ** 9, **kw)
    return broken


@pytest.mark.parametrize("broken", [
    "window_ignored", "rope_on_a_full_layer", "gate_left_out",
    "route_scale_left_out", "weights_from_the_biased_scores",
    "shared_expert_left_out", "qk_norm_over_the_whole_projection",
    "a_missing_post_norm",
])
def test_broken_reference_falls_outside_the_limits(f32, broken, monkeypatch):
    """Each case breaks the reference in ONE way; the float32 program,
    which the whole reference matches to 1e-4, must then miss the limits
    that the bf16 program is held to."""
    ref = dict(REF)
    if broken == "window_ignored":
        ref["sliding_window"] = 10 ** 9
    elif broken == "rope_on_a_full_layer":
        monkeypatch.setattr(afmoe, "attention", _rope_everywhere(afmoe.attention))
    elif broken == "gate_left_out":
        monkeypatch.setattr(afmoe, "gate_fn", jnp.ones_like)
    elif broken == "route_scale_left_out":
        ref["route_scale"] = 1.0
    elif broken == "weights_from_the_biased_scores":
        monkeypatch.setattr(
            afmoe, "route", functools.partial(afmoe.route, biased_weights=True)
        )
    elif broken == "shared_expert_left_out":
        monkeypatch.setattr(
            afmoe, "moe", functools.partial(afmoe.moe, shared=False)
        )
    elif broken == "qk_norm_over_the_whole_projection":
        monkeypatch.setattr(afmoe, "qk_norm", _whole_qk_norm)
    elif broken == "a_missing_post_norm":
        monkeypatch.setattr(afmoe, "post_norm", lambda x, weight: x)
    weights = driver.reference_weights(f32["params"])
    tok, tgt = f32["tok"], f32["tgt"]
    want = jnp.stack(
        [afmoe.logits(weights, tok[b], last=T, **ref) for b in range(2)]
    )
    rel_rms, max_abs = _check_numbers(forward(f32["params"], tok, CFG), want)
    want_loss = float(afmoe.loss(weights, tok, tgt, **ref))
    assert not _inside(rel_rms, max_abs, float(f32["loss"]), want_loss), (
        rel_rms, max_abs, float(f32["loss"]), want_loss
    )


def test_the_shares_routed_parts_and_the_shared_expert_add_up_to_the_layer():
    """THE SHARE TEST.  16 experts in four shares of four: each share's
    routed part (the program's ``moe_ffn`` on a bank of four with the whole
    router, and the reference given the same range), with the shared
    expert counted once, adds up to the uncut 16-expert reference of the
    whole layer."""
    d, f, E, k = 64, 32, 16, 4
    bank = init_moe_params(
        jax.random.PRNGKey(3), d, f, E, gated=True, shared_d_ff=f, bias=True
    )
    bank["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (E,))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, d))
    m = x.reshape(2 * T, d)

    def names(moe):
        out = {
            "router": moe["gate"], "expert_bias": moe["bias"],
            "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
            "experts.down_proj": moe["w2"],
        }
        out.update({
            f"shared_experts.{n}": bank["shared"][w]
            for n, w in (("gate_proj", "w1"), ("up_proj", "w3"),
                         ("down_proj", "w2"))
        })
        return out

    route = dict(top_k=k, route_norm=True, route_scale=2.826)
    whole, _ = afmoe.moe(m, names(bank), **route)
    shared = afmoe.gated_mlp(
        m, bank["shared"]["w1"], bank["shared"]["w3"], bank["shared"]["w2"]
    )
    program, reference, counts = shared, shared, 0
    for r in range(4):
        share = {
            key: bank[key][4 * r:4 * r + 4] for key in ("w1", "w2", "w3")
        }
        share.update(gate=bank["gate"], bias=bank["bias"])   # no "shared"
        y, aux = moe_ffn(
            x, share, capacity_factor=None, k=k, return_aux=True,
            router="sigmoid", route_scale=2.826, first_expert=4 * r,
            held_row_factor=4.0,
        )
        assert int(aux["dropped"]) == 0
        counts = counts + int(aux["held_entries"])
        program = program + y.reshape(2 * T, d)
        part, _ = afmoe.moe(
            m, names(share), first_expert=4 * r, shared=False, **route
        )
        _close(y.reshape(2 * T, d), part)
        reference = reference + part
    assert counts == 2 * T * k      # every entry is held by exactly one share
    _close(reference, whole, 1e-5)
    _close(program, whole)
    # and the program's whole bank, all 16 held, is the whole layer too
    _close(
        moe_ffn(x, bank, capacity_factor=None, k=k, router="sigmoid",
                route_scale=2.826).reshape(2 * T, d),
        whole,
    )


def test_the_drivers_balancing_rounds_even_out_the_load(f32, mesh11):
    """Set-up's rounds of the bias rule (``driver.balanced``, through the
    program's own probe): the largest load over the mean falls and the
    held experts' share of the entries nears the balanced 4 of 16."""
    params = jax.tree.map(lambda p: p, f32["params"])
    for lp in params["layers"][1:]:
        lp["moe"]["bias"] = jnp.zeros((16,), jnp.float32)
    _, shard = make_sharded_forward(CFG, mesh11)
    probe = make_sharded_router_probe(CFG, mesh11)
    batches = [_batch(seed=s)[0] for s in (1, 2)]

    def load(p):
        counts = sum(
            np.asarray(probe(shard(p), b)["expert_tokens"]) for b in batches
        )
        return ((counts.max(axis=1) / counts.mean(axis=1)).max(),
                abs(counts[:, 4:8].sum() / counts.sum() - 0.25))

    before = load(params)
    after = load(driver.balanced(
        lambda p, b: probe(shard(p), b), params, batches, jnp.asarray,
        rates=(0.05,) * 6 + (0.02,) * 6,
    ))
    assert after[0] < before[0] and after[0] < 1.5
    assert after[1] < 0.03
    assert np.abs(np.asarray(params["layers"][1]["moe"]["bias"])).max() > 0


def test_entries_past_the_row_buffer_are_dropped_and_counted():
    d, f, k = 64, 32, 4
    share = init_moe_params(
        jax.random.PRNGKey(3), d, f, 4, gated=True, router_experts=16
    )
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, d))
    kw = dict(capacity_factor=None, k=k, return_aux=True, router="sigmoid",
              first_expert=8)
    y, aux = moe_ffn(x, share, held_row_factor=4.0, **kw)
    held = int(aux["held_entries"])
    assert int(aux["dropped"]) == 0 and 0 < held < 2 * T * k
    rows = held_rows(2 * T * k, 4, 16, 0.25)
    assert rows < held
    small, lost = moe_ffn(x, share, held_row_factor=0.25, **kw)
    assert int(lost["dropped"]) == held - rows
    assert int(lost["held_entries"]) == held
    assert np.isfinite(np.asarray(small)).all()
    # the gradient through a buffer that is not full stays finite and real
    g = jax.grad(lambda x: moe_ffn(
        x, share, held_row_factor=4.0, **{**kw, "return_aux": False}
    ).sum())(x)
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0
    assert held_rows(131072, 16, 128, 2.0) == 32768
    assert held_rows(768, 4, 16, 4.0) == 768     # never more than every entry


def test_the_decode_path_and_the_sharded_blocks_refuse_plainly(f32):
    params = f32["params"]
    with pytest.raises(ValueError, match="prefill/generate serve"):
        generate(params, f32["tok"][:, :8], 2, CFG)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    with pytest.raises(ValueError, match="prefill/generate serve"):
        make_sharded_generate(CFG, mesh, 2)
    for name in ("context_parallel", "seq_parallel"):
        with pytest.raises(ValueError, match="plain block only"):
            make_sharded_forward(dataclasses.replace(CFG, **{name: True}), mesh)
    from accl_tpu.models import encoder_forward

    with pytest.raises(ValueError, match="default block"):
        encoder_forward(
            params, f32["tok"],
            dataclasses.replace(CFG, n_experts=0, layers=None, moe_router="softmax",
                                moe_shared_d_ff=0, moe_router_experts=None,
                                norm="layernorm", ffn="gelu", qk_norm=False,
                                tie_head=True),     # the gate and post-norms stay
        )
    with pytest.raises(ValueError, match="unknown attention impl 'paged'"):
        make_sharded_train_step(dataclasses.replace(CFG, attention="paged"), mesh)


def test_tp2_shards_the_gate_and_the_shared_expert(f32):
    """Heads, the gate's columns and the shared expert's width split over
    tp; the head-wide QK-norm scales stay whole."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    params, tok, tgt = f32["params"], f32["tok"], f32["tgt"]
    fwd, shard = make_sharded_forward(CFG, mesh)
    _close(fwd(shard(params), tok), forward(params, tok, CFG), 1e-5)
    step, shard = make_sharded_train_step(CFG, mesh, lr=0.05)
    _, loss = step(shard(params), tok, tgt)
    _close(loss, f32["loss"], 1e-5)
