"""SDAR's block of ``accl_tpu.models`` (a Qwen3-MoE block: RMSNorm 1e-6,
GQA, QK-norm a head, RoPE, a softmax router with a renormalised top-k and
a held share of the experts, no shared expert, untied head) under
block-diffusion TRAINING (ids noised inside the step from a key, ``[noisy
; clean]`` under the block layout, the head and a ``1 / t``-weighted loss
on the noisy half only, no shift) against the plain float32 reference of
``perfbench/reference/sdar_moe.py``, at small sizes on the CPU mesh with
seeded weights.

Float32 against float32 is held to 1e-4 of the largest value, and every
way of breaking the reference lands outside that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import (
    BlockDiffusion,
    TransformerConfig,
    diffusion_noise,
    forward,
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
from accl_tpu.models.transformer import loss_fn
from perfbench.drivers import train_steps_sdar as driver
from perfbench.reference import sdar_moe

L, BLOCK, MASK = 32, 4, 255
#: 4 of 16 experts held (the second of four shares), top 4, heads of 32
#: on a model of 64, GQA 2 to 1
CFG = TransformerConfig(
    vocab=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32, n_layers=2,
    d_ff=32, max_seq=64, pos_embedding="rope", rope_base=1e6, norm="rmsnorm",
    norm_eps=1e-6, ffn="swiglu", qk_norm="head", tie_head=False,
    diffusion=BlockDiffusion(BLOCK, MASK), n_experts=4, moe_top_k=4,
    moe_capacity_factor=None, moe_norm_topk_prob=True, moe_aux_weight=0.001,
    moe_router_z_weight=0.0, moe_router_experts=16, moe_first_expert=4,
    moe_held_row_factor=4.0, attention="naive",
)
REF = dict(
    n_head=4, n_kv_head=2, block=BLOCK, top_k=4, norm_topk_prob=True,
    first_expert=4, q_block=16,
)
KEY = jax.random.PRNGKey(7)
_VISIBLE = sdar_moe.visible


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm scales that are not all one, so that a
    missing scale shows."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(
            jax.random.PRNGKey(p.size), p.shape, p.dtype
        ) if p.ndim == 1 else p,
        params,
    )


def _tokens(batch=2, seed=1, length=L):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, length), 0, MASK
    )


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


def _mesh(dp, tp):
    return Mesh(np.array(jax.devices()[:dp * tp]).reshape(dp, tp), ("dp", "tp"))


@pytest.fixture(scope="module")
def f32():
    with jax.default_matmul_precision("highest"):
        params, tok = _params(), _tokens()
        noisy, masked, t = diffusion_noise(KEY, tok, CFG.diffusion)
        # each side ONE compiled function: an eager walk compiles every
        # operation by itself (ROADMAP D14)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, tok, KEY, CFG)
        ))(params)
        weights = driver.reference_weights(params)
        want_loss, want_grads = jax.jit(jax.value_and_grad(
            lambda w: sdar_moe.loss(w, noisy, tok, masked, t, **REF)
        ))(weights)
        return dict(
            params=params, tok=tok, noisy=noisy, masked=masked, t=t,
            both=jnp.concatenate([noisy, tok], axis=1), weights=weights,
            loss=loss, want_loss=want_loss, grads=grads, want_grads=want_grads,
        )


# -- the configuration and the refusals ---------------------------------------


def test_the_driver_maps_every_published_key():
    from perfbench import manifest

    cell = manifest.cell(manifest.load(), "train_sdar_t4096_b2")
    cfg = driver.program_config(cell["config"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads(), cfg.head_size()) == (
        2048, 32, 4, 128)
    assert (cfg.n_experts, cfg.router_experts(), cfg.moe_top_k, cfg.d_ff) == (
        16, 128, 8, 768)
    assert (cfg.norm_eps, cfg.rope_base, cfg.qk_norm, cfg.tie_head) == (
        1e-6, 1e6, "head", False)
    assert cfg.diffusion == BlockDiffusion(4, 18991, 1e-3)
    assert (cfg.moe_aux_weight, cfg.moe_norm_topk_prob) == (0.001, True)
    assert cfg.n_layers == 6 and cfg.layers is None and not cfg.plain()
    assert driver.reference_model(cell["config"]) == dict(
        n_head=32, n_kv_head=4, block=4, top_k=8, norm_topk_prob=True,
        first_expert=0,
    )


@pytest.mark.parametrize("bad", [
    dict(pos_embedding="learned"),
    dict(diffusion=BlockDiffusion(4, 256)),
    dict(diffusion=BlockDiffusion(0, 255)),
    dict(diffusion=BlockDiffusion(4, 255, eps=0.0)),
])
def test_a_diffusion_config_outside_its_contract_is_refused(bad):
    with pytest.raises(ValueError, match="block diffusion"):
        dataclasses.replace(CFG, **bad)


@pytest.mark.parametrize("path", [
    "generate", "make_sharded_generate", "context_parallel", "seq_parallel",
    "vocab_parallel", "encoder", "pipeline", "odd_rows",
])
def test_the_paths_beside_train_and_forward_refuse_block_diffusion_by_name(path):
    from accl_tpu.models import encoder_forward

    dense = dataclasses.replace(
        CFG, n_experts=0, moe_router_experts=None, moe_first_expert=0,
        moe_capacity_factor=1.5,
    )
    params, tok = _params(dense), _tokens()
    with pytest.raises(ValueError, match="block diffusion"):
        if path == "generate":
            generate(params, tok, 2, dense)
        elif path == "make_sharded_generate":
            make_sharded_generate(dense, _mesh(1, 1), 2)[0](params, tok)
        elif path == "encoder":
            encoder_forward(params, tok, dense)
        elif path == "pipeline":
            mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1, 1),
                        ("pp", "dp", "tp"))
            make_pp_train_step(dense, mesh, num_microbatches=2)
        elif path == "odd_rows":
            forward(params, tok[:, :L - 1], dense)
        else:
            loss_fn(params, tok, KEY, dataclasses.replace(dense, **{path: True}),
                    tp_axis="tp")


# -- the noising --------------------------------------------------------------


def test_the_noise_is_the_keys_and_leaves_unmasked_ids_untouched():
    tok = _tokens(batch=3, length=64)
    noisy, masked, t = diffusion_noise(KEY, tok, CFG.diffusion)
    again = diffusion_noise(KEY, tok, CFG.diffusion)
    other = diffusion_noise(jax.random.PRNGKey(8), tok, CFG.diffusion)
    for a, b in zip((noisy, masked, t), again):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert (np.asarray(masked) != np.asarray(other[1])).any()
    noisy, masked, t = (np.asarray(a) for a in (noisy, masked, t))
    assert (noisy[masked] == MASK).all()
    assert (noisy[~masked] == np.asarray(tok)[~masked]).all()
    # one level a block a sequence, inside [eps, 1)
    blocks = t.reshape(3, -1, BLOCK)
    assert (blocks == blocks[..., :1]).all()
    assert (t >= 1e-3).all() and (t < 1.0).all()
    assert len(np.unique(blocks[..., 0])) == blocks[..., 0].size
    # raw key words and a typed key draw alike
    typed = diffusion_noise(jax.random.key(7), tok, CFG.diffusion)
    assert (np.asarray(typed[1]) == masked).all()


def test_a_blocks_masked_share_is_its_level_within_binomial_bounds():
    """Blocks of 64 so that a block's share says something: each block's
    count of masked positions within five standard deviations of its level,
    and the whole draw's share near a half."""
    diff = BlockDiffusion(64, MASK)
    tok = _tokens(batch=8, length=2048)
    _, masked, t = diffusion_noise(KEY, tok, diff)
    counts = np.asarray(masked).reshape(8, -1, 64).sum(axis=-1)
    level = np.asarray(t).reshape(8, -1, 64)[..., 0]
    sd = np.sqrt(64 * level * (1 - level))
    assert (np.abs(counts - 64 * level) <= 5 * sd + 1).all()
    assert abs(np.asarray(masked).mean() - 0.5) < 0.06


def test_a_shard_takes_the_whole_batchs_draw_for_its_sequences():
    tok = _tokens(batch=4)
    whole = diffusion_noise(KEY, tok, CFG.diffusion)
    for index in range(2):
        part = diffusion_noise(
            KEY, tok[2 * index:2 * index + 2], CFG.diffusion, (index, 2)
        )
        for a, b in zip(part, whole):
            assert (np.asarray(a) == np.asarray(b[2 * index:2 * index + 2])).all()


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("attention", ["naive", "blockwise", "flash"])
def test_f32_logits_of_the_noisy_half_match_reference(f32, attention):
    cfg = dataclasses.replace(CFG, attention=attention)
    got = jax.jit(lambda p, both: forward(p, both, cfg))(
        f32["params"], f32["both"]
    )
    assert got.shape == (2, L, CFG.vocab)
    reference = lambda **how: jax.jit(lambda w, noisy, tok: sdar_moe.logits(
        w, noisy, tok, **{**REF, **how}
    ))(f32["weights"], f32["noisy"], f32["tok"])
    want = reference()
    _close(got, want)
    # the query block changes no value
    _close(reference(q_block=64), want, 1e-6)


def test_f32_loss_matches_reference(f32):
    _close(f32["loss"], f32["want_loss"], 1e-5)
    # the auxiliary term is in it: without its weight the loss is smaller
    bare = loss_fn(f32["params"], f32["tok"], KEY,
                   dataclasses.replace(CFG, moe_aux_weight=0.0))
    h, _, balance = sdar_moe.hidden(f32["weights"], f32["both"], **REF)
    _close(f32["loss"] - bare, sdar_moe.AUX_COEF * balance, 1e-3)
    assert float(balance) > 1.0


def test_f32_gradient_of_every_parameter_matches_reference(f32):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        driver.reference_weights(f32["grads"])
    )
    want = jax.tree.leaves(f32["want_grads"])
    # head, final norm, embedding; 12 a layer (4 attention matrices, 2
    # QK-norm scales, 2 norms, the router, 3 of the experts)
    assert len(flat) == len(want) == 3 + 12 * 2
    for (path, g), w in zip(flat, want):
        assert np.abs(np.asarray(w)).max() > 0, path
        _close(g, w)


@pytest.mark.parametrize("tp", [1, 2])
def test_one_train_step_moves_parameters_as_the_reference(f32, tp):
    """Through ``make_sharded_train_step``: the key in ``targets``' place,
    ``(params, loss, counters)`` back."""
    lr = 0.05
    step, shard = make_sharded_train_step(CFG, _mesh(1, tp), lr=lr)
    new, loss, counters = step(shard(f32["params"]), f32["tok"], KEY)
    _close(loss, f32["want_loss"], 1e-5)
    want = jax.tree.map(
        lambda p, g: p - lr * g, f32["weights"], f32["want_grads"]
    )
    for g, w in zip(jax.tree.leaves(driver.reference_weights(new)),
                    jax.tree.leaves(want)):
        _close(g, w, 1e-5)
    assert int(counters["masked_tokens"]) == int(f32["masked"].sum())
    _, logits, _ = sdar_moe.hidden(f32["weights"], f32["both"], **REF)
    held = [
        int(np.asarray(driver.router_facts(z, 4)[0])[4:8].sum()) for z in logits
    ]
    assert np.asarray(counters["held_entries"]).tolist() == held


def test_a_dense_step_is_noised_alike_on_one_data_shard_and_on_two(f32):
    """dp 2 (a dense block: the dropless experts take no data axis): each
    shard takes the batch's draw for its sequence, so loss, counters and
    parameters are dp 1's."""
    dense = dataclasses.replace(
        CFG, n_experts=0, moe_router_experts=None, moe_first_expert=0, d_ff=96
    )
    params = _params(dense)
    outs = []
    for dp in (1, 2):
        step, shard = make_sharded_train_step(dense, _mesh(dp, 1), lr=0.05)
        outs.append(step(shard(params), f32["tok"], KEY))
    _close(outs[1][1], outs[0][1], 1e-6)
    assert int(outs[1][2]["masked_tokens"]) == int(f32["masked"].sum())
    for a, b in zip(jax.tree.leaves(outs[1][0]), jax.tree.leaves(outs[0][0])):
        _close(a, b, 1e-5)


def test_one_chip_sharded_forward_and_probe_take_the_doubled_sequence(f32):
    params, both = f32["params"], f32["both"]
    fwd, shard = make_sharded_forward(CFG, _mesh(1, 1))
    _close(fwd(shard(params), both), forward(params, both, CFG), 1e-6)
    counters = make_sharded_router_probe(CFG, _mesh(1, 1))(shard(params), both)
    _, logits, _ = sdar_moe.hidden(f32["weights"], both, **REF)
    want = np.stack([np.asarray(driver.router_facts(z, 4)[0]) for z in logits])
    got = np.asarray(counters["expert_tokens"])
    assert got.shape == (2, 16) and (got == want).all()
    assert got.sum(axis=1).tolist() == [2 * 2 * L * 4] * 2    # 2 L rows
    assert np.asarray(counters["dropped"]).tolist() == [0, 0]


def test_the_clean_half_at_blocks_of_one_is_the_plain_causal_forward(f32):
    """Ties the new path to the old: at B = 1 a clean query sees the clean
    keys up to its own and nothing noisy, so the clean half's hidden state
    is the plain causal model's of the same weights; and a noisy query sees
    its own noisy key beside the clean keys strictly before."""
    from accl_tpu.models.transformer import _enter_block_layout, _embed_tokens

    cfg = dataclasses.replace(
        CFG, diffusion=BlockDiffusion(1, MASK), moe_aux_weight=0.0
    )
    plain = dataclasses.replace(cfg, diffusion=None)
    params, both = f32["params"], f32["both"]

    def layers(tokens, cfg):
        x = _embed_tokens(params, tokens, cfg)
        x, blocks, _ = _enter_block_layout(x, cfg, None, 1)
        for block, lp in zip(blocks, params["layers"]):
            x, _ = block(x, lp)
        return x

    doubled = layers(both, cfg)
    _close(doubled[:, L:], layers(f32["tok"], plain))
    # a noisy row whose id was not masked IS the clean row of its position
    # (its own key, with the same embedding, beside the clean keys before)
    keep = ~np.asarray(f32["masked"])
    assert keep.any() and not keep.all()
    _close(doubled[:, :L][keep], doubled[:, L:][keep])
    assert np.abs(np.asarray(
        doubled[:, :L][~keep] - doubled[:, L:][~keep]
    )).min(axis=-1).max() > 0


# -- ways of getting it wrong --------------------------------------------------


def _leaked_own_block(rows, cols, length, block):
    """The noisy -> clean part with ``<=``: a noisy query sees its OWN
    clean block, the ids it is asked to predict."""
    ok = _VISIBLE(rows, cols, length, block)
    q_noisy, k_clean = rows < length, cols >= length
    same = (rows % length) // block == (cols % length) // block
    return ok | (q_noisy & k_clean & same)


def _causal(rows, cols, length, block):
    return cols <= rows


@pytest.mark.parametrize("broken", [
    "leaked_block", "causal_mask", "shifted_loss", "positions_not_repeated",
    "qk_norm_eps_1e-5", "unweighted", "not_renormalised",
])
def test_a_broken_reference_is_told_apart(f32, broken, monkeypatch):
    ref = dict(REF)
    if broken == "leaked_block":
        monkeypatch.setattr(sdar_moe, "visible", _leaked_own_block)
    elif broken == "causal_mask":
        monkeypatch.setattr(sdar_moe, "visible", _causal)
    elif broken == "shifted_loss":
        monkeypatch.setattr(
            sdar_moe, "targets_of", lambda clean: jnp.roll(clean, -1, axis=-1)
        )
    elif broken == "positions_not_repeated":
        monkeypatch.setattr(
            sdar_moe, "rope",
            lambda x, positions, rope=sdar_moe.rope: rope(
                x, jnp.arange(x.shape[0])
            ),
        )
    elif broken == "qk_norm_eps_1e-5":
        monkeypatch.setattr(
            sdar_moe, "qk_norm",
            lambda x, w: x / jnp.sqrt(
                jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-1
            ) * w,
        )
    elif broken == "unweighted":
        monkeypatch.setattr(
            sdar_moe, "weighted_nll",
            lambda z, clean, masked, t, f=sdar_moe.weighted_nll: f(
                z, clean, masked, jnp.ones_like(t)
            ),
        )
    elif broken == "not_renormalised":
        ref["norm_topk_prob"] = False
    want = sdar_moe.logits(f32["weights"], f32["noisy"], f32["tok"], **ref)
    want_loss = sdar_moe.loss(
        f32["weights"], f32["noisy"], f32["tok"], f32["masked"], f32["t"], **ref
    )
    got = forward(f32["params"], f32["both"], CFG)
    logits_off = np.abs(np.asarray(got - want)).max() / np.abs(want).max()
    loss_off = abs(float(f32["loss"] - want_loss)) / float(want_loss)
    assert logits_off > 1e-3 or loss_off > 1e-3, (logits_off, loss_off)


# -- the share -----------------------------------------------------------------


def test_the_shares_routed_parts_add_up_to_the_layer_and_the_switch_term():
    """THE SHARE TEST.  16 experts in four shares of four: each share's
    routed part (the program's ``moe_ffn`` on a bank of four with the whole
    router, and the reference given the same range) adds up to the uncut
    16-expert reference of the whole layer; and every share computes the
    same Switch term, over all 16 outputs, the reference's."""
    d, f, E, k = 64, 32, 16, 4
    bank = init_moe_params(jax.random.PRNGKey(3), d, f, E, gated=True)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 2 * L, d))
    m = x.reshape(-1, d)

    def names(moe):
        return {
            "router": moe["gate"],
            "experts.gate_proj": moe["w1"], "experts.up_proj": moe["w3"],
            "experts.down_proj": moe["w2"],
        }

    route = dict(top_k=k, norm_topk_prob=True)
    whole, _, balance = sdar_moe.moe(m, names(bank), **route)
    program, reference, counts = 0.0, 0.0, 0
    for r in range(4):
        share = {
            key: bank[key][4 * r:4 * r + 4] for key in ("w1", "w2", "w3")
        }
        share.update(gate=bank["gate"])
        y, aux = moe_ffn(
            x, share, capacity_factor=None, k=k, return_aux=True,
            first_expert=4 * r, held_row_factor=4.0, switch_balance=True,
        )
        assert int(aux["dropped"]) == 0
        counts = counts + int(aux["held_entries"])
        program = program + y.reshape(-1, d)
        part, _, _ = sdar_moe.moe(m, names(share), first_expert=4 * r, **route)
        _close(y.reshape(-1, d), part)
        reference = reference + part
        _close(aux["load_balance"], balance, 1e-5)
        # not asked for, not computed: the other held-share cells' steps
        _, aux = moe_ffn(
            x, share, capacity_factor=None, k=k, return_aux=True,
            first_expert=4 * r, held_row_factor=4.0,
        )
        assert float(aux["load_balance"]) == 0.0
    assert counts == m.shape[0] * k     # every entry is held by exactly one
    _close(reference, whole, 1e-5)
    _close(program, whole)
    # and the program's whole bank, all 16 held, is the whole layer too
    y, aux = moe_ffn(x, bank, capacity_factor=None, k=k, return_aux=True)
    _close(y.reshape(-1, d), whole)
    _close(aux["load_balance"], balance, 1e-5)


def test_the_drivers_balancing_rounds_even_out_the_load(f32):
    """Set-up's rounds of gradient descent on the model's own auxiliary
    loss (``driver.balanced``): the Switch term falls towards 1."""
    params, tok = f32["params"], f32["tok"]

    def term(p):
        _, aux = loss_fn(p, tok, KEY, CFG, with_aux=True)
        return float(aux["load_balance"]) / CFG.n_layers

    after = driver.balanced(params, [tok], [KEY], CFG, rates=(2.0,) * 12)
    assert abs(term(after) - 1.0) < 0.01 < term(params) - 1.0
    for old, new in zip(params["layers"], after["layers"]):
        assert (np.asarray(old["wq"]) == np.asarray(new["wq"])).all()
        assert (np.asarray(old["moe"]["gate"])
                != np.asarray(new["moe"]["gate"])).any()
