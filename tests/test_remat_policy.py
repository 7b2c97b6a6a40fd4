"""What a block keeps when it is rematerialised (``TransformerConfig.remat``):
the values its mixer names ``KEPT_UNDER_REMAT``, which are the KDA and the
Mamba-2 mixers' five bf16 input projections, and nothing else; a block whose
mixer names nothing is replayed whole.  Tiny widths, so that the mixers' XLA
forms run (``jax.checkpoint`` refuses an interpreted kernel's host
callbacks), bf16 weights as the train cells have them, dense FFNs (the held
experts' kernels are interpreted here too).  Keeping a value changes which
instructions run twice and no number the program states: with every stated
cast carried out, gradients are compared bit for bit.  (On the chip the
compiler also tiles OTHER matmuls of the changed step otherwise, and a
float32 sum in another order rounds otherwise: ``PERF.md`` section 6, PR 49.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

# jax 0.9 exports ``print_saved_residuals`` alone; this is the list it prints
from jax._src.ad_checkpoint import saved_residuals

from accl_tpu.models import (
    DeltaAttention,
    LatentAttention,
    LayerKind,
    Mamba2,
    TransformerConfig,
    hybrid_layers,
    init_params,
    make_sharded_train_step,
)
from accl_tpu.models.transformer import (
    KEPT_UNDER_REMAT,
    _enter_block_layout,
    _layer_blocks,
)

B, T, D = 2, 80, 64      # a KDA chunk of 64 and a tail; two SSD chunks and one
BASE = dict(
    vocab=256, d_model=D, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=96,
    max_seq=128, pos_embedding="rope", rope_base=10000.0, norm="rmsnorm",
    norm_eps=1e-5, ffn="swiglu", tie_head=False, attention="naive",
    dtype=jnp.bfloat16,
)
KDA = LayerKind(mixer="kda", rope=False, ffn="dense", d_ff=96)


def _stack(mixer):
    """Two blocks of ``mixer`` (the Mamba-2 ones with a dense FFN block
    between them: a block there is one sub-layer)."""
    if mixer == "mamba2":
        return TransformerConfig(
            **{**BASE, "ffn": "relu2"}, n_layers=3,
            layers=hybrid_layers("M-M", d_ff=96),
            mamba=Mamba2(n_heads=8, head_dim=8, state=16, groups=2, conv=4,
                         chunk=32),
        )
    if mixer.startswith("kda"):
        # the bounded gate straight from the hidden state (Ling-3.0's), the
        # published one without a bound through a rank (Solar Open 2's)
        kda = (
            DeltaAttention(head_dim=16, conv=4, lower_bound=-5.0)
            if mixer == "kda_bounded" else
            DeltaAttention(head_dim=16, conv=4, lower_bound=None,
                           beta_scale=2.0, gate_rank=8)
        )
        return TransformerConfig(**BASE, n_layers=2, layers=(KDA, KDA), kda=kda)
    if mixer == "latent":
        kind = LayerKind(mixer="latent", ffn="dense", d_ff=96)
        base = {k: v for k, v in BASE.items() if k not in ("n_kv_heads", "head_dim")}
        return TransformerConfig(
            **base, n_layers=2, layers=(kind, kind), attn_gate="head",
            latent=LatentAttention(q_rank=None, kv_rank=16, nope_dim=16,
                                   rope_dim=8, v_dim=16),
        )
    kind = LayerKind(mixer="attention", ffn="dense", d_ff=96)
    return TransformerConfig(**BASE, n_layers=2, layers=(kind, kind))


#: mixer -> the shapes it names, a block: (B, T, columns) in bf16
NAMED = {
    "kda_bounded": [64] * 5,            # q, k, v, the decay gate, the out gate
    "kda_unbounded": [64] * 5,          # the gates' FINAL products, not rank 8
    "mamba2": [64, 64, 32, 32, 8],      # z, x, B, C (2 groups of 16), dt
}


def _inputs(cfg, seed=0):
    params = init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, D), cfg.dtype)
    return x, params["layers"]


def _blocks(cfg, x, how):
    """The stack's blocks: as the program makes them (``"policy"``: ``remat``
    on; ``"off"``), or each rematerialised WHOLE, as before this policy."""
    on = dataclasses.replace(cfg, remat=how == "policy")
    _, block, _ = _enter_block_layout(x, on, None, 1)
    blocks = _layer_blocks(block, on)
    return [jax.checkpoint(b) for b in blocks] if how == "whole" else blocks


def _compiled(fn, *args):
    """``fn(*args)`` with every cast the text states carried out.  By default
    XLA may skip a rounding to bf16 whose result it casts straight back
    (``xla_allow_excess_precision``), and does so wherever a projection's
    matmul fuses into its float32 chain: then a REPLAYED projection is not
    the number the first forward stored, whatever is kept.  The train cells'
    chains are kernels, whose operand is the stored bf16 array."""
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    return fn.lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False}
    )(*args)


def _grads(cfg, how):
    x, layers = _inputs(cfg)

    def loss(x, layers):
        for blk, lp in zip(_blocks(cfg, x, how), layers):
            x = blk(x, lp)
        return (x.astype(jnp.float32) ** 2).sum()

    return _compiled(jax.value_and_grad(loss, argnums=(0, 1)), x, layers)


def _same(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            equal_nan=False,
        )


def _spacings_apart(got, want):
    """The largest distance between two trees' leaves, in bf16 spacings of
    the leaf's largest value."""
    return max(
        float(
            np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
            / (np.abs(np.asarray(b, np.float32)).max() * 2.0 ** -8)
        )
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True)
    )


@pytest.mark.parametrize("mixer", list(NAMED))
def test_gradients_are_the_whole_replays_bit_for_bit(mixer):
    """Loss and gradients (the stream's and every weight's) under the policy
    are those under whole-block ``jax.checkpoint``, the parent's ``remat``,
    to the bit: what is kept is the number the replay would have made.
    Against ``remat`` off the Mamba-2 stack is bit-equal too; the KDA stack's
    XLA form is not, under the policy or the whole replay alike (XLA's CPU
    fusions of its float32 chains associate differently inside a checkpoint:
    a quarter of the elements a bf16 spacing or two apart), so there the two
    stand equally near."""
    cfg = _stack(mixer)
    kept, whole, off = (_grads(cfg, how) for how in ("policy", "whole", "off"))
    assert np.isfinite(np.asarray(kept[0], np.float32))
    assert all(np.asarray(g, np.float32).any() for g in jax.tree.leaves(kept[1]))
    _same(kept, whole)
    if mixer == "mamba2":
        _same(kept, off)
    assert _spacings_apart(kept, off) == _spacings_apart(whole, off) <= 4.0


def _named(why):
    """Whether a residual's description is a value under ``KEPT_UNDER_REMAT``
    (jax rounds a float residual to its own type where it is made, against
    XLA's excess precision: the description names that ``reduce_precision``
    at the naming call's line, or the name itself)."""
    return f"named '{KEPT_UNDER_REMAT}'" in why or (
        "reduce_precision" in why and "_kept_under_remat" in why
    )


def _residuals(cfg, how, layer=0):
    x, layers = _inputs(cfg)
    return saved_residuals(_blocks(cfg, x, how)[layer], x, layers[layer])


@pytest.mark.parametrize("mixer", list(NAMED))
def test_a_block_keeps_its_mixers_named_projections_and_nothing_else(mixer):
    found = _residuals(_stack(mixer), "policy")
    named = [aval for aval, why in found if _named(why)]
    assert sorted(a.shape for a in named) == sorted(
        (B, T, cols) for cols in NAMED[mixer]
    )
    assert all(a.dtype == jnp.bfloat16 for a in named)
    # what else a rematerialised block holds for its backward is what it was
    # given (the stream and the layer's weights): nothing it computed
    others = [(str(a), why) for a, why in found if not _named(why)]
    assert others and all("argument" in why for _, why in others), others
    assert others == [(str(a), why) for a, why in _residuals(_stack(mixer), "whole")]


def test_the_dense_block_between_two_mamba_blocks_keeps_nothing():
    found = _residuals(_stack("mamba2"), "policy", layer=1)
    assert found and all("argument" in why for _, why in found)


@pytest.mark.parametrize("mixer", ["attention", "latent"])
def test_a_mixer_that_names_nothing_saves_what_it_saved_before(mixer):
    cfg = _stack(mixer)
    found, whole = _residuals(cfg, "policy"), _residuals(cfg, "whole")
    assert [(str(a), why) for a, why in found] == [
        (str(a), why) for a, why in whole
    ]
    assert all("argument" in why for _, why in found)
    _same(_grads(cfg, "policy"), _grads(cfg, "whole"))


def test_a_stack_of_layers_all_alike_takes_the_same_policy():
    """``cfg.layers`` None (the StarCoder cells' form): ``_layer_blocks``'
    other ``remat`` site."""
    cfg = TransformerConfig(**BASE, n_layers=2)
    found, whole = _residuals(cfg, "policy"), _residuals(cfg, "whole")
    assert [str(a) for a, _ in found] == [str(a) for a, _ in whole]
    _same(_grads(cfg, "policy"), _grads(cfg, "whole"))


@pytest.mark.parametrize("mixer", list(NAMED))
def test_the_step_under_tp_2_is_the_whole_replays(mixer, monkeypatch):
    """Through ``make_sharded_train_step``'s own ``check_vma`` shard_map, the
    mixers' heads (and Mamba-2's groups) split over two devices: the kept
    projections are each chip's shard, and loss and updated weights are the
    whole-block replay's to the bit (and as near the step's without ``remat``
    as that is)."""
    cfg = _stack(mixer)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, cfg.vocab)

    def stepped(how):
        if how == "whole":   # the parent's ``remat``: a policy that keeps nothing
            monkeypatch.setattr(
                jax.checkpoint_policies, "save_only_these_names",
                lambda *names: jax.checkpoint_policies.nothing_saveable,
            )
        step, shard = make_sharded_train_step(
            dataclasses.replace(cfg, remat=how != "off"), mesh, lr=0.5
        )
        return jax.device_get(
            _compiled(step, shard(params), tok, jnp.roll(tok, -1, -1))
        )

    kept, off, whole = (stepped(how) for how in ("policy", "off", "whole"))
    _same(kept, whole)
    assert _spacings_apart(kept, off) == _spacings_apart(whole, off) <= 4.0
    moved = [
        (np.asarray(a, np.float32) != np.asarray(b, np.float32)).any()
        for a, b in zip(jax.tree.leaves(kept[0]), jax.tree.leaves(params))
    ]
    assert sum(moved) > len(moved) // 2
