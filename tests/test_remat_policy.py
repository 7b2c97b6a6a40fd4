"""What a block keeps when it is rematerialised (``TransformerConfig.remat``):
the values its mixer names ``KEPT_UNDER_REMAT``, which are the KDA and the
Mamba-2 mixers' five bf16 input projections and a softmax mixer's q, k and v
(with ``q_rope`` / ``k_rope`` where a head splits) as its core takes them, the
flash core's ``o`` and ``lse`` where the core is the kernels, and nothing
else; a block whose mixer names nothing is replayed whole.  Tiny widths, so
that the mixers' XLA forms run (``jax.checkpoint`` refuses an interpreted
kernel's host callbacks: the flash rule's half runs on a plain stand-in for
the kernels), bf16 weights as the train cells have them, dense FFNs (the held
experts' kernels are interpreted here too).  Keeping a value changes which
instructions run twice and no number the program states: with every stated
cast carried out, gradients are compared bit for bit.  (On the chip the
compiler also tiles OTHER matmuls of the changed step otherwise, and a
float32 sum in another order rounds otherwise: ``PERF.md`` section 6, PR 49.)"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

# jax 0.9 exports ``print_saved_residuals`` alone; this is the list it prints
from jax._src.ad_checkpoint import saved_residuals

from accl_tpu.models import (
    DeltaAttention,
    HeadGeometry,
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
    kind = LayerKind(mixer="attention", ffn="dense", d_ff=96, **SOFTMAX[mixer])
    base = BASE if kind.heads is None else {**BASE, "head_dim": 24}
    return TransformerConfig(**base, n_layers=2, layers=(kind, kind))


#: what differs among the softmax mixers' kinds: the plain one; MiMo-V2.5's
#: (a head of 24 whose first 8 columns rotate, v 16 wide and scaled, a sink a
#: head; the core takes the two parts of q and k apart); a sliding one on as
#: many K/V heads as query heads
SOFTMAX = {
    "attention": {},
    "attention_split_heads": dict(
        heads=HeadGeometry(rope_dim=8, v_dim=16, v_scale=0.707), sink=True,
    ),
    "attention_window": dict(window=16, kv_heads=4),
}


def _token_major(*columns):
    """What a recurrent mixer names: its projections, (B, T, columns)."""
    return [(B, T, c) for c in columns]


def _head_major(**heads):
    """What a softmax mixer names: the core's operands, (B, heads, T, a
    head's columns), by ``name=(heads, columns)``."""
    return [(B, h, T, c) for h, c in heads.values()]


#: mixer -> the shapes it names, a block, in bf16
NAMED = {
    # q, k, v, the decay gate, the out gate
    "kda_bounded": _token_major(64, 64, 64, 64, 64),
    # the gates' FINAL products, not rank 8
    "kda_unbounded": _token_major(64, 64, 64, 64, 64),
    # z, x, B, C (2 groups of 16), dt
    "mamba2": _token_major(64, 64, 32, 32, 8),
    # after the transposes and the rope: four query heads on two K/V heads
    "attention": _head_major(q=(4, 16), k=(2, 16), v=(2, 16)),
    # after the split: the parts without position, and the rotated ones
    "attention_split_heads": _head_major(
        q=(4, 16), k=(2, 16), v=(2, 16), q_rope=(4, 8), k_rope=(2, 8),
    ),
    "attention_window": _head_major(q=(4, 16), k=(4, 16), v=(4, 16)),
}


def _inputs(cfg, seed=0):
    params = init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, D), cfg.dtype)
    return x, params["layers"]


def _blocks(cfg, x, how):
    """The stack's blocks: as the program makes them (``"policy"``: ``remat``
    on; ``"off"``), or each rematerialised WHOLE, as before this policy."""
    on = dataclasses.replace(cfg, remat=how == "policy")
    _, blocks, _ = _enter_block_layout(x, on, None, 1)
    blocks = _layer_blocks(blocks, on)
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
    a quarter of the elements a bf16 spacing or two apart), so there, and for
    the softmax mixers, the two stand equally near."""
    cfg = _stack(mixer)
    kept, whole, off = (_grads(cfg, how) for how in ("policy", "whole", "off"))
    assert np.isfinite(np.asarray(kept[0], np.float32))
    assert all(np.asarray(g, np.float32).any() for g in jax.tree.leaves(kept[1]))
    _same(kept, whole)
    if mixer == "mamba2":
        _same(kept, off)
    assert _spacings_apart(kept, off) == _spacings_apart(whole, off) <= 4.0


def _named(why):
    """Whether a residual's description is a value under ``KEPT_UNDER_REMAT``:
    the name itself or, for a float, the ``reduce_precision`` that jax puts
    where the value is named (it rounds a kept float to its own type there,
    against XLA's excess precision; the description gives that op and the
    line of the naming call, or of the ``custom_vjp`` call whose forward rule
    named it).  Nothing else in a block of these stacks is such an op."""
    return f"named '{KEPT_UNDER_REMAT}'" in why or "reduce_precision" in why


def _residuals(cfg, how, layer=0):
    x, layers = _inputs(cfg)
    return saved_residuals(_blocks(cfg, x, how)[layer], x, layers[layer])


@pytest.mark.parametrize("mixer", list(NAMED))
def test_a_block_keeps_its_mixers_named_projections_and_nothing_else(mixer):
    found = _residuals(_stack(mixer), "policy")
    named = [aval for aval, why in found if _named(why)]
    assert sorted(a.shape for a in named) == sorted(NAMED[mixer])
    assert all(a.dtype == jnp.bfloat16 for a in named)
    # what else a rematerialised block holds for its backward is what it was
    # given (the stream and the layer's weights): nothing it computed
    others = [(str(a), why) for a, why in found if not _named(why)]
    assert others and all("argument" in why for _, why in others), others
    assert others == [(str(a), why) for a, why in _residuals(_stack(mixer), "whole")]


def test_the_dense_block_between_two_mamba_blocks_keeps_nothing():
    found = _residuals(_stack("mamba2"), "policy", layer=1)
    assert found and all("argument" in why for _, why in found)


@pytest.mark.parametrize("mixer", ["latent"])
def test_a_mixer_that_names_nothing_saves_what_it_saved_before(mixer):
    """The latent mixer on the XLA forms: its q, k and v are expanded from
    the latents inside the block and replayed; what it keeps is the flash
    rule's (``o`` and ``lse``), where the core is the kernels."""
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
    assert sorted(a.shape for a, why in found if _named(why)) == sorted(
        NAMED["attention"]
    )
    assert [str(a) for a, why in found if not _named(why)] == [
        str(a) for a, _ in whole
    ]
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


# -- the flash rule's half: ``o`` and ``lse`` --------------------------------


def _plain_scores(q, k, causal, window, scale, q_rope, k_rope):
    """float32 scores (B, H, T, T), masked, of q on k's fewer heads."""
    def on_q_heads(t):
        return jnp.repeat(t, q.shape[1] // t.shape[1], axis=1)

    if q_rope is not None:
        q = jnp.concatenate([q, q_rope], -1)
        k = jnp.concatenate([on_q_heads(k), on_q_heads(k_rope)], -1)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, on_q_heads(k), preferred_element_type=jnp.float32
    ) * scale
    i, j = jnp.arange(q.shape[2])[:, None], jnp.arange(q.shape[2])[None]
    seen = (j <= i) if causal else jnp.ones_like(j <= i)
    if window is not None:
        seen &= i - j < window
    return jnp.where(seen, s, -1e30)


def _plain_core(q, k, v, causal, window, scale, q_rope, k_rope):
    s = _plain_scores(q, k, causal, window, scale, q_rope, k_rope)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.exp(s - lse[..., None]).astype(v.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(v, q.shape[1] // v.shape[1], 1))
    return out, lse


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def flash_fwd_stand_in(*operands):
    """The forward as a call of its own name, which a jaxpr shows."""
    return _plain_core(*operands)


@pytest.fixture
def plain_flash(monkeypatch):
    """``jax.numpy`` in the two kernels' places, with their signatures (an
    interpreted kernel is host callbacks, which ``jax.checkpoint`` refuses):
    the ``custom_vjp`` wrapper round them, which is what names ``o`` and
    ``lse``, is the program's own."""
    from accl_tpu.ops.pallas import attention as flash

    def fwd(q, k, v, causal, block, interpret, with_lse, window=None,
            scale=None, q_rope=None, k_rope=None, layout=None, sink=None):
        assert layout is None and sink is None
        return flash_fwd_stand_in(q, k, v, causal, window, scale, q_rope, k_rope)

    def bwd(q, k, v, o, lse, g, causal, block, interpret, window=None,
            scale=None, q_rope=None, k_rope=None, layout=None):
        rope = () if q_rope is None else (q_rope, k_rope)

        def out(q, k, v, *rope):
            return _plain_core(
                q, k, v, causal, window, scale, *(rope or (None, None))
            )[0]

        grads = jax.vjp(out, q, k, v, *rope)[1](g)
        return grads if rope else grads + (None, None)

    monkeypatch.setattr(flash, "_flash_fwd_impl", fwd)
    monkeypatch.setattr(flash, "_flash_bwd_impl", bwd)


def _flash_block(mixer):
    """The first block of ``mixer``'s stack on the flash core, rematerialised
    as the program does it, with its inputs."""
    cfg = dataclasses.replace(_stack(mixer), attention="flash")
    x, layers = _inputs(cfg)
    return _blocks(cfg, x, "policy")[0], x, layers[0]


def _forward_calls(block, x, lp):
    """How often the gradient of ``block`` runs the core's forward."""
    grad = jax.grad(lambda x, lp: (block(x, lp).astype(jnp.float32) ** 2).sum())
    return str(jax.make_jaxpr(grad)(x, lp)).count("name=flash_fwd_stand_in")


#: what a block on the flash core keeps beside what its mixer names: the
#: core's output (four heads of v's width) and a float32 logsumexp a row
FLASH_KEEPS = [((B, 4, T, 16), "bfloat16"), ((B, 4, T), "float32")]


@pytest.mark.parametrize("mixer", ["attention", "attention_window", "latent"])
def test_a_block_on_the_flash_core_keeps_o_and_lse_and_runs_it_once(
    mixer, plain_flash, monkeypatch
):
    """``_flash_vjp_fwd`` names the kernel's two outputs, so a rematerialised
    block saves them (beside the softmax mixer's q, k and v; the latent
    mixer's are expanded inside the block and are not named) and its
    gradient calls the core's forward ONCE; with the name taken away the
    backward calls it a second time, as the parent's did."""
    from accl_tpu.utils import remat

    block, x, lp = _flash_block(mixer)
    found = saved_residuals(block, x, lp)
    named = sorted(
        (a.shape, str(a.dtype)) for a, why in found if _named(why)
    )
    assert named == sorted(
        [(shape, "bfloat16") for shape in NAMED.get(mixer, [])] + FLASH_KEEPS
    )
    assert all("argument" in why for _, why in found if not _named(why))
    assert _forward_calls(block, x, lp) == 1
    monkeypatch.setattr(remat, "checkpoint_name", lambda value, name: value)
    assert _forward_calls(*_flash_block(mixer)) == 2


# -- the benchmark's counter of it -------------------------------------------


def _traced(*names):
    """A reader's ``ctx`` whose traced steps ran instructions ``names``."""
    events = [[name + " custom-call tpu_custom_call bf16[64,8192,128]", 10.0 * i, 5.0]
              for i, name in enumerate(names)]
    return {"slices": {"steps": {
        "reduced": {"devices": {"/device:TPU:0": events}, "host": []},
        "window": (0.0, 10.0 * len(names)),
    }}}


@pytest.mark.parametrize("names,reads", [
    # the parent's step: a layer's forward, its replay, its backward
    (["flash_fwd.1", "flash_fwd.2", "fusion.3", "flash_fwd.4", "flash_fwd.5",
      "flash_bwd.6", "flash_bwd.7"], 2.0),
    (["flash_fwd.1", "flash_fwd.2", "fusion.3", "flash_bwd.6", "flash_bwd.7"], 1.0),
    (["fusion.3", "kda_fwd.1", "kda_bwd.2"], None),
    ([], None),
])
def test_flash_fwd_per_bwd_counts_the_kernels_by_their_names(names, reads):
    from perfbench.layer_metrics import flash_fwd_per_bwd

    assert flash_fwd_per_bwd.read(_traced(*names)) == reads
    assert flash_fwd_per_bwd.read({"slices": {}}) is None


def test_flash_fwd_per_bwd_is_reported_by_the_cells_under_remat():
    """The entry lists the train cells whose configuration sets ``remat``
    (each has a softmax or latent layer on the flash core), and no other."""
    from perfbench import manifest

    doc = manifest.load()
    entry = doc["per_layer"][-1]
    assert entry == {
        "name": "flash_fwd_per_bwd", "unit": "count", "better": "lower",
        "source": "device_trace", "layer": "models",
        "moves": "train_tokens_per_s", "workloads": entry["workloads"],
    }
    under_remat = [
        w["name"] for w in doc["workloads"]
        if manifest.cell(doc, w["name"])["config"].get("program", {}).get("remat")
    ]
    assert entry["workloads"] == under_remat and len(under_remat) == 5
