"""The table of mixers (``accl_tpu/models/mixers``): one module a mixer, chosen
once by name.  Shapes only; nothing here is compiled."""

import ast
import dataclasses
import inspect
import pathlib

import jax
import pytest

from accl_tpu.models import (
    DeltaAttention,
    HeadGeometry,
    LatentAttention,
    LayerKind,
    Mamba2,
    TransformerConfig,
)
from accl_tpu.models import mixers, transformer
from accl_tpu.models.mixers import MIXERS

BASE = dict(
    vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=48, max_seq=32,
    pos_embedding="rope",
)
#: a small configuration a mixer, in the forms whose trees differ, and the
#: kind of its one layer
CONFIGS = {
    "attention": (dict(n_kv_heads=2, qk_norm=True, attn_gate=True), LayerKind()),
    "attention_own_heads": (dict(qk_norm=False), LayerKind(
        kv_heads=2, sink=True, heads=HeadGeometry(rope_dim=4, v_dim=12),
    )),
    "latent": (dict(
        latent=LatentAttention(16, 8, 8, 4, 8), attn_gate="head",
    ), LayerKind()),
    "latent_direct_q": (dict(latent=LatentAttention(None, 8, 8, 4, 8)), LayerKind()),
    "kda": (dict(kda=DeltaAttention(8)), LayerKind(mixer="kda", rope=False)),
    "kda_ranked_gates": (dict(
        kda=DeltaAttention(8, lower_bound=None, beta_scale=2.0, gate_rank=4),
    ), LayerKind(mixer="kda", rope=False)),
    "kda_head_decay": (dict(kda=DeltaAttention(
        8, lower_bound=None, v_dim=16, head_decay=True, out_gate="silu",
    )), LayerKind(mixer="kda", rope=False)),
    "mamba2": (dict(mamba=Mamba2(4, 8, 16, 2)), LayerKind(mixer="mamba2", rope=False)),
}


def _config(case):
    fields, kind = CONFIGS[case]
    kind = dataclasses.replace(kind, d_ff=48)
    return TransformerConfig(**BASE, **fields, layers=(kind,)), kind


@pytest.mark.parametrize("case", list(CONFIGS))
def test_specs_and_init_give_the_same_tree(case):
    cfg, kind = _config(case)
    mixer = MIXERS[cfg.mixer(kind)]
    specs = mixer.specs(cfg, kind)
    shapes = jax.eval_shape(
        lambda key: mixer.init(key, cfg, kind),
        jax.random.split(jax.random.PRNGKey(0), 2),
    )
    assert set(specs) == set(shapes)
    for name, spec in specs.items():
        assert len(spec) <= shapes[name].ndim, name
    # and they are the mixer's part of the layer's tree, the block's norms
    # and its FFN beside them
    layer = transformer.param_specs(cfg)["layers"][0]
    assert set(layer) - set(specs) == {"ln1", "ln2", "w1", "w2"}


def test_the_table_holds_every_mixer_a_kind_can_name():
    assert list(MIXERS) == ["attention", "latent", "kda", "mamba2"]
    for name, module in MIXERS.items():
        assert module.__name__ == f"accl_tpu.models.mixers.{name}"
        for fn in ("check", "specs", "init", "bind", "plain"):
            assert callable(getattr(module, fn)), (name, fn)
    # ``"none"`` is the absence of a mixer and no entry; any other name that
    # is not in the table is refused
    one = LayerKind(mixer="none", ffn="dense", d_ff=48)
    assert TransformerConfig(**BASE, layers=(one,)).mixer(one) == "none"
    for name in MIXERS:     # each is a name a kind can say outright
        cfg, kind = _config(name)
        named = dataclasses.replace(kind, mixer=name)
        assert dataclasses.replace(cfg, layers=(named,)).mixer(named) == name
    with pytest.raises(ValueError, match="unknown mixer 'mamba'"):
        TransformerConfig(**BASE, layers=(LayerKind(mixer="mamba", d_ff=48),))


@pytest.mark.parametrize("block", ["_block", "_block_sp", "_block_cp"])
def test_a_block_takes_one_mixer_and_none_of_its_settings(block):
    params = inspect.signature(getattr(transformer, block)).parameters
    assert "mixer" in params
    assert not set(params) & {
        "latent", "kda", "mamba", "geometry", "head_norm", "block_diffusion",
        "attn_impl", "rope_base", "window", "qk_eps", "n_heads_local",
    }
    assert len(params) - 2 <= 10          # beside the activation and the tree


def test_no_mixer_imports_the_transformer_module():
    directory = pathlib.Path(mixers.__file__).parent
    files = sorted(directory.glob("*.py"))
    assert {f.stem for f in files} == {"__init__", *MIXERS}
    for file in files:
        for node in ast.walk(ast.parse(file.read_text())):
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [a.name for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                continue
            assert not any("transformer" in n for n in names), (file.name, names)


def test_only_the_attention_mixer_at_its_defaults_is_plain():
    for case in CONFIGS:
        cfg, kind = _config(case)
        why = MIXERS[cfg.mixer(kind)].plain(cfg, kind)
        assert (why is None) == (case == "attention"), case
        assert not cfg.plain()             # a pattern is none either
    dense = TransformerConfig(**BASE)
    assert dense.plain() and MIXERS["attention"].plain(dense, dense.pattern()[0]) is None
