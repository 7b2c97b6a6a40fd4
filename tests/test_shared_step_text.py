"""Guard for the code the benchmark's train cells share
(``make_sharded_train_step``, ``loss_fn``, ``_block``, the mixers of
``accl_tpu/models/mixers/`` (``_attn_partial``, ``_kda_partial``), ``_mlp``,
``_dropless_experts``, the attention lowerings): the StarCoder
and the OLMoE train step at their rehearsal sizes are the programs they
were before PR 31's layer pattern, held experts and window went in.

Two texts a configuration, each by its sha256 at the parent commit
(04da78e): the LOWERED module (StableHLO, what the program hands the
compiler: the same in every process) and the COMPILED module without what
is the machine's or the file's and not the program's (the CPU's thread
partitions, stack frame ids and the table of file names and lines).  A
later PR that means to change these steps records its own digests here
and says so in CHANGES.md; one that does not mean to has found what it
broke.

Since PR 55 a softmax mixer and the flash core's forward rule pass what a
rematerialised block keeps through ``jax.ad_checkpoint.checkpoint_name``
(``accl_tpu/utils/remat.py``), in every step: without ``remat`` the name
lowers to NO operation, but MLIR's symbol table counts it.  jax lowers each
distinct equation as a private function that it inlines and drops, named
after the primitive (``name``), and the table numbers a clashing symbol from
ONE counter: a second ``name`` at another shape (k beside q; ``lse`` beside
``o``) moves the suffix of every numbered symbol after it
(``@take_along_axis_97`` becomes ``_98``) and nothing else.  So the digests
below are held on the step as it lowers with the name taken away (the
parent's text byte for byte: none is re-recorded), and the step as the
program makes it is held to that text but for those suffixes.
"""

import dataclasses
import hashlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from accl_tpu.models import init_params, make_sharded_train_step
from perfbench import manifest

#: (cell, attention) -> (sha256 of the lowered text, of the normalised
#: compiled text).  ``None`` is the configuration's own ``auto``, which at
#: a rehearsal's length is the naive form; ``"flash"`` puts the two Pallas
#: kernels in the step (interpreted here: their traced bodies are in the
#: text), as the chip has them at the timed sizes; there only the lowered
#: text is held (the compiled text numbers the interpreter's host
#: callbacks by what the process compiled before).  The two ``"flash"``
#: digests are PR 32's, which changed the kernels' traced bodies by intent
#: (a body a tile class); the two ``None`` lowered ones are still the
#: parent's of PR 31.  The compiled ones are of the same programs, taken
#: again in PR 37 without the ops' names (``normalised``): PR 37 put the
#: embedding table's scatter-add under a scope, which renames ops and
#: changes none.
PARENT = {
    ("train_t8192_b1", None): (
        "4a3bfd2ae38f5de2aaadbca04d00a094943aeb1d7e2bd1a68ea34553a2bd33cc",
        "1e3f204462bc36b939009eea2db25e4729a8cd349d5904638579f4b19212b69d",
    ),
    ("train_olmoe_t4096_b2", None): (
        "5df31246299c5b34126f52f122132a1193099bba9663f409d25a1503efaebed5",
        "95b83023ee09847bbd51d38c81e821891f2ffb350c9c2a7a9489eaa881015622",
    ),
    ("train_t8192_b1", "flash"): (
        "14239fba61a811ec071d82588cdf88eb13010f5c51aa1471b374ecb28992f889",
        None,
    ),
    ("train_olmoe_t4096_b2", "flash"): (
        "de019a6df551f727da152016aa0ab751b90dc1cac47e9b49924762845ca3e741",
        None,
    ),
    # PR 48 edits the four files the Ling-3.0 step runs (``ops/kda.py``,
    # ``ops/pallas/kda.py``, ``ops/pallas/kda_mixer.py`` and
    # ``_kda_partial``, since PR 56 in ``models/mixers/kda.py``) for a decay gate without a bound, chosen statically:
    # the bounded gate's step is the one it was at PR 48's parent (42dead0),
    # where both digests were taken (a rehearsal's head width runs
    # ``_kda_partial`` and the XLA form of the core and of the chains;
    # ``tests/test_chip_compile.py`` holds the layer at the cell's widths,
    # where the kernels run)
    ("train_ling3_t8192_b2", None): (
        "34eccf5413322d7fe4d5ae29c09e679a20eed91bb8706fe5b16020fa635bb3ad",
        "18c31e0451d195443276ec318dff12bfeb585a88a498401214d222d8ecfa2095",
    ),
}


@pytest.fixture(autouse=True)
def scatter_add_as_at_the_timed_sizes(monkeypatch):
    """A rehearsal's table is small enough for the embedding lookup's
    cotangent to go by the one-hot matmul (``_onehot_wins``); the cells'
    timed sizes keep XLA's scatter-add, and that is the program guarded."""
    from accl_tpu.models import transformer

    monkeypatch.setattr(transformer, "_onehot_wins", lambda *shape: False)


def normalised(compiled: str) -> str:
    text = re.sub(
        r'backend_config=\{"outer_dimension_partitions":\[[^\]]*\]\}', "",
        compiled,
    )
    text = re.sub(r" stack_frame_id=\d+", "", text)
    # an op's name says which scopes and transforms traced it, not what it is
    text = re.sub(r', metadata=\{op_name="[^"]*"\}', "", text)
    start = text.find("\nFileNames")
    if start >= 0:
        text = text[:start] + text[text.find("\n\n", text.find("\nStackFrames")):]
    return text


def step_texts(cell_name: str, attention=None, compiled=True):
    cell = manifest.cell(manifest.load(), cell_name, rehearse=True)
    driver = importlib.import_module(
        "perfbench.drivers." + cell["traffic"]["driver"]
    )
    cfg = driver.program_config(cell["config"])
    if attention is not None:
        cfg = dataclasses.replace(cfg, attention=attention)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    step, _ = make_sharded_train_step(
        cfg, mesh, lr=float(cell["traffic"]["lr"])
    )
    params = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct(
        (int(cell["traffic"]["batch"]), int(cell["traffic"]["seq"])), jnp.int32
    )
    lowered = step.lower(params, tok, tok)
    if not compiled:
        return lowered.as_text(), None
    return lowered.as_text(), normalised(lowered.compile().as_text())


def _without_symbol_counters(lowered: str) -> str:
    """The lowered text without the number MLIR's symbol table appends to a
    private symbol whose name was taken."""
    return re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered)


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("cell,attention", list(PARENT))
def test_rehearsal_size_step_is_the_program_it_was_at_the_parent(
    cell, attention, monkeypatch
):
    from accl_tpu.utils import remat

    as_the_program_makes_it, _ = step_texts(cell, attention, compiled=False)
    monkeypatch.setattr(remat, "checkpoint_name", lambda value, name: value)
    lowered, compiled = step_texts(cell, attention)
    want_lowered, want_compiled = PARENT[cell, attention]
    assert _sha(lowered) == want_lowered
    assert want_compiled in (None, _sha(compiled))
    # the names a block would keep under ``remat`` add no operation, operand
    # or function to a step without it
    assert _without_symbol_counters(as_the_program_makes_it) == (
        _without_symbol_counters(lowered)
    )
