"""``utils/profiling.py``'s table of device scopes, held to the code as
``tests/test_window_spans.py`` holds the table of host spans: every row
names a scope some train step really carries, and every
``device_scope("...")`` the package makes has its row.

The benchmark's readers (``perfbench/scope_ops.py`` and the
``*_time_share`` metrics) find a layer's device time by these names, so
a scope renamed in the program or a row that names nothing would read
as 0 on the chip.  The steps are the model tests' own tiny
configurations, lowered on the CPU mesh and not run.
"""

import functools
import importlib
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import accl_tpu
from accl_tpu.models import make_sharded_train_step
from accl_tpu.utils import profiling

#: the table's rows: between the paragraph that introduces the device
#: scopes and the one on the counters after it
ROWS = re.findall(
    r"^``(accl\.\w+::\w+)``",
    profiling.__doc__.split("Device scopes (:func:`device_scope`)")[1]
    .split("Counters of the same paths")[0],
    re.MULTILINE,
)

#: row -> the smallest configuration that has the row's mechanism
WHERE = {
    "accl.attn::core": "trinity",          # its one full-attention layer
    "accl.attn::window": "trinity",        # its two sliding layers
    "accl.attn::latent": "deepseek_v2",
    "accl.attn::mla": "deepseek_v2",
    "accl.attn::kda": "ling3",
    "accl.attn::kda_proj": "ling3",
    "accl.attn::ssd": "nemotron3",
    "accl.attn::mamba_proj": "nemotron3",
    "accl.attn::gqa_proj": "solar2",       # a softmax layer beside KDA ones
    "accl.attn::blockdiff": "sdar",
    "accl.diffusion::noise": "sdar",
    "accl.loss::diffusion": "sdar",
    "accl.moe::route": "olmoe",
    "accl.moe::dispatch": "olmoe",
    "accl.moe::experts": "olmoe",
    "accl.moe::combine": "olmoe",
    "accl.moe::shared": "deepseek_v2",     # its two shared experts
    "accl.moe::latent": "nemotron3",
    "accl.embed::grad": "olmoe",           # any step: the lookup's backward
}


@functools.lru_cache(maxsize=None)
def _step_text(model):
    """The lowered train step of ``tests/test_<model>.py``'s ``CFG`` on
    one device, with the locations that carry the scopes."""
    module = importlib.import_module("test_" + model)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
    step, shard = make_sharded_train_step(module.CFG, mesh, lr=1.0)
    # (block diffusion trains on ids and a noise key, not on targets)
    batch = (
        (module._tokens(), module.KEY) if model == "sdar" else module._batch()
    )
    return step.lower(
        shard(module._params()), *batch
    ).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ROWS)
def test_a_row_of_the_scope_table_names_a_scope_of_a_train_step(scope):
    assert scope in WHERE, f"{scope}: name the configuration that has it"
    # (``accl.attn::kda`` is not found in ``accl.attn::kda_proj``)
    assert re.search(re.escape(scope) + r"(?!\w)", _step_text(WHERE[scope])), (
        f"no instruction of the {WHERE[scope]} step is under {scope}"
    )


def test_every_device_scope_the_package_makes_has_its_row():
    root = os.path.dirname(accl_tpu.__file__)
    made = {}
    for base, _, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(base, name)
                with open(path) as f:
                    for scope in re.findall(
                        r"""device_scope\(\s*["']([^"']+)["']""", f.read()
                    ):
                        made.setdefault(scope, os.path.relpath(path, root))
    assert made, "no device_scope literal found: the pattern is stale"
    missing = {s: p for s, p in made.items() if s not in ROWS}
    assert not missing, f"no row in utils/profiling.py's table: {missing}"
    assert set(ROWS) <= set(made), set(ROWS) - set(made)
