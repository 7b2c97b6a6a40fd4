"""What PR 52 adds to the benchmark, checked by hand on the CPU: the
manifest's additions, ``flops_olmoh.py`` against hand arithmetic, what the
four new readers give from a program without the scopes and facts (the
parent's side of a traced run, another cell's facts), and the controls
rehearsed (``tests/test_bench_rehearsal_solar2.py`` runs the cell's
rehearsal in tier-1; this file is not tier-1)."""

import json
import subprocess
import sys

import pytest

from perfbench import flops_olmoh, manifest
from perfbench.layer_metrics import (
    olmoh_attn_core_roofline_share,
    olmoh_attn_core_time_share,
    olmoh_gdn_core_roofline_share,
    olmoh_mfu,
)

CELL = "train_olmoh_t8192_b1"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = (olmoh_mfu, olmoh_gdn_core_roofline_share, olmoh_attn_core_time_share,
       olmoh_attn_core_roofline_share)
JOINED = ("train_tokens_per_s", "device_idle_share", "embed_grad_time_share",
          "kda_core_time_share", "kda_proj_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


def test_the_manifest_gains_one_configuration_one_cell_and_four_metrics(cell):
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "olmo_hybrid_7b_train")
    assert entry["file"] == "perfbench/configs/olmo_hybrid_7b_train.json"
    assert entry["reduced"] == ["num_hidden_layers"] == list(cell["config"]["reduced"])
    assert entry["source"] == cell["config"]["source"]
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train_steps_olmoh"
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-4:] == [reader.__name__.rsplit(".", 1)[1] for reader in NEW]
    for name in JOINED:
        metric = next(
            m for m in doc["end_to_end"] + doc["per_layer"] if m["name"] == name
        )
        assert metric["workloads"][-1] == CELL
    for name in ("kda_core_roofline_share", "flash_roofline_share", "moe_time_share"):
        metric = next(m for m in doc["per_layer"] if m["name"] == name)
        assert CELL not in metric["workloads"]


def test_the_counts_by_hand(cell):
    cfg = cell["config"]
    assert flops_olmoh.layer_mixers(cfg) == ["linear"] * 3 + ["full"]
    assert flops_olmoh.linear_matmul_params(cfg) == (
        3840 * 30 * (2 * 96 + 3 * 192) + 2 * 3840 * 30
    ) == 88_704_000
    assert flops_olmoh.full_matmul_params(cfg) == 4 * 3840 * 3840
    assert flops_olmoh.mlp_params(cfg) == 3 * 3840 * 11008
    assert flops_olmoh.matmul_params(cfg) == 1_217_694_720
    C, dk, dv = 64, 96, 192
    chunk = (dk * C * (C - 1) + dk * C * (C + 1) + (dk + dv) * C * (C - 1)
             + 6 * C * dk * dv + dv * C * (C + 1))
    assert flops_olmoh.gdn_core_train_flops(cfg, 8192) == 3 * 30 * 128 * chunk
    inputs = (2 * dk + dv) * 2 + 4 + 4
    assert flops_olmoh.gdn_core_train_bytes(cfg, 8192) == 8192 * 30 * (
        2 * (inputs + dv * 2) + inputs
    )
    pairs = 8192 * 8193 // 2
    assert flops_olmoh.attn_core_train_flops(cfg, 8192) == 12 * pairs * 3840
    assert flops_olmoh.attn_core_train_bytes(cfg, 8192) == 12 * 8192 * 3840 * 2
    per_token = flops_olmoh.train_flops_per_token(cfg, 8192)
    assert 7.5e9 < per_token < 7.6e9           # 61.7 TFLOP a step of 8,192


@pytest.mark.parametrize("facts", [
    {},                                              # the parent: nothing
    {"tokens_per_s": 1.0, "seq": 8192, "batch": 1, "traced_steps": 3,
     "mixers": {"kda_layers": 3, "gqa_layers": 1}},  # the Solar cell's facts
])
def test_a_program_without_the_facts_gives_the_readers_nothing(cell, facts):
    ctx = {"cell": cell, "facts": facts, "peaks": PEAKS, "device": {}, "slices": {}}
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__


def test_the_mfu_from_a_rate(cell):
    ctx = {"cell": cell, "peaks": PEAKS, "device": {}, "slices": {}, "facts": {
        "tokens_per_s": 10000.0, "seq": 8192,
        "mixers": {"linear_layers": 3, "full_layers": 1},
    }}
    want = 100.0 * flops_olmoh.train_flops_per_token(cell["config"], 8192) * 1e4 / 197e12
    assert olmoh_mfu.read(ctx) == pytest.approx(want) and 30.0 < want < 45.0


def test_the_controls_each_end_not_correct():
    from perfbench import controls_olmoh as controls

    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.controls_olmoh", "--seed", "5",
         "--rehearse"],
        cwd=manifest.CHECKOUT, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    *lines, last = map(json.loads, proc.stdout.strip().splitlines())
    assert last == {"controls": "ok", "wrong": []}
    assert [l["control"] for l in lines] == ["sound", *controls.CONTROLS]
