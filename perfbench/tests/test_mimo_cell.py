"""What PR 54 adds to the benchmark, checked by hand on the CPU: the
manifest's additions FOUND BY NAME, ``flops_mimo.py`` against hand
arithmetic, what the five new readers give from a program without the scopes
and facts (the parent's side of a traced run, another cell's facts), and the
controls rehearsed (``tests/test_bench_rehearsal_solar2.py`` runs the cell's
rehearsal in tier-1; this file is not tier-1)."""

import json
import subprocess
import sys

import pytest

from perfbench import flops_mimo, manifest
from perfbench.layer_metrics import (
    mimo_expert_roofline_share,
    mimo_full_core_roofline_share,
    mimo_mfu,
    mimo_proj_time_share,
    mimo_swa_core_roofline_share,
)

CELL = "train_mimo_t8192_b1"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = (mimo_mfu, mimo_proj_time_share, mimo_swa_core_roofline_share,
       mimo_full_core_roofline_share, mimo_expert_roofline_share)
# accepted metrics whose readers find something to read in this cell: the
# sliding cores' time is ``attn_window_time_share``'s (the flash kernels under
# ``accl.attn::window``).  NOT ``flash_time_share``: its reader takes every
# Mosaic kernel for a flash kernel, true of the dense StarCoder cells alone
# (here it read 33.6 with ``gmm_*`` and ``place_rows`` where the cores are 24.9)
JOINED = ("train_tokens_per_s", "device_idle_share", "moe_time_share",
          "moe_route_time_share", "moe_load_imbalance", "moe_held_entry_share",
          "embed_grad_time_share", "attn_window_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


def test_the_manifest_gains_one_configuration_one_cell_and_five_metrics(cell):
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "mimo_v2_5_train")
    assert entry["file"] == "perfbench/configs/mimo_v2_5_train.json"
    assert entry["reduced"] == list(cell["config"]["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ]
    assert entry["source"] == cell["config"]["source"]
    assert cell["chips"] == 1 and cell["traffic"]["driver"] == "train_steps_mimo"
    assert [w["name"] for w in doc["workloads"] if w["config"] == entry["name"]] == [CELL]
    reported = {m["name"]: m for m in cell["per_layer"]}
    for reader in NEW:
        metric = reported[reader.__name__.rsplit(".", 1)[1]]
        assert metric["workloads"] == [CELL] and metric["unit"] == "%"
        assert metric["moves"] == "train_tokens_per_s"
    for name in JOINED:
        metric = next(
            m for m in doc["end_to_end"] + doc["per_layer"] if m["name"] == name
        )
        assert CELL in metric["workloads"]
    for name in ("attn_window_roofline_share", "flash_roofline_share",
                 "flash_time_share", "afmoe_expert_roofline_share",
                 "moe_shared_time_share"):
        metric = next(m for m in doc["per_layer"] if m["name"] == name)
        assert CELL not in metric["workloads"]


def test_the_counts_by_hand(cell):
    cfg = cell["config"]
    assert flops_mimo.layer_kinds(cfg) == (
        [(False, False)] + [(True, True)] * 5 + [(False, True)]
    )
    assert flops_mimo.mixer_params(cfg, False) == (
        4096 * 64 * 192 + 4096 * 4 * (192 + 128) + 64 * 128 * 4096
    ) == 89_128_960
    assert flops_mimo.mixer_params(cfg, True) == 94_371_840
    assert flops_mimo.expert_params(cfg) == 3 * 4096 * 2048
    assert flops_mimo.matmul_params(cfg) == (
        290_455_552 + 5 * 498_073_600 + 492_830_720 + 156_237_824
    ) == 3_429_892_096
    assert flops_mimo.resident_matmul_params(cfg) == (
        2 * 89_128_960 + 5 * 94_371_840 + 201_326_592 + 6 * 4096 * 256
        + 19072 * 4096
    )
    assert flops_mimo.attended_pairs(8192, 128) == 8192 * 128 - 128 * 127 // 2
    assert flops_mimo.attended_pairs(8192) == 8192 * 8193 // 2
    assert flops_mimo.attention_train_flops(cfg, 8192, True) == (
        3 * 2 * 1_040_448 * 64 * (192 + 128)
    )
    q, o = 8192 * 64 * 192 * 2, 8192 * 64 * 128 * 2
    kv = 8192 * 4 * (192 + 128) * 2
    assert flops_mimo.attention_train_bytes(cfg, 8192, False) == (
        3 * (q + kv) + 3 * o
    )
    # 3 held entries a token (8 of 256 over 6 layers, a sixteenth held)
    per_token = flops_mimo.train_flops_per_token(cfg, 8192, 3.0)
    assert 7.0e9 < per_token < 7.3e9            # 58.6 TFLOP a step of 8,192


@pytest.mark.parametrize("facts", [
    {},                                              # the parent: nothing
    {"tokens_per_s": 1.0, "seq": 8192, "batch": 1, "traced_steps": 3,
     "tokens_per_step": 16384, "router": {"held_entries": [1, 2]},
     "mixers": {"kda_layers": 3, "gqa_layers": 1}},  # another cell's facts
])
def test_a_program_without_the_facts_gives_the_readers_nothing(cell, facts):
    ctx = {"cell": cell, "facts": facts, "peaks": PEAKS, "device": {}, "slices": {}}
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__


def test_the_mfu_from_a_rate(cell):
    ctx = {"cell": cell, "peaks": PEAKS, "device": {}, "slices": {}, "facts": {
        "tokens_per_s": 12000.0, "seq": 8192, "tokens_per_step": 8192,
        "router": {"held_entries": [4096] * 6},
        "mixers": {"swa_layers": 5, "full_layers": 2, "expert_layers": 6},
    }}
    want = 100.0 * flops_mimo.train_flops_per_token(
        cell["config"], 8192, 3.0
    ) * 12000.0 / 197e12
    assert mimo_mfu.read(ctx) == pytest.approx(want) and 40.0 < want < 48.0


def test_the_controls_each_end_not_correct():
    from perfbench import controls_mimo as controls

    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.controls_mimo", "--seed", "5",
         "--rehearse"],
        cwd=manifest.CHECKOUT, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode in (0, 1), (proc.stdout[-2000:], proc.stderr[-2000:])
    *lines, last = map(json.loads, proc.stdout.strip().splitlines())
    assert [l["control"] for l in lines] == ["sound", *controls.CONTROLS]
    # at the rehearsal's hidden size of 128 a score's std is 0.05 (1.6 at the
    # published 4,096), so WHICH theta a layer rotates by moves the logits by
    # 1%, under the limits read on the chip in bf16: the one control the
    # rehearsal cannot show (on the chip it reads 70%)
    assert {name for _, name in last["wrong"]} <= {"thetas_swapped"}
    assert lines[0]["correct"]
