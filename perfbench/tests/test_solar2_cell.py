"""What PR 48 adds to the benchmark, checked by hand on the CPU: the
manifest's additions, ``flops_solar2.py`` against hand arithmetic, the five
new readers on a compiled module's text and a trace written by hand, what
they read from a program without the scopes and counters (the parent's side
of a traced run, another cell's facts), and the driver's own pieces
(``tests/test_bench_harness.py`` runs the cell's rehearsal with every other
cell's)."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import flops, flops_solar2
from perfbench import manifest
from perfbench.layer_metrics import (
    embed_grad_time_share,
    kda_core_time_share,
    kda_proj_time_share,
    moe_held_entry_share,
    moe_shared_time_share,
    moe_time_share,
    solar2_expert_roofline_share,
    solar2_gqa_core_roofline_share,
    solar2_gqa_core_time_share,
    solar2_kda_core_roofline_share,
    solar2_mfu,
)

CELL = "train_solar2_t8192_b1"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = (solar2_mfu, solar2_kda_core_roofline_share, solar2_gqa_core_time_share,
       solar2_gqa_core_roofline_share, solar2_expert_roofline_share)
JOINED = (
    "train_tokens_per_s", "device_idle_share", "moe_time_share",
    "moe_route_time_share", "moe_load_imbalance", "moe_held_entry_share",
    "moe_shared_time_share", "embed_grad_time_share", "kda_core_time_share",
    "kda_proj_time_share",
)


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the manifest --------------------------------------------------------------


def test_the_manifest_gains_one_configuration_one_cell_and_five_metrics(cell):
    doc = manifest.load()
    entry = doc["configs"][-1]
    assert entry["name"] == "solar_open2_train"
    assert entry["file"] == "perfbench/configs/solar_open2_train.json"
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
    ] == list(cell["config"]["reduced"])
    assert entry["source"] == cell["config"]["source"]
    assert doc["workloads"][-1]["name"] == CELL
    assert (cell["chips"], cell["traffic"]["seq"], cell["traffic"]["batch"],
            cell["traffic"]["driver"]) == (1, 8192, 1, "train_steps_solar2")
    assert [m["name"] for m in doc["per_layer"][-5:]] == [
        r.__name__.rsplit(".", 1)[1] for r in NEW
    ]
    by_name = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for m in doc["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert m["unit"] == "%"
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in ("kda_core_roofline_share", "moe_group_hit_share",
                 "flash_time_share", "mla_core_time_share"):
        assert CELL not in by_name[name]["workloads"], name
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == set(JOINED[1:]) | {"peak_hbm"} | {
        r.__name__.rsplit(".", 1)[1] for r in NEW
    }
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s", "setup_s"
    }
    # one four-chip cell of ten: the second slot stays open
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    assert len(doc["workloads"]) == 10


# -- flops_solar2.py -----------------------------------------------------------


def test_a_layers_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    assert flops_solar2.layer_mixers(cfg) == ["gqa", "kda", "kda", "kda"]
    # wq, wk, wv, wo 4096 x 8192; two gates 4096 x 128 and 128 x 8192; beta
    assert flops_solar2.kda_matmul_params(cfg) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    ) == 137_625_600
    # wq, the gate a channel and wo 4096 x 8192; wk, wv 4096 x 1024
    assert flops_solar2.gqa_matmul_params(cfg) == (
        3 * 4096 * 8192 + 2 * 4096 * 1024
    ) == 109_051_904
    assert flops_solar2.expert_params(cfg) == 3 * 4096 * 1280 == 15_728_640
    assert flops_solar2.resident_matmul_params(cfg) == (
        3 * 137_625_600 + 109_051_904 + 4 * (4096 * 320 + 15_728_640)
        + 4096 * 24576
    ) == 690_749_440
    # the cut, by the issue's count: mixers with their taps, scalars and
    # norms, the router with its bias, 40 experts, table and head
    assert flops_solar2.parameter_count(cfg) == 3_308_353_344


def test_the_cores_counts_by_hand(cell):
    cfg = cell["config"]
    C, d = 64, 128
    a_chunk = (
        d * C * (C - 1) + d * C * (C + 1) + 2 * d * C * (C - 1)
        + 3 * 2 * C * d * d + d * C * (C + 1)
    )
    assert flops_solar2.kda_core_train_flops(cfg, 8192) == 3.0 * 64 * 128 * a_chunk
    assert flops_solar2.kda_core_train_flops(cfg, 8193) == 3.0 * 64 * 129 * a_chunk
    inputs = 3 * 128 * 2 + 128 * 4 + 4
    assert flops_solar2.kda_core_train_bytes(cfg, 8192) == 8192 * 64 * (
        3 * inputs + 2 * 128 * 2
    )
    least, bound = flops.roofline_seconds(
        flops_solar2.kda_core_train_flops(cfg, 8192),
        flops_solar2.kda_core_train_bytes(cfg, 8192), PEAKS,
    )
    # a layer: 0.218 TFLOP against 2.29 GB: bytes, 2.79 ms
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(2.79, abs=0.01)
    pairs = 8192 * 8193 // 2
    assert flops_solar2.gqa_core_train_flops(cfg, 8192) == 12.0 * pairs * 8192
    q, k = 8192 * 8192 * 2, 8192 * 1024 * 2
    assert flops_solar2.gqa_core_train_bytes(cfg, 8192) == 7 * q + 6 * k
    least, bound = flops.roofline_seconds(
        flops_solar2.gqa_core_train_flops(cfg, 8192),
        flops_solar2.gqa_core_train_bytes(cfg, 8192), PEAKS,
    )
    assert bound == "compute" and least * 1e3 == pytest.approx(16.75, abs=0.02)


def test_train_flops_and_the_expert_roofline_by_hand(cell):
    cfg = cell["config"]
    per_token = flops_solar2.train_flops_per_token(cfg, 8192, 4.0)
    assert per_token == (
        6.0 * 690_749_440 + 6.0 * 15_728_640 * 4.0
        + (3 * flops_solar2.kda_core_train_flops(cfg, 8192)
           + flops_solar2.gqa_core_train_flops(cfg, 8192)) / 8192
    )
    # 5.01 GFLOP a token, 41.0 TFLOP a step of 8,192: 208 ms at the peak
    assert per_token / 1e9 == pytest.approx(5.005, abs=2e-3)
    assert per_token * 8192 / 197e12 * 1e3 == pytest.approx(208.1, abs=0.2)
    held = 4 * 8192.0           # a balanced step: 8,192 entries a layer
    f = flops_solar2.expert_train_flops(cfg, held)
    assert f == 6.0 * held * 15_728_640
    b = flops_solar2.expert_train_bytes(cfg, held)
    assert b == 9 * (held * (4096 + 1280) + 4 * 40 * 4096 * 1280) * 2
    least, bound = flops.roofline_seconds(f, b, PEAKS)
    # 205 rows an expert: the weights' bytes (22.3 ms a step), not the MXU
    # (15.7)
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(22.31, abs=0.05)
    assert f / 197e12 * 1e3 == pytest.approx(15.70, abs=0.05)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::kda_proj)/dot_general"}
  %kda_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::kda)/kda_fwd/pallas_call"}
  %kda_bwd.3 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(accl.attn::kda))/kda_bwd/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::gqa_proj)/dot_general"}
  %flash_fwd.5 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::core)/flash_fwd/pallas_call"}
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::core)/transpose"}
  %gmm_fwd.7 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::shared)/dot_general"}
  ROOT %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
}
'''


def _ctx(cell, scopes=True, router=True, mixers=True):
    from perfbench import scope_ops
    from perfbench.drivers.train_steps_ling3 import scoped_instructions

    reduced = {
        "host": [["bench::step", 0.0, 2000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["fusion.1 fusion f32[8]", 0, 200.0],
            ["kda_fwd.2 custom-call tpu_custom_call f32[8]", 200, 150.0],
            ["kda_bwd.3 custom-call tpu_custom_call f32[8]", 350, 250.0],
            ["fusion.4 fusion f32[8]", 600, 100.0],
            ["flash_fwd.5 custom-call tpu_custom_call f32[8]", 700, 300.0],
            ["fusion.6 fusion f32[8]", 1000, 50.0],
            ["gmm_fwd.7 custom-call tpu_custom_call f32[8]", 1100, 100.0],
            ["fusion.8 fusion f32[8]", 1200, 100.0],
            ["fusion.9 fusion f32[8]", 1300, 100.0],
        ]},
    }
    facts = {
        "tokens_per_s": 13000.0, "tokens_per_step": 8192, "seq": 8192,
        "batch": 1, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
        facts["scope_ops_all"] = {
            s: n for s, n in scoped_instructions(HLO).items()
            if s.startswith("accl.attn::kda")
        }
    if router:
        facts["router"] = {
            "held_entries": [8000, 8192, 8300, 8276],
            "held_entry_share": 12.5, "load_imbalance": 1.2,
        }
    if mixers:
        facts["mixers"] = {"kda_layers": 3, "gqa_layers": 1, "expert_layers": 4}
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 2000.0)}},
    }


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 1350.0               # idle from 1050 to 1100 and from 1400 on
    assert kda_core_time_share.read(ctx) == pytest.approx(100 * 400 / busy)
    assert kda_proj_time_share.read(ctx) == pytest.approx(100 * 200 / busy)
    least = 3 * flops_solar2.kda_core_train_bytes(cfg, 8192) / 819e9
    assert solar2_kda_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 400
    )
    # the flash kernels under the scope, not the transpose beside them
    assert solar2_gqa_core_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    least = flops_solar2.gqa_core_train_flops(cfg, 8192) / 197e12
    assert solar2_gqa_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 300
    )
    assert moe_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    assert moe_shared_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    held = 32768.0
    least, bound = flops.roofline_seconds(
        flops_solar2.expert_train_flops(cfg, held),
        flops_solar2.expert_train_bytes(cfg, held), PEAKS,
    )
    assert solar2_expert_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 100
    )
    assert solar2_mfu.read(ctx) == pytest.approx(
        100 * flops_solar2.train_flops_per_token(cfg, 8192, 4.0)
        * 13000 / 197e12
    )
    assert moe_held_entry_share.read(ctx) == 12.5
    assert embed_grad_time_share.read(ctx) is None      # no such scope here


def test_a_program_without_the_scopes_or_the_counters_reads_as_nothing(cell):
    """The parent's side of a traced run (it fails before a trace: the
    readers must not raise on any other program's facts either), and a
    rehearsal."""
    ctx = _ctx(cell, scopes=False, router=False, mixers=False)
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__
    # another cell's facts: scopes and a router, Ling's ``mixers``
    ctx = _ctx(cell)
    ctx["facts"]["mixers"] = {"kda_layers": 6, "mla_layers": 1, "kda_chunk": 64}
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["slices"] = {}
    for reader in NEW[1:]:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops_all"].pop("accl.attn::kda")
    assert solar2_kda_core_roofline_share.read(ctx) is None
    assert solar2_gqa_core_time_share.read(ctx) is not None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_program_and_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_solar2 as driver

    cfg = cell["config"]
    assert driver.reference_model(cfg) == dict(
        n_head=64, n_kv_head=8, top_k=8, routed_scaling_factor=1.0,
        first_expert=0,
    )
    program = driver.program_config(cfg)
    assert (program.n_experts, program.router_experts(),
            program.moe_first_expert) == (40, 320, 0)
    assert program.moe_router == "sigmoid" and program.moe_bias_rate == 0.001
    assert program.norm_eps == 1e-5 and program.remat
    assert program.kda.lower_bound is None and program.kda.beta_scale == 2.0
    assert [program.mixer(k) for k in program.layers] == [
        "attention", "kda", "kda", "kda"
    ]
    assert driver.layer_mixers(cfg) == flops_solar2.layer_mixers(cfg)
    assert len(driver.BALANCE_RATES) > 0     # a fixed number of rounds
    with pytest.raises(ValueError, match="layers_kept"):
        driver.layer_mixers(dict(cfg, num_hidden_layers=8))
    with pytest.raises(ValueError, match="without position"):
        driver.program_config(dict(cfg, use_rope=True))
    with pytest.raises(ValueError, match="solar_open2"):
        driver.program_config(dict(cfg, model_type="bailing_hybrid"))


def test_the_controls_each_end_not_correct_rehearsed():
    """``perfbench/controls_solar2.py``, rehearsed: the cell's own ``judge``
    ends correct on the sound reference and not correct on every planted
    fault."""
    from perfbench import controls_solar2 as controls

    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.controls_solar2", "--seed", "5",
         "--rehearse"],
        cwd=manifest.CHECKOUT, env=base, capture_output=True, text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    *lines, last = map(json.loads, proc.stdout.strip().splitlines())
    assert last == {"controls": "ok", "wrong": []}
    assert [l["control"] for l in lines] == ["sound", *controls.CONTROLS]
