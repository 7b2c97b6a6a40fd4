"""What PR 45 adds to the benchmark, checked by hand on the CPU: the manifest's
additions, ``flops_nemotron3.py`` against hand arithmetic (the cases
``test_flops.py`` would hold: a PR that adds a cell edits no file the
benchmark has), the six new readers on a compiled module's text and a trace
written by hand (the SSD core's time inside a loop's body), what they read
from a program without the scopes and counters (the parent's side of a
traced run), and the driver's own pieces (``tests/test_bench_harness.py``
runs the cell's rehearsal with every other cell's)."""

import pytest

from perfbench import flops, flops_nemotron3
from perfbench import manifest
from perfbench.layer_metrics import (
    embed_grad_time_share,
    latent_moe_proj_time_share,
    mamba_proj_time_share,
    moe_held_entry_share,
    moe_load_imbalance,
    moe_route_time_share,
    moe_shared_time_share,
    moe_time_share,
    nemotron3_expert_roofline_share,
    nemotron3_mfu,
    ssd_core_roofline_share,
    ssd_core_time_share,
)

CELL = "train_nemotron3_t8192_b1"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = (nemotron3_mfu, ssd_core_time_share, ssd_core_roofline_share,
       mamba_proj_time_share, latent_moe_proj_time_share,
       nemotron3_expert_roofline_share)
JOINED = (
    "train_tokens_per_s", "device_idle_share", "moe_time_share",
    "moe_route_time_share", "moe_load_imbalance", "moe_held_entry_share",
    "moe_shared_time_share", "embed_grad_time_share",
)


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the manifest --------------------------------------------------------------


def test_the_manifest_gains_one_configuration_one_cell_and_six_metrics(cell):
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "nemotron3_super_train")
    assert entry["file"] == "perfbench/configs/nemotron3_super_train.json"
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers",
    ] == list(cell["config"]["reduced"])
    assert entry["source"] == cell["config"]["source"]
    assert [w["name"] for w in doc["workloads"] if w["config"] == entry["name"]] == [CELL]
    assert (cell["chips"], cell["traffic"]["seq"], cell["traffic"]["batch"],
            cell["traffic"]["driver"]) == (1, 8192, 1, "train_steps_nemotron3")
    by_name = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for reader in NEW:
        m = by_name[reader.__name__.rsplit(".", 1)[1]]
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
        assert m["unit"] == "%"
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL, name
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported == set(JOINED[1:]) | {"peak_hbm"} | {
        r.__name__.rsplit(".", 1)[1] for r in NEW
    }
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s", "setup_s"
    }
    # one four-chip cell of nine: the second slot stays open
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    assert len(doc["workloads"]) == 9


# -- flops_nemotron3.py --------------------------------------------------------


def test_a_blocks_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    assert flops_nemotron3.layer_letters(cfg) == "MEMEMEMEM*E"
    # W_in 4096 x (8192 + 8192 + 2 x 1024 + 128), W_out 8192 x 4096
    assert flops_nemotron3.mamba_matmul_params(cfg) == (
        4096 * 18_560 + 8192 * 4096
    ) == 109_576_192
    assert flops_nemotron3.attention_matmul_params(cfg) == (
        2 * 4096 * 4096 + 2 * 4096 * 256
    ) == 35_651_584
    assert flops_nemotron3.expert_params(cfg) == 2 * 1024 * 2688 == 5_505_024
    assert flops_nemotron3.expert_block_resident_params(cfg) == (
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    ) == 54_525_952
    assert flops_nemotron3.resident_matmul_params(cfg) == (
        5 * 109_576_192 + 35_651_584 + 5 * 54_525_952 + 4096 * 16384
    ) == 923_271_168


def test_the_ssd_cores_count_by_hand(cell):
    cfg = cell["config"]
    L, P, N = 128, 64, 128
    a_chunk = (
        N * L * (L + 1) / 16        # tril(C B^T), once a group of 16 heads
        + P * L * (L + 1)           # the masked product with x
        + 2 * 2 * L * P * N         # x^T B into the state, C S out of it
    )
    assert a_chunk == 5_383_168
    # forward and twice that backward, 128 heads, 64 chunks a sequence
    assert flops_nemotron3.ssd_core_train_flops(cfg, 8192) == 3.0 * 128 * 64 * a_chunk
    assert flops_nemotron3.ssd_core_train_flops(cfg, 8192) / 1e9 == pytest.approx(
        132.30, abs=0.01
    )
    # a tail of a chunk counts as a chunk
    assert flops_nemotron3.ssd_core_train_flops(cfg, 8193) == 3.0 * 128 * 65 * a_chunk
    # x, B and C in bf16, dt in float32 a head: 20,992 bytes a token; read
    # forward, read backward, the gradients written; y written forward and
    # its cotangent read backward
    inputs = (8192 + 2 * 1024) * 2 + 128 * 4
    assert inputs == 20_992
    assert flops_nemotron3.ssd_core_train_bytes(cfg, 8192) == 8192 * (
        3 * inputs + 2 * 8192 * 2
    ) == 784_334_848
    least, bound = flops.roofline_seconds(
        flops_nemotron3.ssd_core_train_flops(cfg, 8192),
        flops_nemotron3.ssd_core_train_bytes(cfg, 8192), PEAKS,
    )
    # 0.67 ms of MXU against 0.96 ms of HBM a sequence a block
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(0.958, abs=0.001)


def test_attention_and_train_flops_by_hand(cell):
    cfg = cell["config"]
    pairs = 8192 * 8193 // 2
    # QK^T and PV forward, four such products backward: 12 x pairs x 4096
    assert flops_nemotron3.attention_train_flops(cfg, 8192) == 12.0 * pairs * 4096
    per_token = flops_nemotron3.train_flops_per_token(cfg, 8192, 5 * 2.75)
    assert per_token == (
        6.0 * 923_271_168 + 6.0 * 5_505_024 * 13.75
        + (5 * flops_nemotron3.ssd_core_train_flops(cfg, 8192)
           + flops_nemotron3.attention_train_flops(cfg, 8192)) / 8192
    )
    # 6.28 GFLOP a token, 51 TFLOP a step of 8,192: 261 ms at the peak
    assert per_token / 1e9 == pytest.approx(6.276, abs=1e-3)
    assert per_token * 8192 / 197e12 * 1e3 == pytest.approx(261.0, abs=0.1)


def test_expert_roofline_terms_by_hand(cell):
    cfg = cell["config"]
    held = 5 * 22528.0          # a balanced step: 22,528 entries a block
    f = flops_nemotron3.expert_train_flops(cfg, held)
    assert f == 6.0 * held * 5_505_024
    b = flops_nemotron3.expert_train_bytes(cfg, held)
    assert b == 6 * (held * (1024 + 2688) + 5 * 64 * 1024 * 2688) * 2
    least, bound = flops.roofline_seconds(f, b, PEAKS)
    # 352 rows an expert: the weights' bytes, not the MXU (19.0 against
    # 18.9 ms a step: the two bounds meet here)
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(19.03, abs=0.05)
    assert f / 197e12 * 1e3 == pytest.approx(18.89, abs=0.05)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

%cond.1 (c: f32[8]) -> pred[] {
  %c = f32[8]{0} parameter(0)
  ROOT %compare.40 = pred[] compare(%c, %c), direction=LT, metadata={op_name="jit(step)/jvp(accl.attn::ssd)/while/cond/lt"}
}

%body.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.20 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::ssd)/while/body/mul"}
  ROOT %fusion.21 = f32[8]{0} fusion(%fusion.20), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::ssd)/while/body/add"}
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::mamba_proj)/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::ssd)/exp"}
  %while.3 = f32[8]{0} while(%a), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(accl.attn::ssd)/while"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::latent)/dot_general"}
  %flash_fwd.5 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::core)/flash_fwd/pallas_call"}
  %gmm_fwd.7 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::shared)/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
  ROOT %fusion.10 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.attn::mamba_proj))/dot_general"}
}
'''


def _ctx(cell, scopes=True, router=True, mixers=True):
    from perfbench import scope_ops
    from perfbench.drivers.train_steps_ling3 import scoped_instructions

    reduced = {
        "host": [["bench::step", 0.0, 2000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["fusion.1 fusion f32[8]", 0, 200.0],
            ["fusion.2 fusion f32[8]", 200, 50.0],
            # the loop shows as an event round its body's two, twice over
            ["while.3 while f32[8]", 250, 300.0],
            ["fusion.20 fusion f32[8]", 260, 100.0],
            ["fusion.21 fusion f32[8]", 360, 40.0],
            ["fusion.20 fusion f32[8]", 400, 100.0],
            ["fusion.21 fusion f32[8]", 500, 40.0],
            ["fusion.4 fusion f32[8]", 550, 150.0],
            ["flash_fwd.5 custom-call tpu_custom_call f32[8]", 700, 300.0],
            ["gmm_fwd.7 custom-call tpu_custom_call f32[8]", 1000, 100.0],
            ["fusion.8 fusion f32[8]", 1100, 100.0],
            ["fusion.9 fusion f32[8]", 1200, 100.0],
            ["fusion.10 fusion f32[8]", 1400, 400.0],
        ]},
    }
    facts = {
        "tokens_per_s": 8000.0, "tokens_per_step": 8192, "seq": 8192,
        "batch": 1, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
        facts["scope_ops_all"] = {
            s: n for s, n in scoped_instructions(HLO).items()
            if s in ("accl.attn::ssd", "accl.attn::mamba_proj", "accl.moe::latent")
        }
    if router:
        facts["router"] = {
            "held_entries": [22000, 22500, 22528, 22900, 22712],
            "held_entry_share": 12.5, "load_imbalance": 1.2,
        }
    if mixers:
        facts["mixers"] = {"mamba_layers": 5, "attention_layers": 1,
                           "expert_layers": 5, "ssd_chunk": 128}
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 2000.0)}},
    }


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 1700.0               # idle from 1300 to 1400 and from 1800 on
    # the loop's event and its body's are one stretch: 50 + 300, not 630
    assert ssd_core_time_share.read(ctx) == pytest.approx(100 * 350 / busy)
    assert mamba_proj_time_share.read(ctx) == pytest.approx(100 * 600 / busy)
    assert latent_moe_proj_time_share.read(ctx) == pytest.approx(100 * 150 / busy)
    least = 5 * flops_nemotron3.ssd_core_train_bytes(cfg, 8192) / 819e9
    assert ssd_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 350
    )
    # ``moe_time_share`` sums every ``accl.moe::`` scope: the latent too
    assert moe_time_share.read(ctx) == pytest.approx(100 * 450 / busy)
    assert moe_shared_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    assert moe_route_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    held = 112640.0
    least, bound = flops.roofline_seconds(
        flops_nemotron3.expert_train_flops(cfg, held),
        flops_nemotron3.expert_train_bytes(cfg, held), PEAKS,
    )
    assert nemotron3_expert_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 100
    )
    assert nemotron3_mfu.read(ctx) == pytest.approx(
        100 * flops_nemotron3.train_flops_per_token(cfg, 8192, 13.75)
        * 8000 / 197e12
    )
    assert moe_held_entry_share.read(ctx) == 12.5
    assert moe_load_imbalance.read(ctx) == 1.2
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
    ctx["facts"].pop("scope_ops_all")
    ctx["facts"]["mixers"] = {"kda_layers": 6, "mla_layers": 1, "kda_chunk": 64}
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["slices"] = {}
    for reader in NEW[1:]:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops_all"].pop("accl.attn::ssd")
    assert ssd_core_time_share.read(ctx) is None
    assert ssd_core_roofline_share.read(ctx) is None
    assert mamba_proj_time_share.read(ctx) is not None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_program_and_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_nemotron3 as driver

    cfg = cell["config"]
    assert driver.reference_model(cfg) == dict(
        mamba_num_heads=128, mamba_head_dim=64, n_groups=8,
        ssm_state_size=128, n_head=32, n_kv_head=2, top_k=22,
        routed_scaling_factor=5.0, first_expert=0,
    )
    program = driver.program_config(cfg)
    assert (program.n_experts, program.router_experts(),
            program.moe_first_expert) == (64, 512, 0)
    assert program.moe_router == "sigmoid" and program.moe_bias_rate == 0.001
    assert program.norm_eps == 1e-5 and program.remat
    assert [program.mixer(k) for k in program.layers] == (
        ["mamba2", "none"] * 4 + ["mamba2", "attention", "none"]
    )
    assert driver.layer_letters(cfg) == flops_nemotron3.layer_letters(cfg)
    assert len(driver.BALANCE_RATES) > 0     # a fixed number of rounds
    with pytest.raises(ValueError, match="layers_kept"):
        driver.layer_letters(dict(cfg, num_hidden_layers=8))
    with pytest.raises(ValueError, match="group limit"):
        driver.program_config(dict(cfg, n_group=8))
    with pytest.raises(ValueError, match="nemotron_h"):
        driver.program_config(dict(cfg, mlp_hidden_act="silu"))
