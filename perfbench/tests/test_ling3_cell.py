"""What PR 40 adds to the benchmark, checked by hand on the CPU: the
configuration file against the catalog's row, ``flops_ling3.py`` against
hand arithmetic (the cases ``test_flops.py`` would hold: a PR that adds a
cell edits no file the benchmark has), the six new readers on a compiled
module's text and a trace written by hand (the KDA core's time inside a
loop's body), what they read from a program without the scopes and counters
(the parent's side of a traced run), and the driver's own pieces
(``test_rehearsal.py`` runs the cell's rehearsal with every other
cell's)."""

import json

import pytest

from perfbench import flops, flops_ling3
from perfbench import manifest
from perfbench.layer_metrics import (
    embed_grad_time_share,
    kda_core_roofline_share,
    kda_core_time_share,
    kda_proj_time_share,
    ling3_expert_roofline_share,
    ling3_mfu,
    ling3_mla_core_roofline_share,
    mla_core_roofline_share,
    mla_core_time_share,
    mla_latent_time_share,
    moe_group_hit_share,
    moe_held_entry_share,
    moe_load_imbalance,
    moe_route_time_share,
    moe_shared_time_share,
    moe_time_share,
)

CELL = "train_ling3_t8192_b2"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = (ling3_mfu, kda_core_time_share, kda_core_roofline_share,
       kda_proj_time_share, ling3_mla_core_roofline_share,
       ling3_expert_roofline_share)


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the manifest and the configuration file ------------------------------------


def test_the_manifest_gains_one_configuration_one_cell_and_six_metrics(cell):
    doc = manifest.load()
    assert doc["configs"][-1]["name"] == "ling3_flash_train"
    assert doc["workloads"][-1]["name"] == CELL
    assert (cell["chips"], cell["traffic"]["seq"], cell["traffic"]["batch"],
            cell["traffic"]["driver"]) == (1, 8192, 2, "train_steps_ling3")
    assert [m["name"] for m in doc["per_layer"][-6:]] == [
        r.__name__.rsplit(".", 1)[1] for r in NEW
    ]
    for m in doc["per_layer"][-6:]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    reported = {m["name"] for m in cell["per_layer"]}
    assert reported >= {
        "device_idle_share", "peak_hbm", "moe_time_share", "moe_route_time_share",
        "moe_load_imbalance", "moe_held_entry_share", "moe_shared_time_share",
        "moe_group_hit_share", "mla_core_time_share", "mla_latent_time_share",
        "embed_grad_time_share",
    }
    # readers with another model's arithmetic in them stay off the cell
    assert not reported & {
        "mla_core_roofline_share", "dsv2_mfu", "dsv2_expert_roofline_share",
        "trinity_mfu", "afmoe_expert_roofline_share", "moe_expert_roofline_share",
        "model_mfu", "flash_roofline_share",
    }
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_tokens_per_s", "setup_s"
    }


def test_the_file_holds_every_number_of_the_catalog_row_but_the_reduced(cell):
    cfg = cell["config"]
    published = {
        "first_k_dense_replace": 2, "head_dim": 128, "hidden_size": 2560,
        "intermediate_size": 6144, "kda_lower_bound": -5, "kv_lora_rank": 512,
        "layer_group_size": 6, "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "n_group": 8,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 6000000,
        "routed_scaling_factor": 2.5, "short_conv_kernel_size": 4,
        "topk_group": 4, "v_head_dim": 128, "rope_scaling": None,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "ling3_flash_train")
    reduced = {"num_hidden_layers", "num_experts", "vocab_size",
               "num_nextn_predict_layers"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert cfg["num_router_experts"] == 512 and cfg["first_expert"] == 0
    # one whole routing group, an eighth of the vocabulary
    assert cfg["num_experts"] * cfg["n_group"] == cfg["num_router_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("assumed", "departures", "deployment", "rehearsal", "memory"):
        assert cfg[key]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        return
    row = next(r for r in rows if r["name"] == "Ling-3.0-flash")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key


# -- flops_ling3.py ----------------------------------------------------------------


def test_a_layers_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    assert flops_ling3.kda_matmul_params(cfg) == (
        6 * 2560 * 4096 + 2560 * 32
    ) == 62_996_480
    q, kv_a, kv_b, o, gate = (2560 * 32 * 192, 2560 * (512 + 64),
                              512 * 32 * 256, 32 * 128 * 2560, 2560 * 32)
    assert (q, kv_a, kv_b, o, gate) == (
        15_728_640, 1_474_560, 4_194_304, 10_485_760, 81_920
    )
    assert flops_ling3.latent_matmul_params(cfg) == 31_965_184
    assert flops_ling3.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    # six KDA mixers and the latent one, the dense FFN, six routers and
    # shared experts, the held slice of the head
    assert flops_ling3.resident_matmul_params(cfg) == (
        6 * 62_996_480 + 31_965_184 + 3 * 2560 * 6144
        + 6 * (2560 * 512 + 5_898_240) + 2560 * 19648
    ) == 550_682_624
    assert flops_ling3.layer_kinds(cfg) == (
        [("kda", "dense")] + [("kda", "moe")] * 5 + [("latent", "moe")]
    )


def test_the_kda_cores_count_by_hand(cell):
    cfg = cell["config"]
    C, d = flops_ling3.KDA_CHUNK, 128
    assert C == 64
    a_chunk = (
        d * C * (C - 1)             # A, strictly below the diagonal
        + d * C * (C + 1)           # P, the diagonal with it
        + 2 * d * C * (C - 1)       # the solve of dk + dv columns
        + 3 * 2 * C * d * d         # W S, K^^T U, (Gamma Q) S
        + d * C * (C + 1)           # P U
    )
    assert a_chunk == 8_904_704
    # forward and twice that backward, 32 heads, 128 chunks a sequence
    assert flops_ling3.kda_core_train_flops(cfg, 8192) == 3.0 * 32 * 128 * a_chunk
    assert flops_ling3.kda_core_train_flops(cfg, 8192) / 1e9 == pytest.approx(
        109.42, abs=0.01
    )
    # a tail of a chunk counts as a chunk
    assert flops_ling3.kda_core_train_flops(cfg, 8193) == 3.0 * 32 * 129 * a_chunk
    # q, k, v in bf16, the log-decay in float32, beta: 1,284 bytes a token
    # a head; read forward, read backward, the gradients written; o written
    # forward and its cotangent read backward
    inputs = 3 * 128 * 2 + 128 * 4 + 4
    assert flops_ling3.kda_core_train_bytes(cfg, 8192) == 8192 * 32 * (
        3 * inputs + 2 * 128 * 2
    ) == 1_143_996_416
    least, bound = flops.roofline_seconds(
        flops_ling3.kda_core_train_flops(cfg, 8192),
        flops_ling3.kda_core_train_bytes(cfg, 8192), PEAKS,
    )
    # 0.56 ms of MXU against 1.40 ms of HBM a sequence a layer
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(1.397, abs=0.001)


def test_core_and_train_flops_by_hand(cell):
    cfg = cell["config"]
    pairs = 8192 * 8193 // 2
    # scores over 192 columns, values over 128, forward 2 products and
    # backward 4: 6 x pairs x 32 heads x 320
    assert flops_ling3.core_train_flops(cfg, 8192) == 6.0 * pairs * 32 * 320
    q, k, v = 8192 * 32 * 192 * 2, 8192 * (32 * 128 + 64) * 2, 8192 * 32 * 128 * 2
    assert flops_ling3.core_train_bytes(cfg, 8192) == 3 * (q + k) + 6 * v
    least, bound = flops.roofline_seconds(
        flops_ling3.core_train_flops(cfg, 8192),
        flops_ling3.core_train_bytes(cfg, 8192), PEAKS,
    )
    assert bound == "compute"
    per_token = flops_ling3.train_flops_per_token(cfg, 8192, 6.0)
    assert per_token == (
        6.0 * 550_682_624 + 6.0 * 5_898_240 * 6.0
        + (6 * flops_ling3.kda_core_train_flops(cfg, 8192)
           + flops_ling3.core_train_flops(cfg, 8192)) / 8192
    )
    # 3.85 GFLOP a token, 63 TFLOP a step of 16,384: 320 ms at the peak
    assert per_token / 1e9 == pytest.approx(3.848, abs=1e-3)
    assert per_token * 16384 / 197e12 * 1e3 == pytest.approx(320.1, abs=0.1)


def test_expert_roofline_terms_by_hand(cell):
    cfg = cell["config"]
    held = 6 * 16384.0          # a balanced step: 16,384 entries a layer
    f = flops_ling3.expert_train_flops(cfg, held)
    assert f == 6.0 * held * 5_898_240
    b = flops_ling3.expert_train_bytes(cfg, held)
    assert b == 9 * (held * (2560 + 768) + 6 * 64 * 2560 * 768) * 2
    least, bound = flops.roofline_seconds(f, b, PEAKS)
    # 256 rows an expert: the weights' bytes, not the MXU (23.8 against
    # 17.7 ms a step)
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(23.8, abs=0.1)
    assert f / 197e12 * 1e3 == pytest.approx(17.66, abs=0.05)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

%fused_computation.9 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %exp.30 = f32[8]{0} exponential(%q), metadata={op_name="jit(step)/jvp(accl.attn::kda)/exp"}
}

%cond.1 (c: f32[8]) -> pred[] {
  %c = f32[8]{0} parameter(0)
  ROOT %compare.40 = pred[] compare(%c, %c), direction=LT, metadata={op_name="jit(step)/jvp(accl.attn::kda)/while/cond/lt"}
}

%body.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.20 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::kda)/while/body/dot_general"}
  ROOT %fusion.21 = f32[8]{0} fusion(%fusion.20), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::kda)/while/body/add"}
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::kda_proj)/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(step)/jvp(accl.attn::kda)/exp"}
  %while.3 = f32[8]{0} while(%a), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/jvp(accl.attn::kda)/while"}
  %fusion.4 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::latent)/dot_general"}
  %flash_fwd.5 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::mla)/flash_fwd/pallas_call"}
  %flash_bwd.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(accl.attn::mla))/flash_bwd/pallas_call"}
  %gmm_fwd.7 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::shared)/dot_general"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
  ROOT %fusion.10 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.attn::kda_proj))/dot_general"}
}
'''


def _ctx(cell, scopes=True, router=True, mixers=True):
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3 as driver

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
            ["flash_fwd.5 custom-call tpu_custom_call f32[8]", 700, 100.0],
            ["flash_bwd.6 custom-call tpu_custom_call f32[8]", 800, 200.0],
            ["gmm_fwd.7 custom-call tpu_custom_call f32[8]", 1000, 100.0],
            ["fusion.8 fusion f32[8]", 1100, 100.0],
            ["fusion.9 fusion f32[8]", 1200, 100.0],
            ["fusion.10 fusion f32[8]", 1400, 400.0],
        ]},
    }
    facts = {
        "tokens_per_s": 20000.0, "tokens_per_step": 16384, "seq": 8192,
        "batch": 2, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
        all_of = driver.scoped_instructions(HLO)
        facts["scope_ops_all"] = {
            s: n for s, n in all_of.items() if s.startswith("accl.attn::kda")
        }
    if router:
        facts["router"] = {
            "held_entries": [16000, 16500, 16384, 16200, 16700, 16520],
            "held_entry_share": 12.5, "load_imbalance": 1.3,
            "group_hit_share": 50.2,
        }
    if mixers:
        facts["mixers"] = {"kda_layers": 6, "mla_layers": 1, "kda_chunk": 64}
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 2000.0)}},
    }


def test_the_step_text_is_read_in_the_loops_bodies_too():
    from perfbench import scope_ops
    from perfbench.drivers import train_steps_ling3 as driver

    found = driver.scoped_instructions(HLO)
    # the entry, then the loop's body and condition; not what a fusion calls
    assert found["accl.attn::kda"] == [
        "fusion.2", "while.3", "fusion.20", "fusion.21", "compare.40"
    ]
    assert found["accl.attn::kda_proj"] == ["fusion.1", "fusion.10"]
    # the entry computation alone has not the body's two
    assert scope_ops.scopes_of(HLO)["accl.attn::kda"] == ["fusion.2", "while.3"]


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 1700.0               # idle from 1300 to 1400 and from 1800 on
    # the loop's event and its body's are one stretch: 50 + 300, not 630
    assert kda_core_time_share.read(ctx) == pytest.approx(100 * 350 / busy)
    assert kda_proj_time_share.read(ctx) == pytest.approx(100 * 600 / busy)
    least = 2 * 6 * flops_ling3.kda_core_train_bytes(cfg, 8192) / 819e9
    assert kda_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 350
    )
    # ONE latent layer, where ``mla_core_roofline_share`` would count seven
    least = 2 * flops_ling3.core_train_flops(cfg, 8192) / 197e12
    assert ling3_mla_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 300
    )
    assert mla_core_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    assert mla_latent_time_share.read(ctx) == pytest.approx(100 * 150 / busy)
    assert moe_shared_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    assert moe_route_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    assert moe_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    held = 98304.0
    assert ling3_expert_roofline_share.read(ctx) == pytest.approx(
        100 * flops_ling3.expert_train_bytes(cfg, held) / 819e9 * 1e9 / 100
    )
    assert ling3_mfu.read(ctx) == pytest.approx(
        100 * flops_ling3.train_flops_per_token(cfg, 8192, 6.0) * 20000 / 197e12
    )
    assert moe_held_entry_share.read(ctx) == 12.5
    assert moe_group_hit_share.read(ctx) == 50.2
    assert moe_load_imbalance.read(ctx) == 1.3
    assert embed_grad_time_share.read(ctx) is None      # no such scope here


def test_a_program_without_the_scopes_or_the_counters_reads_as_nothing(cell):
    """The parent's side of a traced run (it fails before a trace: the
    readers must not raise on any other program's facts either), and a
    rehearsal."""
    ctx = _ctx(cell, scopes=False, router=False, mixers=False)
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__
    # another cell's facts: scopes and a router, no ``mixers``
    ctx = _ctx(cell, mixers=False)
    ctx["facts"].pop("scope_ops_all")
    for reader in NEW:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["slices"] = {}
    for reader in NEW[1:]:
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops_all"].pop("accl.attn::kda")
    assert kda_core_time_share.read(ctx) is None
    assert kda_core_roofline_share.read(ctx) is None
    assert kda_proj_time_share.read(ctx) is not None
    # every layer's reader stays off this cell: it would count seven cores
    assert mla_core_roofline_share.read(_ctx(cell)) is not None
    assert "mla_core_roofline_share" not in {
        m["name"] for m in cell["per_layer"]
    }


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_program_and_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_ling3 as driver

    cfg = cell["config"]
    assert driver.reference_model(cfg) == dict(
        n_head=32, kda_lower_bound=-5.0, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
        rope_theta=6000000.0, top_k=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, first_expert=0,
    )
    program = driver.program_config(cfg)
    assert (program.n_experts, program.router_experts(),
            program.moe_first_expert) == (64, 512, 0)
    assert program.latent.q_rank is None and program.attn_gate == "head"
    assert program.attn_scale() == pytest.approx(192 ** -0.5)
    assert program.moe_router == "sigmoid" and program.moe_bias_rate == 0.001
    assert program.norm_eps == 1e-6 and program.remat
    assert [program.mixer(k) for k in program.layers] == ["kda"] * 6 + ["latent"]
    assert [k.ffn for k in program.layers] == ["dense"] + ["moe"] * 6
    assert driver.layer_kinds(cfg) == flops_ling3.layer_kinds(cfg)
    assert len(driver.BALANCE_RATES) > 0     # a fixed number of rounds
    with pytest.raises(ValueError, match="layers_kept"):
        driver.layer_kinds(dict(cfg, num_hidden_layers=8))
    clamped = dict(cfg, layers_kept=[1, 6, 7, 8, 9, 10, 35])
    with pytest.raises(ValueError, match="SwiGLU clamp"):
        driver.program_config(clamped)
    with pytest.raises(ValueError, match="KDA variant"):
        driver.program_config(dict(cfg, q_lora_rank=1536))
