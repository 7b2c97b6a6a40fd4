"""What PR 34 adds to the benchmark, checked by hand on the CPU: the
configuration file against the catalog's row, ``flops_deepseek_v2.py``
against hand arithmetic, the six new readers on a compiled module's text
and a trace written by hand, what they read from a program without the
scopes and counters (the parent's side of a traced run), and the driver's
own pieces (``test_rehearsal.py`` runs the cell's rehearsal with every
other cell's)."""

import json

import pytest

from perfbench import flops_deepseek_v2 as flops_dsv2
from perfbench import manifest, scope_ops
from perfbench.layer_metrics import (
    dsv2_expert_roofline_share,
    dsv2_mfu,
    mla_core_roofline_share,
    mla_core_time_share,
    mla_latent_time_share,
    moe_group_hit_share,
    moe_held_entry_share,
    moe_shared_time_share,
    moe_time_share,
)

CELL = "train_dsv2_t4096_b1"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the configuration file ----------------------------------------------------


def test_the_file_holds_every_number_of_the_catalog_row_but_the_reduced(cell):
    cfg = cell["config"]
    published = {
        "first_k_dense_replace": 1, "hidden_size": 5120,
        "intermediate_size": 12288, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 2,
        "num_attention_heads": 128, "num_experts_per_tok": 6,
        "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 16, "topk_group": 3, "v_head_dim": 128,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
            "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096, "type": "yarn",
        },
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "deepseek_v2_train")
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 20, 12800)
    assert cfg["published"] == {
        "num_hidden_layers": 60, "n_routed_experts": 160, "vocab_size": 102400,
    }
    assert cfg["num_router_experts"] == 160 and cfg["first_expert"] == 0
    # one whole routing group, an eighth of the vocabulary
    assert cfg["n_routed_experts"] * cfg["n_group"] == cfg["num_router_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("assumed", "departures", "deployment", "rehearsal", "memory"):
        assert cfg[key]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        return
    row = next(r for r in rows if r["name"] == "DeepSeek-V2")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key


# -- flops_deepseek_v2.py --------------------------------------------------------


def test_a_layers_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    q_a, q_b = 5120 * 1536, 1536 * 128 * 192
    kv_a, kv_b, o = 5120 * (512 + 64), 512 * 128 * 256, 128 * 128 * 5120
    assert (q_a, q_b, kv_a, kv_b, o) == (
        7_864_320, 37_748_736, 2_949_120, 16_777_216, 83_886_080
    )
    assert flops_dsv2.latent_matmul_params(cfg) == 149_225_472
    assert flops_dsv2.expert_params(cfg) == 3 * 5120 * 1536 == 23_592_960
    # five mixers, the dense FFN, four routers and shared pairs, the head
    assert flops_dsv2.resident_matmul_params(cfg) == (
        5 * 149_225_472 + 3 * 5120 * 12288
        + 4 * (5120 * 160 + 2 * 23_592_960) + 5120 * 12800
    ) == 1_192_427_520


def test_core_and_train_flops_by_hand(cell):
    cfg = cell["config"]
    pairs = 4096 * 4097 // 2
    # scores over 192 columns, values over 128, forward 2 products and
    # backward 4: 6 x pairs x 128 heads x 320
    assert flops_dsv2.core_train_flops(cfg, 4096) == 6.0 * pairs * 128 * 320
    # a token: 0.5035 GFLOP of core a layer; 7.155 resident; 0.1416 an entry
    assert flops_dsv2.core_train_flops(cfg, 4096) / 4096 / 1e9 == pytest.approx(
        0.50344, abs=1e-4
    )
    per_token = flops_dsv2.train_flops_per_token(cfg, 4096, 3.0)
    assert per_token == (
        6.0 * 1_192_427_520 + 6.0 * 23_592_960 * 3.0
        + 5 * flops_dsv2.core_train_flops(cfg, 4096) / 4096
    )
    assert per_token / 1e9 == pytest.approx(10.096, abs=1e-3)
    # the core is compute-bound: 2.06 TFLOP against 1.1 GB a layer
    from perfbench import flops

    least, bound = flops.roofline_seconds(
        flops_dsv2.core_train_flops(cfg, 4096),
        flops_dsv2.core_train_bytes(cfg, 4096), PEAKS,
    )
    assert bound == "compute"
    q, k, v = 4096 * 128 * 192 * 2, 4096 * (128 * 128 + 64) * 2, 4096 * 128 * 128 * 2
    assert flops_dsv2.core_train_bytes(cfg, 4096) == 3 * (q + k) + 6 * v


def test_expert_roofline_terms_by_hand(cell):
    from perfbench import flops

    cfg = cell["config"]
    held = 4 * 3072.0           # a balanced step: 3,072 entries a layer
    f = flops_dsv2.expert_train_flops(cfg, held)
    assert f == 6.0 * held * 23_592_960
    b = flops_dsv2.expert_train_bytes(cfg, held, 4)
    assert b == 9 * (held * (5120 + 1536) + 4 * 20 * 5120 * 1536) * 2
    least, bound = flops.roofline_seconds(f, b, PEAKS)
    # 154 rows an expert: the weights' bytes, not the MXU (15.6 against
    # 8.8 ms a step)
    assert bound == "memory"
    assert least * 1e3 == pytest.approx(15.6, abs=0.1)
    assert f / 197e12 * 1e3 == pytest.approx(8.83, abs=0.05)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::latent)/dot_general"}
  %flash_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::mla)/flash_fwd/pallas_call"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::mla)/pad"}
  %flash_bwd.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(accl.attn::mla))/flash_bwd/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.attn::latent))/dot_general"}
  %gmm_fwd.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::shared)/dot_general"}
  ROOT %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
}
'''


def _ctx(cell, scopes=True, router=True):
    reduced = {
        "host": [["bench::step", 0.0, 1000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["fusion.1 fusion f32[8]", 0, 150.0],
            ["flash_fwd.2 custom-call tpu_custom_call f32[8]", 150, 60.0],
            ["fusion.3 fusion f32[8]", 210, 10.0],     # in the scope, no kernel
            ["flash_bwd.4 custom-call tpu_custom_call f32[8]", 220, 140.0],
            ["fusion.5 fusion f32[8]", 360, 250.0],
            ["gmm_fwd.6 custom-call tpu_custom_call f32[8]", 610, 100.0],
            ["fusion.7 fusion f32[8]", 710, 90.0],
            ["fusion.8 fusion f32[8]", 800, 100.0],
        ]},
    }
    facts = {
        "tokens_per_s": 10000.0, "tokens_per_step": 4096, "seq": 4096,
        "batch": 1, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
    if router:
        facts["router"] = {
            "held_entries": [3000, 3100, 3072, 3116],
            "held_entry_share": 12.5, "load_imbalance": 1.4,
            "group_hit_share": 37.4,
        }
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 1000.0)}},
    }


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 900.0
    # the kernels under accl.attn::mla, not the pad beside them
    assert mla_core_time_share.read(ctx) == pytest.approx(100 * 200 / busy)
    least = 5 * flops_dsv2.core_train_flops(cfg, 4096) / 197e12
    assert mla_core_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 200
    )
    assert mla_latent_time_share.read(ctx) == pytest.approx(100 * 400 / busy)
    assert moe_shared_time_share.read(ctx) == pytest.approx(100 * 90 / busy)
    assert moe_time_share.read(ctx) == pytest.approx(100 * 290 / busy)
    held = 12288.0
    assert dsv2_expert_roofline_share.read(ctx) == pytest.approx(
        100 * flops_dsv2.expert_train_bytes(cfg, held, 4) / 819e9 * 1e9 / 100
    )
    assert dsv2_mfu.read(ctx) == pytest.approx(
        100 * flops_dsv2.train_flops_per_token(cfg, 4096, 3.0) * 10000 / 197e12
    )
    assert moe_held_entry_share.read(ctx) == 12.5
    assert moe_group_hit_share.read(ctx) == 37.4


def test_a_program_without_the_scopes_or_the_counters_reads_as_nothing(cell):
    """The parent's side of a traced run, and a rehearsal."""
    ctx = _ctx(cell, scopes=False, router=False)
    for reader in (mla_core_time_share, mla_core_roofline_share,
                   mla_latent_time_share, dsv2_expert_roofline_share,
                   dsv2_mfu, moe_group_hit_share):
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops"].pop("accl.attn::mla")
    assert mla_core_time_share.read(ctx) is None
    assert mla_latent_time_share.read(ctx) is not None
    ctx = _ctx(cell)
    ctx["slices"] = {}
    for reader in (mla_core_roofline_share, mla_latent_time_share,
                   dsv2_expert_roofline_share):
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["peaks"] = None                      # a rehearsal prints no share
    assert moe_group_hit_share.read(ctx) is None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_program_and_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_deepseek_v2 as driver

    cfg = cell["config"]
    model = driver.reference_model(cfg)
    assert model == dict(
        n_head=128, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, rope_theta=10000.0, rope_scaling=cfg["rope_scaling"],
        top_k=6, n_group=8, topk_group=3, routed_scaling_factor=16.0,
        first_expert=0,
    )
    program = driver.program_config(cfg)
    assert (program.n_experts, program.router_experts(),
            program.moe_first_expert) == (20, 160, 0)
    assert program.head_size() == 192 and program.latent.v_dim == 128
    assert program.attn_scale() == pytest.approx(0.11472, abs=2e-5)
    assert program.moe_balance_weights == (0.003, 0.05, 0.02)
    assert program.norm_eps == 1e-6 and not program.remat
    assert [k.ffn for k in program.layers] == ["dense"] + ["moe"] * 4
    assert len(driver.BALANCE_RATES) > 0     # a fixed number of rounds
