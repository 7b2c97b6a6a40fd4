"""What PR 31 adds to the benchmark, checked by hand on the CPU:
``flops_afmoe.py`` against hand arithmetic, the new readers on a compiled
module's text and a trace written by hand, what they read from a program
without the scopes and counters (the parent's side of a traced run), and
the driver's own pieces (``test_rehearsal.py`` runs the cell's rehearsal
with every other cell's)."""

import pytest

from perfbench import flops_afmoe, manifest, scope_ops
from perfbench.layer_metrics import (
    afmoe_expert_roofline_share,
    attn_window_roofline_share,
    attn_window_time_share,
    moe_held_entry_share,
    moe_load_imbalance,
    moe_shared_time_share,
    moe_time_share,
    trinity_mfu,
)

CELL = "train_trinity_t8192_b2"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the configuration file ----------------------------------------------------


def test_the_file_holds_every_number_of_the_catalog_row_but_the_reduced(cell):
    cfg = cell["config"]
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
        "intermediate_size": 6144, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "moe_intermediate_size": 1024,
        "n_group": 1, "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.826,
        "sliding_window": 2048, "topk_group": 1,
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "trinity_mini_train")
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size",
    }
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 16, 25024)
    assert cfg["published"]["num_experts"] == cfg["num_router_experts"] == 128
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # a dense layer, then one whole period of the published 3:1 pattern
    assert cfg["layer_types"][1:] == ["sliding_attention"] * 3 + ["full_attention"]
    for key in ("assumed", "departures", "deployment", "rehearsal", "memory"):
        assert cfg[key]


# -- flops_afmoe.py ------------------------------------------------------------


def test_a_layers_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    d, q, kv = 2048, 32 * 128, 4 * 128
    attention = 3 * d * q + 2 * d * kv          # q, gate, o; k, v
    assert attention == 27_262_976
    assert flops_afmoe.attention_matmul_params(cfg) == attention
    expert = 3 * d * 1024
    assert flops_afmoe.expert_params(cfg) == expert == 6_291_456
    dense = 3 * d * 6144
    sparse = d * 128 + expert                    # the router, the shared expert
    head = d * 25024
    assert flops_afmoe.resident_matmul_params(cfg) == (
        5 * attention + dense + 4 * sparse + head
    ) == 251_527_168


def test_attended_pairs_and_attention_flops_by_hand(cell):
    cfg = cell["config"]
    T, W = 8192, 2048
    assert flops_afmoe.attended_pairs(T) == T * (T + 1) // 2 == 33_558_528
    # every query has W keys but the first W - 1, which have 1 .. W - 1
    assert flops_afmoe.attended_pairs(T, W) == sum(
        min(i + 1, W) for i in range(T)
    ) == T * W - W * (W - 1) // 2 == 14_681_088
    assert flops_afmoe.attended_pairs(T, T) == flops_afmoe.attended_pairs(T)
    # two products forward, four backward, 2 FLOP a multiply-add, 32 x 128 wide
    assert flops_afmoe.attention_train_flops(cfg, T, W) == (
        6 * 2 * 14_681_088 * 4096
    )
    assert flops_afmoe.layer_windows(cfg) == [2048] * 4 + [None]
    # compute-bound on a v5e: 3.7 ms against 0.6 ms of bytes
    assert (flops_afmoe.attention_train_flops(cfg, T, W) / 197e12
            > 5 * flops_afmoe.attention_train_bytes(cfg, T) / 819e9)


def test_train_flops_a_token_by_hand(cell):
    cfg = cell["config"]
    T = 8192
    attention = (
        4 * flops_afmoe.attention_train_flops(cfg, T, 2048)
        + flops_afmoe.attention_train_flops(cfg, T)
    ) / T
    # one held entry a layer a token: the balanced share, 8 x 16 / 128
    want = 6 * 251_527_168 + 6 * 6_291_456 * 4.0 + attention
    assert flops_afmoe.train_flops_per_token(cfg, T, 4.0) == want
    # about 36 TFLOP a step of 16,384 tokens, as the issue reckoned
    assert 33e12 < 16384 * want < 38e12
    # were all eight experts counted here it would be a fifth more
    assert flops_afmoe.train_flops_per_token(cfg, T, 32.0) > 1.3 * want


def test_expert_roofline_terms_by_hand(cell):
    cfg = cell["config"]
    entries = 4 * 16384                      # four layers' balanced share
    assert flops_afmoe.expert_train_flops(cfg, entries) == (
        3 * 2 * entries * 3 * 2048 * 1024
    )
    one = entries * (2048 + 1024) + 4 * 16 * 2048 * 1024
    assert flops_afmoe.expert_train_bytes(cfg, entries, 4) == 9 * one * 2
    assert (flops_afmoe.expert_train_flops(cfg, entries) / 197e12
            > flops_afmoe.expert_train_bytes(cfg, entries, 4) / 819e9)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %flash_fwd.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::window)/flash_fwd/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::window)/pad"}
  %flash_bwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(accl.attn::window))/flash_bwd/pallas_call"}
  %flash_fwd.3 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::core)/flash_fwd/pallas_call"}
  %gmm_fwd.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::shared)/dot_general"}
  ROOT %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
}
'''


def _ctx(cell, scopes=True, router=True):
    reduced = {
        "host": [["bench::step", 0.0, 1000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["flash_fwd.1 custom-call tpu_custom_call f32[8]", 0, 100.0],
            ["fusion.7 fusion f32[8]", 100, 10.0],     # in the scope, no kernel
            ["flash_bwd.2 custom-call tpu_custom_call f32[8]", 110, 200.0],
            ["flash_fwd.3 custom-call tpu_custom_call f32[8]", 310, 190.0],
            ["gmm_fwd.4 custom-call tpu_custom_call f32[8]", 500, 250.0],
            ["fusion.5 fusion f32[8]", 750, 50.0],
            ["fusion.6 fusion f32[8]", 800, 100.0],
        ]},
    }
    facts = {
        "tokens_per_s": 40000.0, "tokens_per_step": 16384, "seq": 8192,
        "batch": 2, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
    if router:
        facts["router"] = {
            "held_entries": [16000, 17000, 16500, 16036],
            "held_entry_share": 12.5, "load_imbalance": 1.8,
        }
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 1000.0)}},
    }


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 900.0
    # the kernels under accl.attn::window, not the pad beside them, not core's
    assert attn_window_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    least = 1 * 2 * 4 * flops_afmoe.attention_train_flops(cfg, 8192, 2048) / 197e12
    assert attn_window_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 300
    )
    assert moe_shared_time_share.read(ctx) == pytest.approx(100 * 50 / busy)
    assert moe_time_share.read(ctx) == pytest.approx(100 * 400 / busy)
    held = 65536.0
    assert afmoe_expert_roofline_share.read(ctx) == pytest.approx(
        100 * flops_afmoe.expert_train_flops(cfg, held) / 197e12 * 1e9 / 250
    )
    assert trinity_mfu.read(ctx) == pytest.approx(
        100 * flops_afmoe.train_flops_per_token(cfg, 8192, 4.0) * 40000 / 197e12
    )
    assert moe_held_entry_share.read(ctx) == 12.5
    assert moe_load_imbalance.read(ctx) == 1.8


def test_a_program_without_the_scopes_or_the_counters_reads_as_nothing(cell):
    """The parent's side of a traced run, and a rehearsal."""
    ctx = _ctx(cell, scopes=False, router=False)
    for reader in (attn_window_time_share, attn_window_roofline_share,
                   afmoe_expert_roofline_share, moe_shared_time_share,
                   trinity_mfu, moe_held_entry_share):
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops"].pop("accl.attn::window")
    assert attn_window_time_share.read(ctx) is None
    ctx = _ctx(cell)
    ctx["slices"] = {}
    assert attn_window_roofline_share.read(ctx) is None
    assert afmoe_expert_roofline_share.read(ctx) is None
    ctx = _ctx(cell)
    ctx["peaks"] = None                      # a rehearsal prints no share
    assert moe_held_entry_share.read(ctx) is None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_the_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_trinity as driver

    model = driver.reference_model(cell["config"])
    assert model == dict(
        n_head=32, n_kv_head=4,
        layer_types=("sliding_attention",) * 4 + ("full_attention",),
        sliding_window=2048, top_k=8, route_norm=True, route_scale=2.826,
        first_expert=0,
    )
    import numpy as np

    counts = np.arange(2 * 128).reshape(2, 128)
    assert driver.held_entries(counts, 16, 16).tolist() == [
        sum(range(16, 32)), sum(range(128 + 16, 128 + 32)),
    ]
