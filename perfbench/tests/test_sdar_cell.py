"""What PR 38 adds to the benchmark, checked by hand on the CPU: the
configuration file against the catalog's row, ``flops_sdar.py`` against
hand arithmetic, the seven new readers on a compiled module's text and a
trace written by hand, what they read from a program without the scopes
and counters (the parent's side of a traced run), and the driver's own
pieces (``test_rehearsal.py`` runs the cell's rehearsal with every other
cell's)."""

import json

import pytest

from perfbench import flops, flops_sdar, manifest, scope_ops
from perfbench.layer_metrics import (
    attn_blockdiff_roofline_share,
    attn_blockdiff_tile_pairs,
    attn_blockdiff_time_share,
    diffusion_loss_time_share,
    diffusion_masked_share,
    embed_grad_time_share,
    moe_held_entry_share,
    moe_route_time_share,
    moe_time_share,
    sdar_expert_roofline_share,
    sdar_mfu,
)

CELL = "train_sdar_t4096_b2"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = (
    "sdar_mfu", "attn_blockdiff_time_share", "attn_blockdiff_roofline_share",
    "attn_blockdiff_tile_pairs", "sdar_expert_roofline_share",
    "diffusion_loss_time_share", "diffusion_masked_share",
)
EXTENDED = (
    "device_idle_share", "moe_time_share", "moe_route_time_share",
    "moe_load_imbalance", "moe_held_entry_share", "embed_grad_time_share",
)


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- the manifest and the configuration file -----------------------------------


def test_the_manifest_holds_seven_cells_one_on_four_chips_and_the_new_metrics(cell):
    doc = manifest.load()
    assert len(doc["workloads"]) == 7
    assert [w["name"] for w in doc["workloads"] if w["chips"] == 4] == [
        "coll_w4_sweep"
    ]
    assert doc["workloads"][-1]["name"] == CELL and cell["chips"] == 1
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-7:] == list(NEW)
    assert set(EXTENDED) <= set(names)
    for m in doc["per_layer"][-7:]:
        assert (m["workloads"], m["moves"]) == ([CELL], "train_tokens_per_s")
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"
    ]


def test_the_file_holds_every_number_of_the_catalog_row_but_the_reduced(cell):
    cfg = cell["config"]
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1000000, "decoder_sparse_step": 1,
        "rope_scaling": None, "sliding_window": None, "mlp_only_layers": [],
        "norm_topk_prob": True, "tie_word_embeddings": False,
        "model_type": "sdar_moe",
    }
    for key, value in published.items():
        assert cfg[key] == value, key
    doc = manifest.load()
    entry = next(c for c in doc["configs"] if c["name"] == "sdar_30b_a3b_train")
    reduced = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert set(entry["reduced"]) == set(cfg["reduced"]) == reduced
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (6, 16, 18992)
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936,
    }
    assert cfg["num_router_experts"] == 128 and cfg["first_expert"] == 0
    # an eighth of the experts and of the vocabulary; the mask id a row of it
    assert cfg["num_experts"] * 8 == cfg["num_router_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["mask_token_row"] == cfg["vocab_size"] - 1
    assert cfg["assumed"]["block_length"] == 4
    for key in ("assumed", "departures", "deployment", "rehearsal", "memory"):
        assert cfg[key]
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        return
    row = next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")
    assert entry["source"] == cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in reduced:
            assert cfg[key] == value, key


# -- flops_sdar.py --------------------------------------------------------------


def test_a_layers_matmul_parameters_by_hand(cell):
    cfg = cell["config"]
    assert flops_sdar.attention_matmul_params(cfg) == (
        2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    ) == 18_874_368
    assert flops_sdar.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert flops_sdar.row_matmul_params(cfg) == 6 * (18_874_368 + 2048 * 128)
    assert flops_sdar.head_params(cfg) == 2048 * 18992 == 38_895_616


def test_live_pairs_and_the_steps_flops_by_hand(cell):
    cfg = cell["config"]
    # noisy on noisy L B; noisy on clean B^2 n(n-1)/2; clean on clean
    # B^2 n(n+1)/2 over n = 1,024 blocks
    assert flops_sdar.live_pairs(4096, 4) == (
        16_384 + 16 * 523_776 + 16 * 524_800
    ) == 16_793_600
    # by brute force at a small size
    pairs = sum(
        (q < 16 and k < 16 and q // 4 == k // 4)
        or (q < 16 <= k and (k - 16) // 4 < q // 4)
        or (q >= 16 and k >= 16 and (k - 16) // 4 <= (q - 16) // 4)
        for q in range(32) for k in range(32)
    )
    assert flops_sdar.live_pairs(16, 4) == pairs
    # six products of 2 FLOP over 32 heads of 128: 0.8255 TFLOP a sequence
    core = flops_sdar.core_train_flops(cfg, 4096)
    assert core == 6.0 * 16_793_600 * 32 * 256
    assert core / 1e12 == pytest.approx(0.8255, abs=1e-4)
    held = 6 * 16384.0
    step = flops_sdar.train_flops_per_step(cfg, 4096, 2, held)
    assert step == (
        6.0 * 6 * 19_136_512 * 16384 + 6.0 * 38_895_616 * 8192
        + 6.0 * 4_718_592 * held + 6 * 2 * core
    )
    assert step / 1e12 == pytest.approx(25.89, abs=0.01)
    least, bound = flops.roofline_seconds(
        core, flops_sdar.core_train_bytes(cfg, 4096), PEAKS
    )
    assert bound == "compute"
    q, k = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2
    assert flops_sdar.core_train_bytes(cfg, 4096) == 7 * q + 6 * k


def test_expert_roofline_terms_by_hand(cell):
    cfg = cell["config"]
    held = 6 * 16384.0          # a balanced step: 16,384 entries a layer
    f = flops_sdar.expert_train_flops(cfg, held)
    assert f == 6.0 * held * 4_718_592
    b = flops_sdar.expert_train_bytes(cfg, held)
    assert b == 9 * (held * (2048 + 768) + 6 * 16 * 2048 * 768) * 2
    least, bound = flops.roofline_seconds(f, b, PEAKS)
    # 1,024 rows an expert: compute, 14.1 ms a step against 9.4 of bytes
    assert bound == "compute"
    assert least * 1e3 == pytest.approx(14.13, abs=0.05)
    assert b / 819e9 * 1e3 == pytest.approx(9.40, abs=0.05)


# -- the readers, on a step's text and a trace written by hand ----------------

HLO = '''HloModule jit_step

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/accl.diffusion::noise/threefry2x32"}
  %flash_fwd.2 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::blockdiff)/flash_fwd/pallas_call"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::blockdiff)/pad"}
  %flash_bwd.4 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/transpose(jvp(accl.attn::blockdiff))/flash_bwd/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.loss::diffusion)/dot_general"}
  %gmm_fwd.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.moe::experts)/jit(_gmm)/gmm_fwd/pallas_call"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.loss::diffusion))/dot_general"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/jvp(accl.moe::route)/top_k"}
  ROOT %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.embed::grad))/scatter-add"}
}
'''


def _ctx(cell, scopes=True, counters=True):
    reduced = {
        "host": [["bench::step", 0.0, 1000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["fusion.1 fusion f32[8]", 0, 10.0],
            ["flash_fwd.2 custom-call tpu_custom_call f32[8]", 10, 100.0],
            ["fusion.3 fusion f32[8]", 110, 10.0],     # in the scope, no kernel
            ["flash_bwd.4 custom-call tpu_custom_call f32[8]", 120, 200.0],
            ["fusion.5 fusion f32[8]", 320, 60.0],
            ["gmm_fwd.6 custom-call tpu_custom_call f32[8]", 380, 100.0],
            ["fusion.7 fusion f32[8]", 480, 120.0],
            ["fusion.8 fusion f32[8]", 600, 100.0],
            ["fusion.9 fusion f32[8]", 700, 200.0],
        ]},
    }
    facts = {
        "tokens_per_s": 20000.0, "tokens_per_step": 8192, "seq": 4096,
        "batch": 2, "traced_steps": 1,
    }
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
    if counters:
        facts["router"] = {
            "held_entries": [16000, 16400, 16384, 16752, 16384, 16384],
            "held_entry_share": 12.5, "load_imbalance": 1.4,
        }
        facts["diffusion"] = {
            "masked_tokens": 4000, "noisy_positions": 8192, "block": 4,
        }
        facts["attention_tiles"] = {
            "interior": 56, "block": 8, "strict": 8, "lower": 8, "padded": 0,
        }
    return {
        "cell": cell, "peaks": PEAKS, "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 1000.0)}},
    }


def test_the_readers_on_a_hand_written_trace(cell):
    ctx, cfg = _ctx(cell), cell["config"]
    busy = 900.0
    # the kernels under accl.attn::blockdiff, not the pad beside them
    assert attn_blockdiff_time_share.read(ctx) == pytest.approx(100 * 300 / busy)
    least = 2 * 6 * flops_sdar.core_train_flops(cfg, 4096) / 197e12
    assert attn_blockdiff_roofline_share.read(ctx) == pytest.approx(
        100 * least * 1e9 / 300
    )
    assert attn_blockdiff_tile_pairs.read(ctx) == 80.0
    assert diffusion_loss_time_share.read(ctx) == pytest.approx(
        100 * (10 + 60 + 120) / busy
    )
    assert diffusion_masked_share.read(ctx) == pytest.approx(100 * 4000 / 8192)
    held = 98304.0
    assert sdar_expert_roofline_share.read(ctx) == pytest.approx(
        100 * flops_sdar.expert_train_flops(cfg, held) / 197e12 * 1e9 / 100
    )
    assert sdar_mfu.read(ctx) == pytest.approx(
        100 * flops_sdar.train_flops_per_step(cfg, 4096, 2, held) / 8192
        * 20000 / 197e12
    )
    # and the accepted readers the cell's lists gained
    assert moe_time_share.read(ctx) == pytest.approx(100 * 200 / busy)
    assert moe_route_time_share.read(ctx) == pytest.approx(100 * 100 / busy)
    assert moe_held_entry_share.read(ctx) == 12.5
    assert embed_grad_time_share.read(ctx) == pytest.approx(100 * 200 / busy)


def test_a_program_without_the_scopes_or_the_counters_reads_as_nothing(cell):
    """The parent's side of a traced run, and a rehearsal."""
    ctx = _ctx(cell, scopes=False, counters=False)
    for name in NEW:
        reader = globals()[name]
        assert reader.read(ctx) is None, name
    ctx = _ctx(cell)
    ctx["facts"]["scope_ops"].pop("accl.attn::blockdiff")
    assert attn_blockdiff_time_share.read(ctx) is None
    assert attn_blockdiff_roofline_share.read(ctx) is None
    assert diffusion_loss_time_share.read(ctx) is not None
    ctx = _ctx(cell)
    ctx["slices"] = {}
    for reader in (attn_blockdiff_roofline_share, diffusion_loss_time_share,
                   sdar_expert_roofline_share):
        assert reader.read(ctx) is None, reader.__name__
    ctx = _ctx(cell)
    ctx["peaks"] = None                      # a rehearsal prints neither count
    assert attn_blockdiff_tile_pairs.read(ctx) is None
    assert diffusion_masked_share.read(ctx) is None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_builds_program_and_reference_from_the_same_keys(cell):
    from perfbench.drivers import train_steps_sdar as driver

    cfg = cell["config"]
    assert driver.reference_model(cfg) == dict(
        n_head=32, n_kv_head=4, block=4, top_k=8, norm_topk_prob=True,
        first_expert=0,
    )
    program = driver.program_config(cfg)
    assert (program.n_experts, program.router_experts(),
            program.moe_first_expert) == (16, 128, 0)
    assert program.diffusion.mask_id == cfg["mask_token_row"] == 18991
    assert cell["traffic"]["driver"] == "train_steps_sdar"
    assert (cell["traffic"]["seq"], cell["traffic"]["batch"]) == (4096, 2)
    # the reference stands alone
    import ast
    import inspect

    from perfbench.reference import sdar_moe

    imported = {
        (node.module if isinstance(node, ast.ImportFrom) else alias.name)
        for node in ast.walk(ast.parse(inspect.getsource(sdar_moe)))
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    }
    assert not any("accl_tpu" in (name or "") for name in imported), imported


def test_the_keyed_step_hands_a_fresh_key_a_step_and_keeps_the_counters():
    from perfbench.drivers.train_steps_sdar import _KeyedStep

    seen = []

    class Compiled:
        def __call__(self, params, tokens, key):
            seen.append(key)
            return params + 1, 0.5, {"masked_tokens": 10 * key}

        def as_text(self):
            return "text"

    step = _KeyedStep(Compiled(), [1, 2, 3])
    params = 0
    for _ in range(3):
        params, loss = step(params, "tokens", None)
    assert (params, loss, seen) == (3, 0.5, [1, 2, 3])
    assert (step.calls, step.masked, step.as_text()) == (3, [10, 20, 30], "text")
    with pytest.raises(IndexError):
        step(params, "tokens", None)
