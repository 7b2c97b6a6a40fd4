"""What PR 26 adds to the benchmark, checked by hand on the CPU:
``flops_olmoe.py`` against hand arithmetic, ``scope_ops.py`` and the MoE
readers on a compiled module's text written by hand and on a trace cut
from a v5e run of the cell, the driver's own pieces, and the cell's
rehearsal (``test_rehearsal.py`` runs it with every other cell's)."""

import json
import os

import pytest

from perfbench import flops_olmoe, manifest, scope_ops, trace_reduce
from perfbench.layer_metrics import (
    _moe,
    moe_expert_roofline_share,
    moe_load_imbalance,
    moe_route_time_share,
    moe_time_share,
    olmoe_mfu,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "train_olmoe_t4096_b2"


@pytest.fixture(scope="module")
def cell():
    return manifest.cell(manifest.load(), CELL)


# -- flops_olmoe.py ----------------------------------------------------------


def test_a_layers_parameters_by_hand(cell):
    d, f, e = 2048, 1024, 64
    attention = 4 * d * d                       # q, k, v, o: 16 heads of 128
    assert attention == 16_777_216
    router = d * e
    experts = e * 3 * d * f
    assert experts == 402_653_184
    norms = 2 * d + 2 * d                       # two block norms, q_norm, k_norm
    assert attention + router + experts + norms == 419_569_664
    assert flops_olmoe.layer_params(cell["config"]) == 419_569_664


def test_forward_flops_a_token_by_hand(cell):
    d, f = 2048, 1024
    projections = 2 * 4 * d * d                 # 33.55 M
    router = 2 * d * 64                         # 0.26 M
    experts = 2 * 8 * 3 * d * f                 # 100.66 M: 8 of the 64
    attention = 2 * 4096 * d                    # QK^T + PV, halved: 16.78 M
    assert projections + router + experts + attention == 151_257_088
    assert flops_olmoe.forward_flops_per_token(cell["config"], 4096) == 151_257_088


def test_train_flops_a_token_by_hand(cell):
    cfg = cell["config"]
    layers = cfg["num_hidden_layers"]
    active = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024   # 67.24 M
    head = 2048 * 50304
    assert flops_olmoe.active_matmul_params(cfg) == layers * active + head
    want = 6 * (layers * active + head) + layers * 3 * 2 * 4096 * 2048
    assert flops_olmoe.train_flops_per_token(cfg, 4096) == want
    # at the published depth the head is a thirteenth of it
    full = dict(cfg, num_hidden_layers=16)
    share = 6 * head / flops_olmoe.train_flops_per_token(full, 4096)
    assert 0.078 < share < 0.079


def test_expert_roofline_terms_by_hand(cell):
    cfg = cell["config"]
    tokens = 8192
    # forward 2 FLOP a MAC, x3 with the two backward products
    assert flops_olmoe.expert_train_flops(cfg, tokens) == (
        3 * 2 * tokens * 8 * 3 * 2048 * 1024
    ) == 2_473_901_162_496
    rows = tokens * 8
    one = rows * (2048 + 1024) + 64 * 2048 * 1024
    assert flops_olmoe.expert_train_bytes(cfg, tokens) == 9 * one * 2
    # compute-bound on a v5e: 12.6 ms against 7.4 ms
    assert 2_473_901_162_496 / 197e12 > 9 * one * 2 / 819e9


# -- scope_ops.py ------------------------------------------------------------

HLO = '''HloModule jit_step

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/jvp(accl.moe::experts)/mul" stack_frame_id=1}
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(accl.moe::experts)/mul" stack_frame_id=1}
  %sort.2 = f32[8]{0} sort(%a), dimensions={0}, metadata={op_name="jit(step)/jvp(accl.moe::dispatch)/sort"}
  %fusion.4 = f32[8]{0} fusion(%sort.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(accl.moe::route))/add_any"}
  %ragged-dot-none.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %jvp_accl.attn__core_.6 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(accl.attn::core)/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(accl.moe::combine)/reduce_sum"}
}
'''


def test_scopes_come_from_the_entry_computations_op_names():
    assert scope_ops.scopes_of(HLO) == {
        "accl.moe::experts": ["fusion.3"],       # not mul.9: inside a fusion
        "accl.moe::dispatch": ["sort.2"],
        "accl.moe::route": ["fusion.4"],         # a backward op
        "accl.attn::core": ["jvp_accl.attn__core_.6"],
        "accl.moe::combine": ["fusion.5"],
    }
    assert scope_ops.scopes_of("no entry here") == {}


def test_scope_time_joins_by_instruction_name_and_counts_ragged_dot():
    reduced = {"host": [], "devices": {"/device:TPU:0": [
        ["fusion.3 fusion f32[8]", 0, 10.0],
        ["sort.2 sort f32[8]", 10, 5.0],
        ["ragged-dot-none.1 custom-call tpu_custom_call f32[8]", 15, 100.0],
        ["ragged-dot-metadata.7 custom-call tpu_custom_call s32[65]", 115, 1.0],
        ["fusion.99 fusion f32[8]", 116, 50.0],  # in no scope
        ["fusion.3 fusion f32[8]", 200, 10.0],   # the next step
    ]}}
    got = scope_ops.scope_ns(reduced, scope_ops.scopes_of(HLO))
    assert got == {"accl.moe::experts": 121.0, "accl.moe::dispatch": 5.0}


def _ctx(cell, recorded):
    return {
        "cell": cell, "peaks": {"bf16_flops_per_s": 197e12,
                                "hbm_bytes_per_s": 819e9},
        "facts": recorded["facts"],
        "slices": {"steps": {"reduced": recorded,
                             "window": trace_reduce.window_of(recorded)}},
    }


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "chip_train_olmoe.json")) as f:
        return json.load(f)


def test_the_readers_on_a_trace_cut_from_the_chip(cell, recorded):
    """One traced step of the cell on the v5e (PR 26), reduced by
    ``trace_reduce.load`` and cut to that step, with the scope map the
    driver handed over; ``expect`` was worked out from the same events by
    a scratch script's own loops (sums by instruction name, a union of
    intervals), not by the readers."""
    ctx, want = _ctx(cell, recorded), recorded["expect"]
    by_scope, busy = _moe.times(ctx)
    for scope, ns in want["scope_ns"].items():
        assert by_scope[scope] == pytest.approx(ns)
    assert busy == pytest.approx(want["busy_ns"])
    assert moe_time_share.read(ctx) == pytest.approx(want["moe_time_share"])
    assert moe_route_time_share.read(ctx) == pytest.approx(
        want["moe_route_time_share"]
    )
    assert moe_expert_roofline_share.read(ctx) == pytest.approx(
        want["moe_expert_roofline_share"]
    )
    for value in (moe_time_share.read(ctx), moe_route_time_share.read(ctx),
                  moe_expert_roofline_share.read(ctx)):
        assert 0 < value < 100
    assert olmoe_mfu.read(ctx) == pytest.approx(
        100 * flops_olmoe.train_flops_per_token(cell["config"], 4096)
        * recorded["facts"]["tokens_per_s"] / 197e12
    )
    assert moe_load_imbalance.read(ctx) == recorded["facts"]["router"][
        "load_imbalance"
    ]


def test_a_program_without_the_scopes_reads_as_nothing(cell, recorded):
    """The parent's side of a traced run: no ``scope_ops`` in the facts."""
    ctx = _ctx(cell, recorded)
    ctx["facts"] = {k: v for k, v in recorded["facts"].items()
                    if k not in ("scope_ops", "router")}
    assert moe_time_share.read(ctx) is None
    assert moe_route_time_share.read(ctx) is None
    assert moe_expert_roofline_share.read(ctx) is None
    assert moe_load_imbalance.read(ctx) is None
    ctx["slices"] = {}
    ctx["facts"] = recorded["facts"]
    assert moe_time_share.read(ctx) is None


# -- the driver's own pieces ---------------------------------------------------


def test_the_driver_maps_every_published_key(cell):
    from perfbench.drivers import train_steps_olmoe as driver

    cfg = driver.program_config(cell["config"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads(), cfg.d_ff) == (
        2048, 16, 16, 1024)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.vocab, cfg.max_seq) == (
        64, 8, 50304, 4096)
    assert cfg.moe_capacity_factor is None and not cfg.moe_norm_topk_prob
    assert (cfg.norm, cfg.ffn, cfg.qk_norm, cfg.tie_head) == (
        "rmsnorm", "swiglu", True, False)
    assert (cfg.moe_aux_weight, cfg.moe_router_z_weight) == (0.01, 0.001)
    assert cfg.uses_rope() and cfg.rope_base == 10000.0


def test_router_facts_by_hand():
    import jax.numpy as jnp

    from perfbench.drivers import train_steps_olmoe as driver

    # two tokens, four experts, top 2: token 0 picks experts 3 and 1 and
    # its third logit is 1.0 below the second; token 1 picks 0 and 1
    logits = jnp.array([[0.0, 2.0, 1.0, 3.0], [5.0, 4.0, 3.9375, 0.0]])
    counts, gaps = driver.router_facts(logits, 2)
    assert counts.tolist() == [1, 2, 0, 1]
    spacing = 2.0 ** -8 * float(jnp.sqrt(jnp.mean(logits ** 2)))
    assert gaps.tolist() == pytest.approx([1.0 / spacing, 0.0625 / spacing])
