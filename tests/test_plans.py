"""Cached collective plans (accl_tpu.plans) + measurement-driven tuning
plans (accl_tpu.tuning): key anatomy, counters, invalidation rules, JSON
round-trip, per-size-bucket overlay dispatch, and the autotuner itself.

The dispatch-side counter contracts (warm call = 1 interaction AND a
plan-cache hit; set_tuning/soft_reset/epoch churn re-plan exactly once)
live in tests/test_dispatch_overhead.py next to the interaction counter
they extend.
"""

import json
import os

import numpy as np
import pytest

from helpers import run_parallel

from accl_tpu import emulated_group
from accl_tpu.constants import Operation
from accl_tpu.plans import CollectivePlan, PlanCache, size_bucket
from accl_tpu.tuning import (
    REGISTER_DEFAULTS,
    TuningPlan,
    autotune,
    validate_registers,
)


# ---------------------------------------------------------------------------
# plan-cache mechanics (no engine needed)
# ---------------------------------------------------------------------------


def test_size_bucket_is_pow2_floor():
    assert size_bucket(0) == 0
    assert size_bucket(1) == 0
    assert size_bucket(2) == 1
    assert size_bucket(1023) == 9
    assert size_bucket(1024) == 10
    assert size_bucket(1025) == 10


def _plan(key):
    return CollectivePlan(
        key, arithcfg=None, compression=0, wire_dtype=None,
        bucket=4, eager=True, algorithm="xla",
    )


def test_plan_cache_counters_and_invalidation():
    pc = PlanCache(maxsize=4)
    assert pc.get(("k",)) is None          # miss
    pc.store(_plan(("k",)))
    assert pc.get(("k",)) is not None      # hit
    s = pc.stats()
    assert (s["hits"], s["misses"], s["size"]) == (1, 1, 1)
    pc.invalidate("set_tuning")
    s = pc.stats()
    assert s["size"] == 0 and s["invalidations"] == 1
    assert s["last_invalidation"] == "set_tuning"
    assert pc.get(("k",)) is None          # post-invalidation miss


def test_plan_cache_capacity_clears_wholesale():
    pc = PlanCache(maxsize=2)
    pc.store(_plan(("a",)))
    pc.store(_plan(("b",)))
    pc.store(_plan(("c",)))  # over capacity: pool cleared, then stored
    assert len(pc) == 1
    assert pc.get(("c",)) is not None


# ---------------------------------------------------------------------------
# TuningPlan serialization + lookup
# ---------------------------------------------------------------------------


def _toy_plan(world=2, tier="emulator"):
    return TuningPlan(
        world=world,
        tier=tier,
        defaults=dict(REGISTER_DEFAULTS),
        entries={
            "allreduce": {
                4: {"registers": {"ring_segments": 2}, "measured_ns": 10.0},
                10: {"registers": {}, "measured_ns": 20.0},
            },
            "bcast": {
                6: {"registers": {"bcast_flat_tree_max_ranks": 0},
                    "measured_ns": 5.0},
            },
        },
        provenance={"generated_by": "test"},
    )


def test_tuning_plan_json_round_trip(tmp_path):
    plan = _toy_plan()
    path = tmp_path / "plan.json"
    plan.save(str(path))
    back = TuningPlan.load(str(path))
    assert back.world == plan.world and back.tier == plan.tier
    assert back.entries["allreduce"][4]["registers"] == {"ring_segments": 2}
    assert back.defaults["allreduce_algorithm"] == "xla"
    # bucket keys survive as ints through the str-keyed JSON form
    assert set(back.entries["allreduce"]) == {4, 10}


def test_registers_for_nearest_bucket_clamps():
    plan = _toy_plan()
    assert plan.registers_for("allreduce", 4) == {"ring_segments": 2}
    assert plan.registers_for("allreduce", 10) == {}
    # unmeasured buckets answer from the nearest measured one
    assert plan.registers_for("allreduce", 5) == {"ring_segments": 2}
    assert plan.registers_for("allreduce", 19) == {}
    assert plan.registers_for("alltoall", 4) == {}  # no entries: empty


def test_validate_registers_rejects_garbage():
    with pytest.raises(ValueError, match="unknown tuning register"):
        validate_registers({"no_such_register": 1})
    with pytest.raises(ValueError, match="unknown algorithm"):
        validate_registers({"allreduce_algorithm": "quantum"})
    with pytest.raises(ValueError, match="negative"):
        validate_registers({"ring_segments": -1})
    # rooted registers only take rooted lowerings (the engines' own
    # SET_TUNING rule, enforced at plan load so a bad plan fails loudly
    # instead of as CONFIG_ERROR mid-apply / a silent xla fallback)
    with pytest.raises(ValueError, match="not a rooted lowering"):
        validate_registers({"bcast_algorithm": "ring"})
    with pytest.raises(ValueError, match="not a rooted lowering"):
        validate_registers({"gather_algorithm": "pallas_ring_bidir"})
    assert validate_registers({"reduce_algorithm": "pallas_ring"}) == {
        "reduce_algorithm": "pallas_ring"
    }
    out = validate_registers(
        {"allreduce_algorithm": 1, "ring_segments": 2}
    )
    assert out == {"allreduce_algorithm": "ring", "ring_segments": 2}


@pytest.mark.parametrize(
    "name", ["cmdring_run_windows", "cmdring_linger_us"]
)
def test_retired_posture_registers_are_refused_by_name(name, tmp_path):
    """The two registers that steered the command ring's resident run
    went with it: a plan that still carries one fails at load, by name,
    like any unknown register — not as CONFIG_ERROR mid-collective."""
    from accl_tpu.constants import TUNING_DEFAULTS, TUNING_KEY_NAMES

    assert name not in REGISTER_DEFAULTS and name not in TUNING_DEFAULTS
    assert name not in TUNING_KEY_NAMES.values()
    with pytest.raises(ValueError, match=f"unknown tuning register '{name}'"):
        validate_registers({name: 1})
    stale = tmp_path / "stale_posture.json"
    stale.write_text(json.dumps(
        {"world": 2, "tier": "xla", "defaults": {name: 1}, "entries": {}}
    ))
    with pytest.raises(ValueError, match=name):
        TuningPlan.load(str(stale))


@pytest.mark.parametrize("tier", ["emulator", "xla"])
@pytest.mark.parametrize("key", [13, 14])
def test_set_tuning_of_a_retired_register_is_config_error(tier, key):
    """Registers 13 and 14 stay unassigned (the others keep their
    numbers): the engine answers a SET_TUNING of either with
    CONFIG_ERROR, as for any unknown key, and writes nothing."""
    from accl_tpu import ACCLError
    from accl_tpu.constants import ConfigFunction, ErrorCode, TuningKey

    assert key not in {int(k) for k in TuningKey}
    assert int(TuningKey.WIRE_DTYPE) == 12
    assert int(TuningKey.HIERARCHICAL) == 15
    if tier == "xla":
        from accl_tpu.core import xla_group

        g = xla_group(2)
        table = g[0].engine.gang.tuning
    else:
        g = emulated_group(2)
        table = g[0].engine.tuning
    try:
        before = dict(table)
        with pytest.raises(ValueError):
            g[0].set_tuning(key, 1)  # the facade knows no such key
        with pytest.raises(ACCLError) as ei:
            g[0]._config(ConfigFunction.SET_TUNING, 1.0, key=key)
        assert ei.value.code == ErrorCode.CONFIG_ERROR
        assert dict(table) == before
    finally:
        for a in g:
            a.deinit()


def test_tuning_cli_runs_and_refuses_the_retired_posture_flags(
    tmp_path, capsys
):
    """The sweep CLI end to end: a race on the emulator tier emits a
    loadable plan, and the two flags that raced the resident run's
    posture are no longer options."""
    from accl_tpu.tuning import main as tuning_main

    out = tmp_path / "plan.json"
    base = [
        "--backend", "emulator", "--world", "2",
        "--min-exp", "4", "--max-exp", "4", "--runs", "1",
        "--collectives", "allreduce", "--segments", "1",
        "--out", str(out),
    ]
    for flag in ("--cmdring-run-windows", "--cmdring-linger-us"):
        with pytest.raises(SystemExit) as ei:
            tuning_main(base + [flag, "32"])
        assert ei.value.code == 2
        assert "unrecognized arguments" in capsys.readouterr().err
    assert not out.exists()
    assert tuning_main(base) == 0
    plan = TuningPlan.load(str(out))
    assert plan.world == 2 and plan.tier == "emulator"
    assert "allreduce" in plan.entries
    assert not any("cmdring" in k for k in plan.provenance)


def test_stale_plan_file_fails_loudly(tmp_path):
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({
        "world": 2, "tier": "emulator",
        "entries": {"allreduce": {"4": {
            "registers": {"renamed_register": 3}
        }}},
    }))
    with pytest.raises(ValueError, match="unknown tuning register"):
        TuningPlan.load(str(path))


# ---------------------------------------------------------------------------
# load_tuning_plan / env / per-size-bucket overlay dispatch
# ---------------------------------------------------------------------------


@pytest.fixture
def pair():
    g = emulated_group(2)
    yield g
    for a in g:
        a.deinit()


def test_load_tuning_plan_applies_defaults_and_overlay(pair):
    plan = _toy_plan(world=2)
    plan.defaults["bcast_flat_tree_max_ranks"] = 7
    for a in pair:
        assert a.load_tuning_plan(plan) is plan
    # defaults went through the SET_TUNING wire path into the engine
    assert pair[0].engine.tuning["bcast_flat_tree_max_ranks"] == 7
    caps = pair[0].capabilities()
    assert caps["tuning_plan"]["world"] == 2
    assert "allreduce" in caps["tuning_plan"]["collectives"]

    # the per-bucket overlay rides the plan into CallOptions.tuning:
    # bucket 4 (n=16) carries ring_segments=2; bucket 10 (n=1024) none
    n_small, n_big = 16, 1024
    rows = [np.full(n_big, float(r + 1), np.float32) for r in range(2)]
    sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(pair)]
    rb = [a.create_buffer(n_big, np.float32) for a in pair]
    run_parallel(pair, lambda a, r: a.allreduce(sb[r], rb[r], n_small))
    run_parallel(pair, lambda a, r: a.allreduce(sb[r], rb[r], n_big))
    for r in range(2):
        rb[r].sync_from_device()
        np.testing.assert_allclose(rb[r].host_view()[:n_small], 3.0)
    plans = list(pair[0]._plans._plans.values())
    by_bucket = {p.bucket: p for p in plans if p.key[0] == Operation.ALLREDUCE}
    assert by_bucket[size_bucket(n_small)].tuning == {"ring_segments": 2}
    assert by_bucket[size_bucket(n_big)].tuning is None


def test_load_tuning_plan_world_mismatch(pair):
    plan = _toy_plan(world=8)
    with pytest.raises(ValueError, match="world=8"):
        pair[0].load_tuning_plan(plan)
    assert pair[0].load_tuning_plan(plan, strict=False) is None
    assert pair[0].capabilities()["tuning_plan"] is None


def test_tuning_plan_env_round_trip(tmp_path):
    path = tmp_path / "env_plan.json"
    _toy_plan(world=2).save(str(path))
    os.environ["ACCL_TUNING_PLAN"] = str(path)
    try:
        g = emulated_group(2)
        try:
            caps = g[0].capabilities()
            assert caps["tuning_plan"] is not None
            assert caps["tuning_plan"]["world"] == 2
        finally:
            for a in g:
                a.deinit()
    finally:
        del os.environ["ACCL_TUNING_PLAN"]


def test_eager_threshold_overlay_steers_protocol(pair):
    """A per-bucket max_eager_size overlay flips the wire protocol for
    that bucket only — the facade's plan verdict records it and the
    result stays correct over the rendezvous path."""
    plan = TuningPlan(
        world=2, tier="emulator", defaults={},
        entries={"allreduce": {
            6: {"registers": {"max_eager_size": 4}},  # n=64 -> rendezvous
        }},
    )
    for a in pair:
        a.load_tuning_plan(plan)
    n = 64
    rows = [np.full(n, float(r + 1), np.float32) for r in range(2)]
    sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(pair)]
    rb = [a.create_buffer(n, np.float32) for a in pair]
    run_parallel(pair, lambda a, r: a.allreduce(sb[r], rb[r], n))
    for r in range(2):
        rb[r].sync_from_device()
        np.testing.assert_allclose(rb[r].host_view(), 3.0)
    plans = [
        p for p in pair[0]._plans._plans.values()
        if p.key[0] == Operation.ALLREDUCE
    ]
    assert plans and not plans[0].eager, (
        "the overlay threshold must flip the plan's protocol verdict"
    )


def test_gang_overlay_selects_ring_and_stays_correct(rng):
    """On the XLA gang tier a per-bucket overlay steers the PREPARED
    program (the plan-cached handle): a bucket whose registers select
    the explicit ring must produce ring results bit-comparable to the
    default lowering, warm (prepared) and cold alike."""
    from accl_tpu.core import xla_group

    plan = TuningPlan(
        world=4, tier="xla", defaults={},
        entries={"allreduce": {
            5: {"registers": {"allreduce_algorithm": "ring",
                              "ring_segments": 2}},
            10: {"registers": {}},
        }},
    )
    g = xla_group(4)
    try:
        for a in g:
            a.load_tuning_plan(plan)
        n = 32  # bucket 5: the ring overlay
        rows = [rng.standard_normal(n).astype(np.float32) for _ in range(4)]
        sb = [a.create_buffer_from(rows[r]) for r, a in enumerate(g)]
        rb = [a.create_buffer(n, np.float32) for a in g]
        for _ in range(3):  # cold (plan build) + prepared warm calls
            run_parallel(g, lambda a, r: a.allreduce(sb[r], rb[r], n))
        for r in range(4):
            rb[r].sync_from_device()
            np.testing.assert_allclose(
                rb[r].host_view(), np.sum(rows, axis=0), rtol=1e-4,
                atol=1e-5,
            )
        # the overlay reached the engine: the plan carries it
        plans = [
            p for p in g[0]._plans._plans.values()
            if p.key[0] == Operation.ALLREDUCE
        ]
        assert plans and plans[0].tuning == {
            "allreduce_algorithm": "ring", "ring_segments": 2
        }
    finally:
        for a in g:
            a.deinit()


# ---------------------------------------------------------------------------
# the autotuner itself (structural smoke on a live group)
# ---------------------------------------------------------------------------


def test_autotune_emits_valid_plan_and_restores_registers(pair):
    before = dict(pair[0].engine.tuning)
    plan = autotune(
        pair, collectives=["bcast", "allreduce"], sizes=[16], runs=1,
    )
    assert plan.world == 2 and plan.tier == "emulator"
    assert set(plan.entries) <= {"bcast", "allreduce"}
    for per_op in plan.entries.values():
        for entry in per_op.values():
            validate_registers(entry["registers"])
            assert entry["measured_ns"] > 0
            assert "defaults" in entry["candidates"]
    # the group keeps serving with stock registers after the race (the
    # race also materializes device-tier algorithm keys in the table —
    # at their defaults — so compare the pre-existing registers)
    after = pair[0].engine.tuning
    assert all(after[k] == v for k, v in before.items())
    assert after.get("allreduce_algorithm", 0) == 0  # xla
    # and the emitted plan round-trips + loads
    back = TuningPlan.from_json(plan.to_json())
    assert pair[0].load_tuning_plan(back) is back


def test_plan_pipeline_verdict():
    """The overlap plane's segmented-pipelining verdict is cached on the
    plan: payloads above the threshold split into the cached segment
    count, everything else (below threshold, disabled registers) is 1."""
    p = CollectivePlan(
        ("k",), arithcfg=None, compression=0, wire_dtype=None,
        bucket=10, eager=False, algorithm="xla",
        pipeline_threshold=4096, pipeline_segments=4,
    )
    assert p.pipeline_for(4096) == 1      # at threshold: no split
    assert p.pipeline_for(4097) == 4      # above: the cached count
    assert p.describe()["pipeline_threshold"] == 4096
    assert p.describe()["pipeline_segments"] == 4
    # disabled registers (the defaults) never split
    off = CollectivePlan(
        ("k2",), arithcfg=None, compression=0, wire_dtype=None,
        bucket=10, eager=False, algorithm="xla",
    )
    assert off.pipeline_for(1 << 30) == 1
    one_seg = CollectivePlan(
        ("k3",), arithcfg=None, compression=0, wire_dtype=None,
        bucket=10, eager=False, algorithm="xla",
        pipeline_threshold=4096, pipeline_segments=1,
    )
    assert one_seg.pipeline_for(1 << 30) == 1
