"""What PR 37 adds to the benchmark, checked by hand on the CPU: the reader
of ``embed_grad_time_share`` on a trace cut from a v5e run of
``train_dsv2_t4096_b1`` and on one written by hand, what it gives against
a program without the scope (the parent's side of a traced run), and its
manifest entry."""

import copy
import json
import os

import pytest

from perfbench import manifest, scope_ops, trace_reduce
from perfbench.layer_metrics import embed_grad_time_share

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "train_dsv2_t4096_b1"
SCOPE = "accl.embed::grad"


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "chip_train_dsv2_embed.json")) as f:
        return json.load(f)


def _ctx(recorded):
    return {
        "facts": copy.deepcopy(recorded["facts"]),
        "slices": {"steps": {"reduced": recorded,
                             "window": trace_reduce.window_of(recorded)}},
    }


def test_the_reader_on_a_trace_cut_from_the_chip(recorded):
    """The end of one traced step of the cell on the v5e (PR 37): the
    table's update and the ops before it, reduced by ``trace_reduce.load``,
    with the scope map the driver handed over; ``expect`` was worked out
    from the same events by a scratch script's own loops."""
    ctx, want = _ctx(recorded), recorded["expect"]
    names = ctx["facts"]["scope_ops"][SCOPE]
    assert scope_ops.scope_ns(
        recorded, {SCOPE: names}
    )[SCOPE] == pytest.approx(want["scope_ns"])
    got = embed_grad_time_share.read(ctx)
    assert got == pytest.approx(want["embed_grad_time_share"])
    assert 0 < got < 100
    # the op under the scope writes the table: the matmul took the place
    # of the scatter-add
    events = [e for e in recorded["devices"]["/device:TPU:0"]
              if scope_ops.instruction_name(e[0]) in names]
    assert events and all("bf16[12800,5120]" in e[0] for e in events)


HLO = '''HloModule jit_step

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, calls=%f, metadata={op_name="jit(step)/jvp(accl.attn::latent)/dot_general"}
  %iota.2 = s32[8]{0} iota(), iota_dimension=0, metadata={op_name="jit(step)/transpose(jvp(accl.embed::grad))/transpose(jvp())/iota"}
  %fusion.3 = bf16[8]{0} fusion(%a), kind=kCustom, calls=%f, metadata={op_name="jit(step)/transpose(jvp(accl.embed::grad))/transpose(jvp())/scatter-add"}
  ROOT %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(step)/sub"}
}
'''


def _hand(scopes=True):
    reduced = {
        "host": [["bench::step", 0.0, 1000.0, "t#0"]],
        "devices": {"/device:TPU:0": [
            ["fusion.1 fusion f32[8]", 0, 400.0],
            ["iota.2 iota s32[8]", 400, 10.0],
            ["fusion.3 fusion bf16[8]", 450, 90.0],   # 40 idle before it
            ["fusion.4 fusion f32[8]", 540, 300.0],
        ]},
    }
    facts = {"tokens_per_s": 1.0}
    if scopes:
        facts["scope_ops"] = scope_ops.scopes_of(HLO)
    return {
        "facts": facts,
        "slices": {"steps": {"reduced": reduced, "window": (0.0, 1000.0)}},
    }


def test_the_reader_on_a_hand_written_trace():
    """Every op under the scope counts (a scatter-add's sort and iota
    beside it), over BUSY time, not the window."""
    assert scope_ops.scopes_of(HLO)[SCOPE] == ["iota.2", "fusion.3"]
    assert embed_grad_time_share.read(_hand()) == pytest.approx(
        100 * 100.0 / 800.0
    )


@pytest.mark.parametrize("case", [
    "no_scope_map", "scope_not_in_the_map", "no_slice", "no_event_under_it",
])
def test_a_program_without_the_scope_reads_as_nothing(case):
    """The parent's side of a traced run: ``None``, and the line leaves
    the metric out."""
    ctx = _hand(scopes=case != "no_scope_map")
    if case == "scope_not_in_the_map":
        ctx["facts"]["scope_ops"].pop(SCOPE)
    elif case == "no_slice":
        ctx["slices"] = {}
    elif case == "no_event_under_it":
        ctx["facts"]["scope_ops"][SCOPE] = ["fusion.99"]
    assert embed_grad_time_share.read(ctx) is None


def test_the_recorded_run_without_its_scope_reads_as_nothing(recorded):
    ctx = _ctx(recorded)
    ctx["facts"]["scope_ops"].pop(SCOPE)
    assert embed_grad_time_share.read(ctx) is None
    ctx["facts"].pop("scope_ops")
    assert embed_grad_time_share.read(ctx) is None


def test_the_manifest_entry(doc):
    entry = doc["per_layer"][-1]          # appended, nothing before it moved
    assert entry["name"] == "embed_grad_time_share"
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "lower", "device_trace"
    )
    assert (entry["layer"], entry["moves"]) == ("models", "train_tokens_per_s")
    moved = next(
        m for m in doc["end_to_end"] if m["name"] == "train_tokens_per_s"
    )
    assert CELL in entry["workloads"]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    # only cells whose driver hands the readers a scope map
    for name in entry["workloads"]:
        cell = manifest.cell(doc, name)
        assert "embed_grad_time_share" in [m["name"] for m in cell["per_layer"]]
        assert cell["traffic"]["driver"] != "train_steps"
