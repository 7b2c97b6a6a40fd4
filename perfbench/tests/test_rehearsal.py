"""The CPU rehearsal of every cell: correct, and no device metric."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import manifest

_COUNTS = {"plan_hit_share", "interactions_per_call",
           "interactions_per_window", "ring_fallbacks", "peak_hbm"}


def _run(cell, trace, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload", cell,
         "--seed", "3", "--seconds", "2", "--trace", str(trace), *extra],
        cwd=manifest.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=600,
    )
    return proc


@pytest.mark.parametrize("cell", [w["name"] for w in manifest.load()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_prints_no_device_metric(cell, trace):
    proc = _run(cell, trace, "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) <= _COUNTS
    if not trace:
        assert line["metrics"] == {}
    diag = json.loads(proc.stdout.strip().splitlines()[-2])
    assert diag["facts"] is None and diag["samples"]


def test_off_the_tpu_the_command_refuses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "perfbench.run", "--workload",
         "train_t1024_b8", "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=manifest.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip()
