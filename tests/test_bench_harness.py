"""Start-up and measurement harness: nothing on the path to a device
number may hide the device.

* ``chip_smoke.py``: each leg at a tiny size on the 8-device CPU mesh,
  and ``main`` refusing to run a leg off the TPU or on a ``device_kind``
  the peak table does not know;
* ``perfbench.run``, the benchmark ``BENCHMARK.json`` declares: the
  command's refusal off the TPU, and that every cell's CPU rehearsal
  (its driver, the program's main path and the check against the plain
  reference, at tiny sizes) is a case of some
  ``tests/test_bench_rehearsal_*.py``;
* the compile-cache helper, the peak table, and the refusals that took
  the place of quiet fallbacks (``xla_group`` past the device count,
  ``dryrun_multichip`` short of devices, the dist launcher on a TPU
  host, a failed native build).

Everything here is stubbed or tiny; a rehearsal is the benchmark's own
command in a child process, a cell a case, in those files.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, os.path.abspath(ROOT))

import chip_smoke  # noqa: E402
from helpers import REHEARSALS, perfbench  # noqa: E402
from perfbench import manifest  # noqa: E402


@pytest.fixture()
def no_cache(monkeypatch):
    """Entry points configure the compile cache first thing; inside the
    test process that global must stay as it was."""
    import accl_tpu.utils

    monkeypatch.setattr(
        accl_tpu.utils, "use_compile_cache", lambda: "<not configured>"
    )


def _fake_devices(kind="TPU v5 lite", platform="tpu", count=1):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return lambda *a, **k: [dev] * count


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# chip_smoke: the legs at a tiny size
# ---------------------------------------------------------------------------


def test_smoke_facade_leg_tiny():
    out = chip_smoke.leg_facade(
        4, sizes=(1 << 10,), warm_iters=1, window_bytes=1 << 10
    )
    assert out["ranks"] == 4 and len(set(out["devices"])) == 4
    per = out["sizes"]["1024"]["interactions"]
    assert all(
        n == 1 for op, n in per.items() if op != "sendrecv"
    ), per
    w = out["window"]
    assert w["slots"] > 0 and w["fallbacks"] == {}
    assert w["breaker_strikes"] == {} and w["warm_interactions"] == 1


def test_smoke_kernels_leg_tiny():
    out = chip_smoke.leg_kernels(
        2, elems=1 << 12, flash_lengths=(128,),
        remote_elems=2 * 8 * 128, wire_elems=1 << 11,
    )
    assert out["local"]["combine"] == "exact"
    assert out["remote"]["ring_allreduce"] == "exact"
    assert out["wire"]["fp8_bytes_differ_normal"] == 0
    assert out["compile_s"] > 0


def test_smoke_wire_lanes_on_one_rank_with_fp8_subnormals():
    """The driver's one-chip machine: a single contribution, and enough
    elements that some fall under the fp8 lane's smallest normal, where
    the rounding step is fixed and no longer 2^-3 of the value."""
    out = chip_smoke._wire_lanes(1, 1 << 16, chip_smoke._Clock())
    assert 1e-4 < out["float8_e4m3_max_err"] < 1.0
    assert 1e-4 < out["int8_max_err"] < 0.1


def test_smoke_kernels_leg_names_what_one_chip_cannot_run(monkeypatch):
    for part in ("_kernels_local", "_kernels_flash", "_wire_lanes"):
        monkeypatch.setattr(chip_smoke, part, lambda *a, **k: {})
    out = chip_smoke.leg_kernels(1, flash_lengths=())
    assert "need two chips" in out["remote"]


def test_smoke_flagship_leg_tiny():
    from accl_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab=256, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=32,
        dtype=jnp.bfloat16, attention="auto",
    )
    out = chip_smoke.leg_flagship(
        cfg, cfg, dp=2, tp=2, seq=32, batch=4, prompt_len=4,
        new_tokens=2, expect_attention="naive",
    )
    assert out["mesh"] == {"dp": 2, "tp": 2}
    assert out["losses"][-1] < out["losses"][0]


def test_smoke_flagship_leg_refuses_the_wrong_attention_lowering():
    from accl_tpu.models import TransformerConfig

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_seq=32,
        attention="auto",
    )
    with pytest.raises(AssertionError, match="resolved to 'naive'"):
        chip_smoke.leg_flagship(cfg, cfg, dp=1, tp=1, seq=32, batch=2)


def test_smoke_zoo_leg_runs_the_dry_run_on_every_chip(monkeypatch):
    """The wiring only: ``dryrun_multichip`` itself costs half a minute
    on the CPU mesh (``test_smoke_zoo_leg_for_real`` is marked slow)."""
    import __graft_entry__

    asked = []
    monkeypatch.setattr(__graft_entry__, "dryrun_multichip", asked.append)
    out = chip_smoke.leg_zoo(4)
    assert asked == [4] and out["chips"] == 4 and "compile_s" in out


@pytest.mark.slow
def test_smoke_zoo_leg_for_real():
    assert chip_smoke.leg_zoo(2)["chips"] == 2


# ---------------------------------------------------------------------------
# chip_smoke.main: the chip or nothing
# ---------------------------------------------------------------------------


def test_smoke_main_refuses_the_cpu(no_cache, capsys):
    assert chip_smoke.main() == 1
    io = capsys.readouterr()
    assert "no TPU" in io.err and "leg" not in io.out


def test_smoke_main_refuses_an_unknown_device_kind(
    no_cache, monkeypatch, capsys
):
    monkeypatch.setattr(jax, "devices", _fake_devices(kind="TPU v9"))
    with pytest.raises(KeyError, match="TPU v9"):
        chip_smoke.main()
    assert "leg" not in capsys.readouterr().out


def _stub_legs(monkeypatch, failing=None):
    def leg(name):
        def run(*a, **k):
            if name == failing:
                raise RuntimeError(f"{name} broke")
            return {"compile_s": 0.0, "steady_s": 0.0}

        return run

    for name in ("facade", "kernels", "flagship", "zoo"):
        monkeypatch.setattr(chip_smoke, f"leg_{name}", leg(name))


def test_smoke_main_last_line_is_the_device_json(
    no_cache, monkeypatch, capsys
):
    monkeypatch.setattr(jax, "devices", _fake_devices(count=4))
    _stub_legs(monkeypatch)
    assert chip_smoke.main() == 0
    assert _last_json(capsys) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


def test_smoke_main_fails_when_any_leg_failed(no_cache, monkeypatch, capsys):
    monkeypatch.setattr(jax, "devices", _fake_devices())
    _stub_legs(monkeypatch, failing="kernels")
    assert chip_smoke.main() == 1
    io = capsys.readouterr()
    last = json.loads(io.out.strip().splitlines()[-1])
    assert last["ok"] is False and last["failed"] == ["kernels"]
    assert "leg zoo" in io.out  # the legs after the failure still ran


# ---------------------------------------------------------------------------
# perfbench.run: the benchmark's command, rehearsed on the CPU
# ---------------------------------------------------------------------------


def test_every_cell_is_rehearsed_once_in_some_file():
    """The rehearsals are ``tests/test_bench_rehearsal_*.py``, a group a
    file (``helpers.REHEARSALS``): every cell of the manifest at
    ``--trace 0``, the sweep and ``train_t1024_b8`` at ``--trace 1`` too,
    none twice."""
    cases = [c for group in REHEARSALS.values() for c in group]
    assert sorted(cases) == sorted(
        [(w["name"], 0) for w in manifest.load()["workloads"]]
        + [("coll_w4_sweep", 1), ("train_t1024_b8", 1)]
    )
    for group in REHEARSALS:
        assert os.path.exists(os.path.join(
            os.path.dirname(__file__), f"test_bench_rehearsal_{group}.py"
        ))


def test_benchmark_refuses_to_run_off_the_tpu():
    proc = perfbench("train_t1024_b8", "--seed", "0", "--seconds", "1",
                     "--trace", "0", JAX_PLATFORMS="cpu")
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert not proc.stdout.strip()


# ---------------------------------------------------------------------------
# the compile cache and the peak table
# ---------------------------------------------------------------------------


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    from accl_tpu.utils import use_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: updates.append(a)
    )
    assert use_compile_cache() == "/somewhere/else"
    assert updates == []  # jax read the variable itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from accl_tpu.utils import use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda *a, **k: updates.append(a)
    )
    want = os.path.join(os.path.abspath(ROOT), ".jax_cache")
    assert use_compile_cache() == want
    assert use_compile_cache() == want  # a fixed path: the cache key
    assert updates == [("jax_compilation_cache_dir", want)] * 2


@pytest.mark.parametrize(
    "kind", ["TPU v5 lite", "TPU v5e", "TPU v5p", "TPU v6 lite", "TPU v4"]
)
def test_device_peaks_known_kinds(kind):
    from accl_tpu.utils import device_peaks

    assert device_peaks(kind)["bf16_flops"] > 0


def test_device_peaks_v5e_row_is_the_published_one():
    from accl_tpu.utils import device_peaks

    row = device_peaks("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row is device_peaks("TPU v5e")


@pytest.mark.parametrize("kind", ["cpu", "TPU v5 turbo", "", "tpu v5 lite"])
def test_device_peaks_unknown_kind_is_an_error(kind):
    from accl_tpu.utils import device_peaks

    with pytest.raises(KeyError, match="no row"):
        device_peaks(kind)


# ---------------------------------------------------------------------------
# refusals where fallbacks were
# ---------------------------------------------------------------------------


def test_xla_group_refuses_more_ranks_than_devices():
    """Over-subscribed ranks used to get ``device=None`` and reduce in
    host numpy under the device tier's name."""
    from accl_tpu.core import xla_group

    n = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"needs {n} devices"):
        xla_group(n)


def test_xla_group_refusal_names_the_tpu_backend(monkeypatch):
    from accl_tpu.core import xla_group

    monkeypatch.setattr(jax, "devices", _fake_devices(count=4))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="jax found 4 on 'tpu'"):
        xla_group(8)


def test_gang_submesh_refuses_a_member_without_a_device():
    from accl_tpu.backends.xla.engine import XLAGangContext

    comm = types.SimpleNamespace(ranks=[
        types.SimpleNamespace(session=0),
        types.SimpleNamespace(session=len(jax.devices())),
    ])
    with pytest.raises(ValueError, match="has no device"):
        XLAGangContext().submesh(comm)


def test_dryrun_multichip_raises_instead_of_moving_to_the_cpu(monkeypatch):
    import __graft_entry__

    def no_children(*a, **k):
        raise AssertionError("dryrun_multichip started a child process")

    monkeypatch.setattr(subprocess, "run", no_children)
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError, match=f"needs {n} devices"):
        __graft_entry__.dryrun_multichip(n)
    assert jax.default_backend() == "cpu"


def _never_spawned(accl, rank, world):  # pragma: no cover
    raise AssertionError("a rank process was spawned")


@pytest.mark.parametrize(
    # (ids spelled out: a bare "tpu" id would read as the chip-tier marker)
    "platforms", ["tpu", "tpu,cpu"], ids=["named-alone", "named-first"]
)
def test_launcher_refuses_the_dist_design_on_a_tpu_host(
    monkeypatch, platforms
):
    from accl_tpu.launch import launch_processes

    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(RuntimeError, match="cannot run on a TPU host"):
        launch_processes(_never_spawned, 2, design="xla_dist", timeout=5)


def test_launcher_finds_a_tpu_host_by_its_device_nodes(monkeypatch):
    import glob

    from accl_tpu import launch

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(
        glob, "glob", lambda pat: ["/dev/vfio/3"] if "vfio" in pat else []
    )
    assert launch._ranks_would_open_tpu()
    monkeypatch.setattr(glob, "glob", lambda pat: [])
    assert not launch._ranks_would_open_tpu()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")  # the test tier's own state
    monkeypatch.setattr(glob, "glob", lambda pat: ["/dev/accel0"])
    assert not launch._ranks_would_open_tpu()


@pytest.fixture()
def fresh_native(monkeypatch):
    import accl_tpu.native as native

    monkeypatch.setattr(native, "_BUILD_OK", None)
    monkeypatch.setattr(native, "_LOAD_ATTEMPTED", False)
    monkeypatch.setattr(native, "_LIB", None)
    return native


def test_native_loader_runs_make_and_lets_it_decide(
    fresh_native, monkeypatch
):
    calls = []

    def make(cmd, **k):
        calls.append(cmd)
        return subprocess.CompletedProcess(cmd, 0, "", "")

    monkeypatch.setattr(subprocess, "run", make)
    assert fresh_native.build() is True
    assert fresh_native.build() is True  # once a process
    assert len(calls) == 1 and calls[0][:2] == ["make", "-C"]


def test_native_failed_build_is_said_and_never_loads_a_stale_library(
    fresh_native, monkeypatch
):
    monkeypatch.setattr(
        subprocess, "run",
        lambda cmd, **k: subprocess.CompletedProcess(
            cmd, 2, "", "dataplane.cpp:7: error: expected ';'"
        ),
    )
    with pytest.warns(RuntimeWarning, match="expected ';'"):
        assert fresh_native.build() is False
    assert fresh_native.available() is False  # the numpy path, not the .so


def test_native_no_toolchain_is_the_numpy_path(fresh_native, monkeypatch):
    import shutil

    monkeypatch.setattr(shutil, "which", lambda name: None)
    assert fresh_native.build() is False


# ---------------------------------------------------------------------------
# which lowering ran
# ---------------------------------------------------------------------------


def _q(T, dtype=jnp.bfloat16, D=128):
    return jax.ShapeDtypeStruct((1, 8, T, D), jnp.dtype(dtype))


def test_resolve_attention_says_what_auto_takes_off_the_tpu():
    from accl_tpu.models.transformer import resolve_attention

    assert resolve_attention("auto", _q(512)) == "naive"
    assert resolve_attention("auto", _q(1024)) == "blockwise"
    assert resolve_attention("flash", _q(8)) == "flash"  # by name: as is


def test_resolve_attention_on_a_tpu_backend(monkeypatch):
    from accl_tpu.models.transformer import resolve_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_attention("auto", _q(512)) == "naive"
    assert resolve_attention("auto", _q(1024)) == "flash"
    assert resolve_attention("auto", _q(8192)) == "flash"  # the gate's edge
    # past the VMEM gate, and for f16, auto quietly leaves the kernel:
    # the smoke asks so that it is not quiet
    assert resolve_attention("auto", _q(16384)) == "blockwise"
    assert resolve_attention("auto", _q(8192, jnp.float32)) == "blockwise"
    assert resolve_attention("auto", _q(1024, jnp.float16)) == "blockwise"


@pytest.mark.parametrize("lane", ["float8_e4m3fn", "bfloat16", "float16"])
def test_wire_cast_lane_cannot_be_compiled_away(lane):
    """Chip run, PR 21: XLA on the TPU removed the narrow -> wide convert
    pair and the cast lanes rounded nothing inside one program.  The
    barrier between the two casts is what keeps the rounding."""
    from accl_tpu.ops import wire as devwire

    x = jnp.linspace(-3.0, 3.0, 64, dtype=jnp.float32)
    fn = jax.jit(lambda v: devwire.wire_lane_roundtrip(v, jnp.dtype(lane)))
    assert "optimization_barrier" in fn.lower(x).as_text()
    assert float(jnp.max(jnp.abs(fn(x) - x))) > 0.0


def test_ring_stream_is_one_program_a_window():
    """What every backend runs: windows dispatched ahead of the device
    ride one program each — no fallback, same bytes."""
    from accl_tpu.core import xla_group

    g = xla_group(2)
    try:
        ring = g[0].engine.gang.cmdring
        n, windows = 32, 4
        send = [a.create_buffer_from(np.full(n, r + 1.0, np.float32))
                for r, a in enumerate(g)]
        out = [a.create_buffer(n, np.float32) for a in g]

        def stream(a, r):
            reqs = []
            a.begin_batch()
            try:
                for _ in range(windows):
                    reqs += [a.allreduce(send[r], out[r], n, run_async=True)
                             for _ in range(2)]
                    a._dispatch_pending()  # post, do not drain
            finally:
                a.end_batch()
            for req in reqs:
                assert req.wait(60)
                req.check()

        chip_smoke._run_ranks(g, stream)
        st = ring.stats()
        assert st["refills"] == st["dispatches"] == windows
        assert st["slots"] == 2 * windows and st["fallbacks"] == {}
        out[0].sync_from_device()
        np.testing.assert_array_equal(out[0].data, 3.0)
    finally:
        for a in g:
            a.deinit()
