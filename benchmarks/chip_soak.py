"""Real-chip endurance soak: the facade on HBM DeviceBuffers, world=1.

The CPU-tier soaks (tests/test_soak.py) prove slot lifecycle over OS
processes; this is the
same discipline on the DEVICE tier — randomized op mix and sizes
through the gang backend on a real TPU, integrity-checked every
iteration against numpy, with the rx-accounting dump asserted clean at
the end (ref stress role: test/host/xrt/src/stress.cpp:24).

Run on the chip (one process; it refuses any other backend)::

    ACCL_SOAK_SECONDS=900 python benchmarks/chip_soak.py

Emits one JSON line: {"iters": N, "ops": M, "seconds": S,
"ops_per_s": R, "rx_leaks": [...], "device": "..."}.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _emit_telemetry(a, phase: str, out_dir: str) -> dict:
    """Write this phase's telemetry snapshot + rank trace artifacts and
    validate them: non-empty flight recorder, JSON that round-trips, a
    trace with events.  Returns {phase, records, paths, ok} — a soak
    whose telemetry is empty/malformed FAILS (exit code), because an
    unobservable chip run is exactly the failure mode this plane exists
    to end."""
    os.makedirs(out_dir, exist_ok=True)
    snap_path = os.path.join(out_dir, f"chip_soak_telemetry_{phase}.json")
    trace_path = os.path.join(out_dir, f"chip_soak_trace_{phase}_rank0.json")
    out = {"phase": phase, "snapshot": snap_path, "trace": trace_path,
           "records": 0, "ok": False}
    try:
        snap = a.telemetry_snapshot()
        with open(snap_path, "w") as f:
            f.write(a.telemetry_json())
        a.export_chrome_trace(trace_path)
        with open(snap_path) as f:
            loaded = json.load(f)
        with open(trace_path) as f:
            trace = json.load(f)
        out["records"] = len(loaded.get("flight_recorder") or ())
        out["ok"] = bool(
            out["records"]
            and snap.get("metrics", {}).get("histograms")
            and trace.get("traceEvents")
        )
    except Exception as e:  # malformed output must fail the soak, loudly
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def main() -> int:
    import jax

    from accl_tpu.utils import use_compile_cache

    use_compile_cache()
    if jax.default_backend() != "tpu":
        print(json.dumps({"error": f"needs a TPU backend, got "
                          f"{jax.default_backend()}"}))
        return 2
    from accl_tpu.core import xla_group

    seconds = float(os.environ.get("ACCL_SOAK_SECONDS", "900"))
    g = xla_group(1)
    a = g[0]
    try:
        a.set_timeout(180.0)
        rng = np.random.default_rng(7)
        # a fixed size set (incl. odd/ragged values) so the gang's
        # per-(op, shape) programs compile once and the soak then
        # measures the slot/request lifecycle at cached-dispatch rate,
        # not the compiler (same reasoning as the dist tier's wire
        # buckets)
        sizes = [1, 3, 7, 17, 64, 100, 255, 512, 777, 1024, 2000, 3000,
                 4095, 4096, 5000, 6001, 8000, 8192, 10000, 12000,
                 14321, 15000, 16000, 16384]
        deadline = time.monotonic() + seconds
        # interaction delta over the TIMED loop only (the counter is
        # engine-lifetime; lifetime/ops would inflate the per-op figure)
        di0 = a.engine.device_interactions()
        t0 = time.monotonic()
        iters = 0
        ops = 0
        while time.monotonic() < deadline:
            op = ["allreduce", "bcast", "allgather", "copy",
                  "combine", "reduce", "alltoall"][int(rng.integers(0, 7))]
            count = int(sizes[int(rng.integers(0, len(sizes)))])
            seed_i = int(rng.integers(0, 1 << 31))
            data = (np.random.default_rng(seed_i)
                    .standard_normal(count).astype(np.float32))
            if op == "copy":
                s = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.copy(s, d, count)
            elif op == "combine":
                from accl_tpu.constants import ReduceFunction

                s = a.create_buffer_from(data)
                s2 = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.combine(ReduceFunction.SUM, s, s2, d, count)
                data = data + data
            elif op == "bcast":
                d = a.create_buffer_from(data)
                a.bcast(d, count, root=0)
            elif op == "reduce":
                s = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.reduce(s, d, count, root=0)
            elif op == "alltoall":
                s = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.alltoall(s, d, count)
            elif op == "allgather":
                s = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.allgather(s, d, count)
            else:
                s = a.create_buffer_from(data)
                d = a.create_buffer(count, np.float32)
                a.allreduce(s, d, count)
            out = d
            out.sync_from_device()
            np.testing.assert_allclose(
                out.data[:count], data, rtol=1e-5, atol=1e-6
            )
            iters += 1
            ops += 1
        dt = time.monotonic() - t0
        # The leak filter is REAL on this tier now: XLAEngine's
        # dump_rx_buffers reports parked gang slots, unmatched p2p posts
        # and undrained stream ports as non-IDLE ``rxbuf`` lines (it used
        # to be absent here, which made rx_leaks vacuously []); a clean
        # run ends with zero such lines.
        rx = a.dump_rx_buffers()
        leaks = [ln for ln in rx.splitlines()
                 if "rxbuf" in ln and "IDLE" not in ln]
        di = a.engine.device_interactions() - di0

        # telemetry artifacts, per phase: snapshot + per-rank trace
        # (merge multi-rank runs with `python -m accl_tpu.telemetry
        # merge`); empty/malformed output fails the soak
        tele_dir = os.environ.get(
            "ACCL_SOAK_TELEMETRY_DIR",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "results"),
        )
        tele_soak = _emit_telemetry(a, "soak", tele_dir)

        # fault-recovery phase: one injected drop-and-recover round.  The
        # device tier's fault mode is "a peer never arrives", so induce a
        # recv whose sender does not exist, assert the watchdog converts
        # it to a FAST structured failure (not a hang), soft-reset, and
        # verify the engine serves collectives again with a clean rx dump.
        fault = {"injected": 0, "recovered": False, "rx_leaks": ["unrun"]}
        a.set_timeout(1.0)
        probe = a.create_buffer(8, np.float32)
        t_f = time.monotonic()
        try:
            a.recv(probe, 8, src=0, tag=0x7A7A)  # dropped: no sender
        except Exception as e:
            fault["injected"] = 1
            fault["error"] = type(e).__name__
            fault["details"] = getattr(e, "details", {})
        fault["fail_seconds"] = round(time.monotonic() - t_f, 2)
        # overlap plane: issue a burst of in-flight collectives and
        # soft-reset behind them — soft_reset's drain point must leave
        # the window FULLY empty (every request completed) before the
        # engine state is abandoned
        burst_s = a.create_buffer_from(np.ones(256, np.float32))
        burst_d = a.create_buffer(256, np.float32)
        burst = [
            a.allreduce(burst_s, burst_d, 256, run_async=True)
            for _ in range(6)
        ]
        a.soft_reset()
        fault["window_drained"] = bool(
            all(r.done() for r in burst)
            and (a.engine.telemetry_report().get("inflight") or {}).get(
                "in_flight", -1
            ) == 0
        )
        a.set_timeout(180.0)
        rs = a.create_buffer_from(np.ones(64, np.float32))
        rd = a.create_buffer(64, np.float32)
        a.allreduce(rs, rd, 64)
        rd.sync_from_device()
        fault["recovered"] = bool(np.allclose(rd.data[:64], 1.0))
        fault["rx_leaks"] = [
            ln for ln in a.dump_rx_buffers().splitlines()
            if "rxbuf" in ln and "IDLE" not in ln
        ]
        # fault-phase telemetry: the snapshot now carries the failed
        # recv in its flight recorder (retcode != OK) — the structured
        # history an offline debugger reads instead of the log
        tele_fault = _emit_telemetry(a, "fault", tele_dir)
        print(json.dumps({
            "iters": iters, "ops": ops, "seconds": round(dt, 1),
            "ops_per_s": round(ops / dt, 2), "rx_leaks": leaks,
            # single-interaction telemetry: ~1 interaction per warm
            # collective on the fast path (buffer staging/sync around
            # each op is separate and not billed here)
            "device_interactions": di,
            "interactions_per_op": round(di / max(ops, 1), 2),
            "device": jax.devices()[0].device_kind,
            "fault_recovery": fault,
            # overlap plane: lifetime window counters (launched/
            # completed must match for a leak-free run)
            "inflight": a.engine.telemetry_report().get("inflight"),
            "telemetry": [tele_soak, tele_fault],
        }))
        ok = (
            not leaks
            and fault["injected"] == 1
            and fault["recovered"]
            and fault["rx_leaks"] == []
            and fault.get("window_drained", False)
            and tele_soak["ok"]
            and tele_fault["ok"]
        )
        return 0 if ok else 1
    finally:
        for x in g:
            x.deinit()


if __name__ == "__main__":
    sys.exit(main())
