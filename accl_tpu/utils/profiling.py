"""XLA profiler (xprof) hooks — the device-side tracing surface.

The reference's tracing story is a free-running hardware counter copied
into exchange memory per call (`ccl_offload_control.c:2279-2303`) plus
host timers; the TPU-native equivalents layer up:

* per-call ns: ``Request.get_duration_ns`` (already on every tier);
* per-call records: the telemetry plane (``accl_tpu.telemetry``) rings
  every completion into the flight recorder and exports Chrome/Perfetto
  spans named ``accl::<op>`` — the SAME naming :func:`annotate` puts in
  the xprof timeline, so host ranges and exported spans line up;
* host spans: :func:`annotate` marks facade calls so they appear as
  named ranges in the xprof timeline;
* device spans: :func:`device_scope` names a region *inside* a jitted
  program (XLA op metadata), so kernels show up attributed in the trace
  viewer;
* whole-program capture: :func:`trace` / :func:`start_server` drive
  ``jax.profiler`` — open the result in xprof/tensorboard or perfetto.

jax is imported LAZILY: the emulator/native tiers (and the telemetry
plane's exporters) run in jax-free processes, and pulling a device
runtime into them just to name a span would be a side effect a tracing
utility must not have.  Off-jax, :func:`annotate` / :func:`device_scope`
degrade to no-op context managers.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional


def _jax():
    import jax

    return jax


class annotate:
    """Host-side named range (xprof Python/host rows); a no-op context
    manager when jax is unavailable (jax-free emulator processes)."""

    def __init__(self, name: str):
        self._name = name
        try:
            self._inner = _jax().profiler.TraceAnnotation(name)
        except Exception:
            self._inner = None

    def __enter__(self):
        if self._inner is not None:
            self._inner.__enter__()
        return self

    def __exit__(self, *exc):
        if self._inner is not None:
            return self._inner.__exit__(*exc)
        return False


def device_scope(name: str):
    """In-program named scope (XLA op metadata; device rows of the
    trace); no-op off-jax."""
    try:
        return _jax().named_scope(name)
    except Exception:
        return contextlib.nullcontext()


@contextlib.contextmanager
def trace(logdir: str, *, host_tracer_level: int = 2) -> Iterator[None]:
    """Capture a profiler trace of everything inside the block into
    ``logdir`` (xprof format; load with tensorboard or xprof)."""
    jax = _jax()
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def start_server(port: int = 9012):
    """Live capture endpoint: run once, then point
    ``tensorboard --logdir`` profile capture (or xprof) at this port."""
    return _jax().profiler.start_server(port)


def device_memory_profile(backend: Optional[str] = None) -> bytes:
    """pprof-format snapshot of live device allocations (the memory side
    of the reference's exchange-memory/buffer dumps)."""
    return _jax().profiler.device_memory_profile(backend)
