"""Profiler slices of a traced run, and the benchmark's own host spans.

End-to-end numbers are taken with the profiler off (``--trace 0``): then
``span`` is a null context and ``start``/``stop`` do nothing.  With
``--trace 1`` a driver opens a short slice of steady work, and wraps its
calls into the program in ``bench::`` spans, which land in the profiler's
trace on the same clock as the device's events.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import time


class Tracer:
    def __init__(self, out_dir: str, enabled: bool):
        self.enabled = enabled
        self.out_dir = out_dir
        self.slices: list = []      # {"label", "dir"}
        self._open = None
        self.overhead_s = 0.0       # seconds spent starting and stopping
        if enabled:
            shutil.rmtree(out_dir, ignore_errors=True)

    @property
    def active(self) -> bool:
        return self._open is not None

    def start(self, label: str) -> None:
        if not self.enabled or self._open is not None:
            return
        import jax

        t0 = time.perf_counter()
        path = os.path.join(self.out_dir, label)
        # no Python call tracing: it slows exactly the host path that the
        # sweep's small calls measure (TraceAnnotations are host level 1)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=options)
        self._open = {"label": label, "dir": path, "t0": time.perf_counter()}
        self.overhead_s += self._open["t0"] - t0

    def stop(self) -> None:
        if self._open is None:
            return
        import jax

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        done, self._open = self._open, None
        self.slices.append({"label": done["label"], "dir": done["dir"]})
        self.overhead_s += time.perf_counter() - t0

    def span(self, name: str):
        """A ``bench::`` host span in the trace while a slice is open."""
        if self._open is None:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)
