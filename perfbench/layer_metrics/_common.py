"""What several readers share.  A reader is ``read(ctx) -> float | None``
in a file named after its metric; ``None`` (nothing to read) leaves the
metric out of the line.  ``ctx`` holds:

* ``cell``   — the cell as ``manifest.cell`` gives it,
* ``facts``  — what the driver counted and timed in this run,
* ``peaks``  — this device's row of ``peaks.json``,
* ``device`` — the ``device`` object of the result line,
* ``slices`` — for each profiler slice of the run, by label,
  ``{"reduced": <trace_reduce dict>, "window": (start_ns, end_ns)}``.
"""

from __future__ import annotations

import statistics

from perfbench import trace_reduce

def is_flash(name: str) -> bool:
    """A Mosaic kernel's device event (``trace_reduce.short_name`` marks
    them by their call target).  The program gives its Pallas kernels no
    ``name=`` yet (PERF.md, Open questions); in a train step of this
    model the flash kernels (forward, dq, dk/dv) are the only ones."""
    return trace_reduce.KERNEL_MARK in name


def slice_of(ctx: dict, label: str):
    return ctx.get("slices", {}).get(label)


def median_us(values_ns):
    return statistics.median(values_ns) / 1e3 if values_ns else None


def busy_ns(sl: dict) -> float:
    return trace_reduce.busy_ns(sl["reduced"], sl["window"])


def idle_share(slices) -> float:
    """1 - busy / window over ``slices``, %; None without a window."""
    slices = [sl for sl in slices if sl is not None]
    window = sum(sl["window"][1] - sl["window"][0] for sl in slices)
    if window <= 0:
        return None
    return 100.0 * (1.0 - sum(busy_ns(sl) for sl in slices) / window)
