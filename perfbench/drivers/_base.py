"""What every traffic driver keeps: counts of operations, the reasons a
run is not correct, and marks through set-up.  A driver is
``Driver(cell, seed, devices, rehearse)`` with ``setup()``,
``measure(seconds, tracer) -> {"metrics", "facts"[, "memory_peak_bytes"]}``
and ``close()``; ``run.py`` finds it by the name in the traffic file."""

from __future__ import annotations

import time
from typing import List


class DriverBase:
    def __init__(self, cell: dict, seed: int, devices, rehearse: bool):
        self.cell = cell
        self.config = cell["config"]
        self.traffic = cell["traffic"]
        self.seed = seed
        self.rehearse = rehearse
        self.attempted = 0
        self.failed = 0
        self.problems: List[str] = []
        self.marks: list = []   # (label, perf_counter) through set-up

    def _mark(self, label: str) -> None:
        self.marks.append((label, time.perf_counter()))

    def correct(self) -> bool:
        return not self.problems and self.failed == 0

    def close(self) -> None:
        pass
