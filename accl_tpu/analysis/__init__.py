"""acclint: the project-invariant static analyzer + lock-order detector.

Usage::

    python -m accl_tpu.analysis            # analyze the package, report
    python -m accl_tpu.analysis --check    # quiet gate mode (CI)
    python -m accl_tpu.analysis --json     # machine-readable findings

    from accl_tpu.analysis import run_checks
    findings = [f for f in run_checks() if not f.suppressed]

Checks (each individually suppressible with
``# acclint: allow[<check>] <reason>``):

========================  ==================================================
unbounded-wait            blocking acquire/wait/join/get without a timeout
jax-free-module           overlap/telemetry/faults/plans/constants/
                          contract/monitor must import without jax/numpy
                          at module scope
timer-discipline          no time.time() windows; use utils.timing
spmd-uniformity           @spmd_uniform functions must not branch on
                          process-local state
collective-sequence       collective op choice / count / root / tag must
                          not derive from rank-varying values (the static
                          half of the contract plane; also covers
                          tests/shared_scenarios.py)
thread-naming             threading.Thread(...) under accl_tpu must pass
                          name="accl-..." (the conftest excepthook guard
                          keys on the prefix)
metric-naming             registry metric names (.inc / gauge) must
                          carry the accl_ prefix (the scrape endpoint
                          exposes them verbatim)
drain-before-config       config writes / soft_reset reach a drain call
error-context             raised ACCLError carries structured details
========================  ==================================================

The dynamic lock-order registry (``accl_tpu.analysis.lockorder``) is
the runtime companion: ``ACCL_LOCKCHECK=1`` wraps project locks and
fails the test session on lock-order cycles or unreviewed edges vs the
committed ``tests/lock_hierarchy.json``.

Zero dependencies beyond the stdlib; importing this package must never
pull jax/numpy (it runs in CI shells and jax-free rank processes).
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from .astchecks import PER_FILE_CHECKS as _AST_CHECKS
from .base import Finding, iter_source_files, load_source, package_root
from .graph import CROSS_FILE_CHECKS
from .markers import spmd_uniform  # noqa: F401  (re-export)
from .spmdseq import check_collective_sequence, extra_scope

#: per-file checks: the astchecks set plus the SPMD sequence analysis
#: (accl_tpu.analysis.spmdseq — the static half of the contract plane)
PER_FILE_CHECKS = dict(
    _AST_CHECKS, **{"collective-sequence": check_collective_sequence}
)

__all__ = [
    "Finding",
    "CHECKS",
    "run_checks",
    "spmd_uniform",
    "package_root",
]

#: every named check, in report order
CHECKS = tuple(PER_FILE_CHECKS) + tuple(CROSS_FILE_CHECKS)


def run_checks(
    paths: Optional[Iterable[str]] = None,
    checks: Optional[Iterable[str]] = None,
) -> List[Finding]:
    """Run the named ``checks`` (default: all) over ``paths`` (default:
    the accl_tpu package).  Returns EVERY finding, suppressed ones
    included — gate callers filter on ``not f.suppressed``."""
    selected = set(checks) if checks is not None else set(CHECKS)
    unknown = selected - set(CHECKS)
    if unknown:
        raise ValueError(
            f"unknown checks: {sorted(unknown)} (known: {sorted(CHECKS)})"
        )
    findings: List[Finding] = []

    def _load(path):
        """Parse one file, appending its parse / suppression-syntax
        findings; returns the SourceFile or None (shared by the main
        scope and the extra-scope loops)."""
        src, parse_finding = load_source(path)
        if parse_finding is not None:
            findings.append(parse_finding)
            return None
        for line in src.bad_suppressions:
            findings.append(Finding(
                check="suppression-syntax", path=src.path, line=line,
                message="acclint suppression without a reason does not "
                        "apply; write '# acclint: allow[check] <why>'",
            ))
        return src

    sources = []
    for path in iter_source_files(paths):
        src = _load(path)
        if src is not None:
            sources.append(src)
    for name, fn in PER_FILE_CHECKS.items():
        if name not in selected:
            continue
        for src in sources:
            findings.extend(fn(src))
    if paths is None and "collective-sequence" in selected:
        # the sequence contract also covers the shared scenario library
        # outside the package (tests/shared_scenarios.py): only the
        # collective-sequence check applies there (the tests' own style
        # is not the package's), plus suppression-syntax — a reasonless
        # allow[] must be flagged wherever suppressions are honored
        for path in extra_scope():
            src = _load(path)
            if src is not None:
                findings.extend(check_collective_sequence(src))
    for name, fn in CROSS_FILE_CHECKS.items():
        if name not in selected:
            continue
        findings.extend(fn(sources))
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return findings
