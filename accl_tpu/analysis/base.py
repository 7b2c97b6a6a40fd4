"""acclint core model: findings, suppressions, and the check registry.

The analyzer encodes the project's concurrency/architecture invariants
as named, individually-suppressible checks (the list lives in
``accl_tpu.analysis.CHECKS``).  Everything here is stdlib-only — the
analyzer must be runnable from CI shells and jax-free processes, and
fast enough to gate every CI run.

Suppression syntax (audited-safe sites)::

    something.wait()  # acclint: allow[unbounded-wait] watchdog bounds this

A suppression names the check it silences in square brackets and MUST
carry a non-empty reason — a bare ``allow[check]`` does not apply (the
reviewed justification is the point of the syntax).  It applies to the
line it sits on, or, when written on its own line, to the line directly
below it.  Several checks can share one comment:
``allow[unbounded-wait,timer-discipline] reason``.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Finding",
    "SourceFile",
    "package_root",
    "iter_source_files",
    "load_source",
]

#: ``# acclint: allow[check-a,check-b] reason...``
_SUPPRESS_RE = re.compile(
    r"#\s*acclint:\s*allow\[([A-Za-z0-9_,\- ]+)\]\s*(.*)"
)


@dataclasses.dataclass
class Finding:
    """One rule violation at one source location."""

    check: str
    path: str  # path as given to the analyzer (repo-relative in CI)
    line: int
    message: str
    suppressed: bool = False
    suppress_reason: str = ""

    def render(self) -> str:
        tag = " [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}: {self.check}: {self.message}{tag}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class SourceFile:
    """One parsed module: AST + per-line suppression table.

    ``suppressions`` maps line number -> {check-name: reason} covering
    both same-line comments and own-line comments (which bind to the
    next line).  A malformed suppression (no reason) is recorded in
    ``bad_suppressions`` so the analyzer can surface it instead of
    silently granting or ignoring it.
    """

    def __init__(self, path: str, text: str):
        self.path = path
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.lines = text.splitlines()
        # one flattened walk, shared by every per-file check (walking
        # the tree once per check made the analyzer seconds-slow)
        self.nodes = list(ast.walk(self.tree))
        self.suppressions: Dict[int, Dict[str, str]] = {}
        self.bad_suppressions: List[int] = []
        if "acclint" in text:  # comment scan only where it can matter
            self._scan_comments()

    def _scan_comments(self) -> None:
        import io

        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(self.text).readline
            ))
        except tokenize.TokenError:  # pragma: no cover - parse succeeded
            tokens = []
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            checks = [c.strip() for c in m.group(1).split(",") if c.strip()]
            reason = m.group(2).strip()
            line = tok.start[0]
            if not reason:
                self.bad_suppressions.append(line)
                continue
            # an own-line comment binds to the next CODE line too
            # (skipping the rest of its own comment block)
            targets = [line]
            stripped = (
                self.lines[line - 1].strip() if line <= len(self.lines) else ""
            )
            if stripped.startswith("#"):
                nxt = line + 1
                while nxt <= len(self.lines) and (
                    not self.lines[nxt - 1].strip()
                    or self.lines[nxt - 1].strip().startswith("#")
                ):
                    nxt += 1
                targets.append(nxt)
            for t in targets:
                slot = self.suppressions.setdefault(t, {})
                for c in checks:
                    slot[c] = reason

    def suppression_for(self, check: str, line: int) -> Optional[str]:
        slot = self.suppressions.get(line)
        if slot is None:
            return None
        return slot.get(check)

    def finding(self, check: str, node_or_line, message: str) -> Finding:
        line = getattr(node_or_line, "lineno", node_or_line)
        reason = self.suppression_for(check, line)
        return Finding(
            check=check,
            path=self.path,
            line=line,
            message=message,
            suppressed=reason is not None,
            suppress_reason=reason or "",
        )


def package_root() -> str:
    """The accl_tpu package directory (the default analysis scope)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def iter_source_files(paths: Optional[Iterable[str]] = None) -> List[str]:
    """Every ``.py`` file under ``paths`` (default: the package),
    sorted for deterministic output.  Explicit file paths pass through."""
    roots = list(paths) if paths else [package_root()]
    out: List[str] = []
    for root in roots:
        if os.path.isfile(root):
            out.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return sorted(out)


def load_source(path: str) -> Tuple[Optional[SourceFile], Optional[Finding]]:
    """Parse one file; a syntax error is itself a finding (the analyzer
    must not silently skip what it cannot read)."""
    try:
        with open(path, encoding="utf-8") as f:
            text = f.read()
        return SourceFile(path, text), None
    except (OSError, SyntaxError, ValueError) as e:
        return None, Finding(
            check="parse",
            path=path,
            line=getattr(e, "lineno", None) or 1,
            message=f"cannot analyze: {e}",
        )
