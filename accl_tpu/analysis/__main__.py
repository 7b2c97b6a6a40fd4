"""acclint CLI: ``python -m accl_tpu.analysis``.

Exit status: 0 when no unsuppressed findings, 1 otherwise, 2 on usage
errors — so it slots straight into shell gates (CI).
"""

from __future__ import annotations

import argparse
import json
import sys

from . import CHECKS, run_checks


def to_sarif(findings) -> dict:
    """Findings as a SARIF 2.1.0 document (the format GitHub's
    upload-sarif action renders as inline diff annotations).  Paths are
    emitted repo-relative when they sit under the working directory —
    the URI form code-scanning matches against the checkout."""
    import os

    cwd = os.getcwd()

    def uri(path: str) -> str:
        ap = os.path.abspath(path)
        if ap.startswith(cwd + os.sep):
            return os.path.relpath(ap, cwd).replace(os.sep, "/")
        return path.replace(os.sep, "/")

    results = []
    for f in findings:
        result = {
            "ruleId": f.check,
            "level": "note" if f.suppressed else "error",
            "message": {"text": f.message},
            "locations": [{
                "physicalLocation": {
                    "artifactLocation": {"uri": uri(f.path)},
                    "region": {"startLine": max(1, int(f.line))},
                },
            }],
        }
        if f.suppressed:
            result["suppressions"] = [{
                "kind": "inSource",
                "justification": f.suppress_reason,
            }]
        results.append(result)
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {
                "driver": {
                    "name": "acclint",
                    "informationUri":
                        "https://github.com/accl-tpu/accl_tpu",
                    "rules": [
                        {
                            "id": c,
                            "shortDescription": {"text": c},
                            "defaultConfiguration": {"level": "error"},
                        }
                        for c in sorted({"parse", "suppression-syntax",
                                         *CHECKS})
                    ],
                },
            },
            "results": results,
        }],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m accl_tpu.analysis",
        description="acclint: project-invariant static analyzer",
    )
    p.add_argument(
        "paths", nargs="*",
        help="files/directories to analyze (default: the accl_tpu package)",
    )
    p.add_argument(
        "--check", action="store_true",
        help="quiet gate mode: one line per unsuppressed finding + summary",
    )
    p.add_argument(
        "--checks", metavar="A,B",
        help=f"comma-separated subset of: {', '.join(CHECKS)}",
    )
    p.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit findings as a JSON array (suppressed included)",
    )
    p.add_argument(
        "--sarif", action="store_true", dest="as_sarif",
        help="emit findings as SARIF 2.1.0 (CI diff annotation via "
             "github/codeql-action/upload-sarif)",
    )
    p.add_argument(
        "--show-suppressed", action="store_true",
        help="also print suppressed findings with their reasons",
    )
    p.add_argument(
        "--list", action="store_true", dest="list_checks",
        help="list check names and exit",
    )
    args = p.parse_args(argv)

    if args.list_checks:
        for c in CHECKS:
            print(c)
        return 0

    checks = None
    if args.checks:
        checks = [c.strip() for c in args.checks.split(",") if c.strip()]
    try:
        findings = run_checks(args.paths or None, checks)
    except ValueError as e:
        print(f"acclint: {e}", file=sys.stderr)
        return 2

    if args.as_sarif:
        print(json.dumps(to_sarif(findings), indent=1))
        return 1 if any(not f.suppressed for f in findings) else 0

    if args.as_json:
        print(json.dumps([f.as_dict() for f in findings], indent=1))
        return 1 if any(not f.suppressed for f in findings) else 0

    live = [f for f in findings if not f.suppressed]
    shown = findings if args.show_suppressed else live
    for f in shown:
        print(f.render())
        if f.suppressed and args.show_suppressed:
            print(f"    reason: {f.suppress_reason}")
    nsupp = sum(1 for f in findings if f.suppressed)
    if not args.check or live:
        print(
            f"acclint: {len(live)} finding(s), {nsupp} suppressed, "
            f"{len(CHECKS)} checks",
            file=sys.stderr,
        )
    return 1 if live else 0


if __name__ == "__main__":
    sys.exit(main())
