"""The documents name files that are there.

A case a document.  A word of it is held to the checkout when it is
path-shaped (ends in ``.py``, ``.json``, ``.md``, ``.csv`` or ``.yml``;
a ``:line`` or ``::test`` suffix dropped) and stands back-quoted or on a
``python ...`` line of a fenced block, and

* it has a directory part, whatever its first component: it is a file
  of the checkout from the root (``tests/test_wire.py``) or the tail of
  one (package-relative names such as ``ops/kda.py``), or
* it is the bare script a ``python`` command runs (``python
  chip_smoke.py``): it is a file at the root, where the command says it
  is run from.

So a document that still sends a reader into a directory or to a script
that was deleted fails here by name, whichever it was.  Not held:
absolute paths, placeholders in angle brackets (``<your_job.py>``),
other bare names (a ``merged.json`` a command writes), and upstream's
sources, none of which has one of these endings.  ``PERF.md``,
``ROADMAP.md``, ``CHANGES.md`` and ``ADVICE.md`` are records: they may
name what is gone.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (
    ["README.md", "PARITY.md"]
    + sorted(
        os.path.join("docs", f)
        for f in os.listdir(os.path.join(ROOT, "docs")) if f.endswith(".md")
    )
    + [os.path.join(".claude", "skills", "verify", "SKILL.md")]
)
PATH = re.compile(
    r"^(?P<path>[\w.\-/]+\.(?:py|json|md|csv|yml))"
    r"(?::\d+(?:-\d+)?)?(?:::[\w\[\]\-.]+)*$"
)
PYTHON = re.compile(r"python3?")


def _checkout_files():
    """Every file of the checkout as ``/<path from the root>``, without
    what running leaves behind (dot-directories other than ``.claude``
    and ``.github``, ``chiprun_out``, ``__pycache__``)."""
    files = []
    for where, dirs, names in os.walk(ROOT):
        dirs[:] = [
            d for d in dirs
            if d in (".claude", ".github")
            or not (d.startswith(".") or d in ("chiprun_out", "__pycache__"))
        ]
        rel = os.path.relpath(where, ROOT)
        files += [
            "/" + (n if rel == "." else os.path.join(rel, n)) for n in names
        ]
    return files


def _commands(text):
    """The word lists that may name paths: every back-quoted span (one
    may run over a line's end), and every line of a fenced block that
    runs ``python``."""
    prose, fenced = [], False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif not fenced:
            prose.append(line)
        elif any(PYTHON.fullmatch(w) for w in line.split()):
            yield line.split()
    for span in re.findall(r"`+([^`]+)`+", "\n".join(prose)):
        yield span.split()


def _held(text):
    """The paths of ``text`` that are held to the checkout, as
    ``(path, from_the_root_only)``."""
    held = set()
    for words in _commands(text):
        for before, word in zip([""] + words, words):
            m = PATH.match(word.strip("()[],;:'\""))
            if not m or m.group("path").startswith("/"):
                continue
            path = m.group("path")
            if "/" in path:
                held.add((path, False))
            elif PYTHON.fullmatch(before):
                held.add((path, True))
    return sorted(held)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        held = _held(f.read())
    if not held:
        pytest.skip(f"{document} names no path")
    files = _checkout_files()
    missing = [
        path for path, root_only in held
        if not os.path.exists(os.path.join(ROOT, path))
        and (root_only or not any(f.endswith("/" + path) for f in files))
    ]
    assert not missing, f"{document} names files that are not there: {missing}"
