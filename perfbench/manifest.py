"""BENCHMARK.json, and the data files its names point at.

Everything a cell needs is found BY NAME: a configuration's ``file`` is
given in the manifest, a traffic mix ``t`` is ``workloads/t.json``, the
mix names its driver (``drivers/<driver>.py``) and a per-layer metric
``m`` is read by ``layer_metrics/m.py``.  A later PR adds a cell or a
metric by adding files and manifest entries; nothing here names one.

The loader refuses what the driver refuses (names, units, lengths), so
a bad manifest fails here, on the CPU, before any chip time is spent.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
#: the checkout: the directory that holds BENCHMARK.json and perfbench/
CHECKOUT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
TOP_KEYS = {
    "command", "paths", "run_seconds", "configs", "workloads",
    "end_to_end", "per_layer",
}


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is outside the contract."""


def check_name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(
            f"{what} {value!r}: a name is 1-64 of letters, digits, '_', "
            "'.', '-' and starts with a letter, a digit or '_'"
        )
    return value


def check_unit(value, what: str) -> str:
    if not isinstance(value, str) or not UNIT_RE.match(value):
        raise ManifestError(
            f"{what} unit {value!r}: 1-16 of letters, digits, '_', '/', "
            "'%', '.', '-' (ASCII: 'us', not the Greek letter)"
        )
    return value


def _line(value, what: str) -> str:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value):
        raise ManifestError(f"{what}: 1-200 characters on one line, no tab")
    return value


def _keys(entry: dict, need: set, may: set, what: str) -> None:
    got = set(entry)
    if not need <= got or not got <= need | may:
        raise ManifestError(
            f"{what}: keys {sorted(got)}, wanted {sorted(need)}"
            + (f" and optionally {sorted(may)}" if may else "")
        )


def _unique(names, what: str) -> None:
    seen = set()
    for n in names:
        if n in seen:
            raise ManifestError(f"{what} {n!r} appears twice")
        seen.add(n)


def _under_paths(path: str, paths) -> bool:
    norm = os.path.normpath(path)
    return not os.path.isabs(path) and any(
        norm == os.path.normpath(p)
        or norm.startswith(os.path.normpath(p) + os.sep)
        for p in paths
    )


def _check_metric(m: dict, cells: set, e2e: bool, e2e_names: set) -> None:
    what = f"metric {m.get('name')!r}"
    need = {"name", "unit", "better", "source"}
    need |= {"bound"} if e2e else {"layer", "moves"}
    _keys(m, need, {"workloads"}, what)
    check_name(m["name"], "metric")
    check_unit(m["unit"], what)
    if m["better"] not in ("lower", "higher"):
        raise ManifestError(f"{what}: better is 'lower' or 'higher'")
    if m["source"] not in (E2E_SOURCES if e2e else SOURCES):
        raise ManifestError(f"{what}: source {m['source']!r} not allowed")
    if e2e:
        if not 0.01 <= float(m["bound"]) <= 0.1:
            raise ManifestError(f"{what}: bound outside 0.01..0.1")
    else:
        _line(m["layer"], f"{what} layer")
        if m["moves"] not in e2e_names:
            raise ManifestError(f"{what}: moves unknown {m['moves']!r}")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise ManifestError(f"{what}: unknown cell {w!r}")


def validate(doc: dict) -> dict:
    """Raise :class:`ManifestError` where ``doc`` is outside the contract
    of the builder's instructions; return it otherwise."""
    if set(doc) != TOP_KEYS:
        raise ManifestError(
            f"BENCHMARK.json keys {sorted(doc)}, wanted {sorted(TOP_KEYS)}"
        )
    paths = doc["paths"]
    if not 1 <= len(paths) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for p in paths:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
                or p.startswith("/") or ".." in p.split("/")):
            raise ManifestError(f"path {p!r} outside the contract")
    cmd = doc["command"]
    if not 1 <= len(cmd) <= 32:
        raise ManifestError("command: 1 to 32 words")
    for w in cmd:
        _line(w, "command word")
        if w.startswith("/") or ".." in w.split("/"):
            raise ManifestError(f"command word {w!r} leaves the repo")
    rs = doc["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= 51:
        raise ManifestError("run_seconds: a whole number from 1 to 51")

    if not 1 <= len(doc["configs"]) <= 24:
        raise ManifestError("configs: 1 to 24")
    files = []
    for c in doc["configs"]:
        _keys(c, {"name", "source", "file", "reduced", "why"}, set(),
              f"config {c.get('name')!r}")
        check_name(c["name"], "config")
        _line(c["source"], "config source")
        _line(c["why"], "config why")
        if not _under_paths(c["file"], paths):
            raise ManifestError(f"config file {c['file']!r} not under paths")
        if len(c["reduced"]) > 16:
            raise ManifestError("reduced: at most 16 keys")
        for k in c["reduced"]:
            check_name(k, "reduced key")
        files.append(c["file"])
    _unique([c["name"] for c in doc["configs"]], "config")
    _unique(files, "config file")
    config_names = {c["name"] for c in doc["configs"]}

    if not 2 <= len(doc["workloads"]) <= 24:
        raise ManifestError("workloads: 2 to 24 cells")
    for w in doc["workloads"]:
        _keys(w, {"name", "config", "traffic", "chips", "why"}, set(),
              f"cell {w.get('name')!r}")
        check_name(w["name"], "cell")
        check_name(w["traffic"], "traffic")
        if w["config"] not in config_names:
            raise ManifestError(f"cell {w['name']!r}: unknown config")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"cell {w['name']!r}: chips is 1 or 4")
        _line(w["why"], "cell why")
    _unique([w["name"] for w in doc["workloads"]], "cell")
    _unique([(w["config"], w["traffic"]) for w in doc["workloads"]],
            "config/traffic pair")
    used = {w["config"] for w in doc["workloads"]}
    if used != config_names:
        raise ManifestError(f"configs without a cell: {config_names - used}")
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    if four > max(1, len(doc["workloads"]) // 4):
        raise ManifestError("more than 25% of the cells ask for 4 chips")

    cells = {w["name"] for w in doc["workloads"]}
    if not 1 <= len(doc["end_to_end"]) <= 16:
        raise ManifestError("end_to_end: 1 to 16 metrics")
    if not 1 <= len(doc["per_layer"]) <= 128:
        raise ManifestError("per_layer: 1 to 128 metrics")
    e2e_names = {m.get("name") for m in doc["end_to_end"]}
    if "setup_s" not in e2e_names:
        raise ManifestError("end_to_end has no setup_s")
    for m in doc["end_to_end"]:
        _check_metric(m, cells, True, e2e_names)
    for m in doc["per_layer"]:
        _check_metric(m, cells, False, e2e_names)
    _unique([m["name"] for m in doc["end_to_end"] + doc["per_layer"]],
            "metric")
    for cell in cells:
        e2e = [m for m in doc["end_to_end"] if applies(m, cell)]
        if len(e2e) < 2 or not any(m["name"] == "setup_s" for m in e2e):
            raise ManifestError(
                f"cell {cell!r}: needs setup_s and one more end-to-end metric"
            )
        moved = {m["name"] for m in e2e}
        layer = [m for m in doc["per_layer"] if applies(m, cell)]
        if not layer:
            raise ManifestError(f"cell {cell!r}: no per-layer metric")
        for m in layer:
            if m["moves"] not in moved:
                raise ManifestError(
                    f"{m['name']!r} is reported in {cell!r}, where the "
                    f"metric it moves, {m['moves']!r}, is not"
                )
    return doc


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(checkout: str = CHECKOUT) -> dict:
    path = os.path.join(checkout, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        raise ManifestError("BENCHMARK.json is over 64 KiB")
    return validate(_read_json(path))


def cell(doc: dict, name: str, rehearse: bool = False) -> dict:
    """Everything one cell runs from: its manifest entry, its
    configuration file, its traffic file and the metrics reported in it.
    In a rehearsal each file's ``rehearsal`` block overrides the sizes."""
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    if entry is None:
        raise ManifestError(
            f"no cell {name!r}; have {[w['name'] for w in doc['workloads']]}"
        )
    cfg_entry = next(c for c in doc["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(CHECKOUT, cfg_entry["file"]))
    traffic = _read_json(
        os.path.join(HERE, "workloads", entry["traffic"] + ".json")
    )
    if rehearse:
        config = {**config, **config.get("rehearsal", {})}
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    return {
        "name": name,
        "chips": entry["chips"],
        "config_name": entry["config"],
        "config": config,
        "traffic_name": entry["traffic"],
        "traffic": traffic,
        "end_to_end": [m for m in doc["end_to_end"] if applies(m, name)],
        "per_layer": [m for m in doc["per_layer"] if applies(m, name)],
    }
