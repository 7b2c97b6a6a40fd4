"""The manifest loader refuses what the driver refuses."""

import copy
import importlib
import os

import pytest

from perfbench import manifest


@pytest.fixture(scope="module")
def doc():
    return manifest.load()


def test_the_real_manifest_loads_and_every_name_resolves(doc):
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    for w in doc["workloads"]:
        cell = manifest.cell(doc, w["name"])
        importlib.import_module(
            "perfbench.drivers." + cell["traffic"]["driver"]
        )
        for m in cell["per_layer"]:
            mod = importlib.import_module("perfbench.layer_metrics." + m["name"])
            assert callable(mod.read)
        rehearsal = manifest.cell(doc, w["name"], rehearse=True)
        assert rehearsal["traffic"] != cell["traffic"]


def test_every_file_under_paths_is_named_from_a_names_characters(doc):
    for root in doc["paths"]:
        for base, dirs, files in os.walk(os.path.join(manifest.CHECKOUT, root)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".pyc"):
                    continue
                assert manifest.re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def _with(doc, path, value):
    d = copy.deepcopy(doc)
    node = d
    for key in path[:-1]:
        node = node[key]
    node[path[-1]] = value
    return d


@pytest.mark.parametrize("path,value", [
    (("workloads", 0, "name"), "coll w4"),          # a space
    (("workloads", 0, "name"), "coll,w4"),          # a comma
    (("workloads", 0, "name"), "coll/w4"),          # a slash
    (("workloads", 0, "name"), "-coll"),            # starts with '-'
    (("workloads", 0, "name"), "x" * 65),           # too long
    (("workloads", 0, "traffic"), "sw eep"),
    (("end_to_end", 0, "unit"), "tokens per s"),    # a space in a unit
    (("end_to_end", 1, "unit"), "µs"),         # the Greek letter
    (("end_to_end", 0, "unit"), "x" * 17),
    (("end_to_end", 0, "better"), "faster"),
    (("end_to_end", 0, "bound"), 0.2),
    (("end_to_end", 0, "source"), "program_counter"),
    (("per_layer", 0, "moves"), "no_such_metric"),
    (("per_layer", 0, "why"), "a key the contract does not have"),
    (("workloads", 1, "chips"), 4),                 # a second 4-chip cell
    (("workloads", 1, "chips"), 2),
    (("configs", 0, "file"), "bench.py"),           # outside paths
    (("command",), ["python3", "/usr/bin/x"]),
    (("run_seconds",), 52),
    (("workloads", 0, "why"), "two\nlines"),
])
def test_the_loader_refuses(doc, path, value):
    with pytest.raises(manifest.ManifestError):
        manifest.validate(_with(doc, path, value))


def test_a_layer_metric_is_reported_only_where_the_metric_it_moves_is(doc):
    bad = copy.deepcopy(doc)
    for m in bad["per_layer"]:
        if m["name"] == "model_mfu":
            m["workloads"] = ["coll_w4_sweep"]
    with pytest.raises(manifest.ManifestError):
        manifest.validate(bad)


def test_an_unknown_cell_is_refused(doc):
    with pytest.raises(manifest.ManifestError):
        manifest.cell(doc, "no_such_cell")
