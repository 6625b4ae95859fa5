"""BENCHMARK.json against the files it names, and the rules a later PR
relies on: everything a cell, a configuration, a traffic kind or a layer
metric needs is a file found by name, and run.py names none of them."""

import json
import os
import re

import pytest

from benchmarks import run

ROOT = run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}


def _cells_of(metric):
    return set(metric.get("workloads", CELLS))


def test_command_and_paths():
    """The contract's own limits (which keys, which sources, which bounds)
    are the driver's to hold; here only what ties the manifest to this
    directory."""
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert {"benchmarks", "tests/benchmark"} <= set(MANIFEST["paths"])
    assert "setup_s" in END_TO_END


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_matches_its_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"] == "benchmarks/configs/%s.json" % entry["name"]
    config = run.load_json("configs", entry["name"])
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert 1 <= len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    assert config["assumed"]
    assert os.path.exists(os.path.join(
        run.HERE, "families", config["family"] + ".py"))
    assert os.path.exists(os.path.join(
        run.HERE, "optimizers", config["optimizer"] + ".py"))
    assert any(w["config"] == entry["name"] for w in CELLS.values())


@pytest.mark.parametrize("entry", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry_matches_its_file(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(entry[key])
    cell = run.load_json("workloads", entry["name"])
    for key in ("name", "config", "traffic", "chips", "why"):
        assert cell[key] == entry[key]
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert entry["chips"] in (1, 4)
    assert os.path.exists(os.path.join(
        run.HERE, "traffic", entry["traffic"] + ".py"))
    # the cell reports exactly the metrics the manifest gives it
    assert set(cell["end_to_end"]) == {
        n for n, m in END_TO_END.items() if entry["name"] in _cells_of(m)}
    assert set(cell["per_layer"]) == {
        n for n, m in PER_LAYER.items() if entry["name"] in _cells_of(m)}
    assert "setup_s" in cell["end_to_end"] and len(cell["end_to_end"]) > 1
    assert cell["per_layer"]


@pytest.mark.parametrize("entry", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_entry_matches_its_reader(entry):
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    reader = run.load_module("layer_metrics", entry["name"])
    assert reader.UNIT == entry["unit"]
    assert reader.LAYER == entry["layer"]
    assert reader.MOVES == entry["moves"]
    assert reader.SOURCE == entry["source"]
    assert callable(reader.compute)
    # the end-to-end metric it should move is reported wherever it is
    assert _cells_of(entry) <= _cells_of(END_TO_END[entry["moves"]])


def test_end_to_end_names_and_units():
    for metric in MANIFEST["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")


def test_every_file_under_paths_has_a_plain_name():
    for path in MANIFEST["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_run_py_names_no_model_mix_or_metric():
    """run.py dispatches by name: a later PR adds a configuration, a
    family, a cell, a traffic kind or a metric without editing it."""
    with open(os.path.join(run.HERE, "run.py")) as f:
        code = f.read()
    code = code[code.index('"""', 3) + 3:]        # the usage text aside
    named = set(PER_LAYER) | set(CELLS) | (set(END_TO_END) - {"setup_s"})
    for kind in ("configs", "workloads", "families", "traffic",
                 "layer_metrics", "optimizers"):
        for name in os.listdir(os.path.join(run.HERE, kind)):
            named.add(os.path.splitext(name)[0])
    named.discard("__pycache__")
    for name in sorted(named):
        assert name not in code, name
