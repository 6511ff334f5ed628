"""BENCHMARK.json against the contract's letter: every file it names
exists, every name and unit uses only the allowed characters."""

import json
import os
import re

import pytest

from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(cells.ROOT, path))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(len(manifest["workloads"]) // 4, 1)


def test_names_units_and_files(manifest):
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        body = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert isinstance(body["serve_argv"], list)
        names.append(c["name"])
    configs = set(names)
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        cell = cells.load_cell(w["name"])
        assert cell.traffic["config"] == w["config"]
        assert cell.config["chips"] == w["chips"]
        assert set(cell.traffic["correct"]) >= {"requests_to_a_pass",
                                                "logit_gap_max"}
        names.append(w["name"])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert os.path.exists(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".py"))
        assert callable(cells.load_reader(m["name"]).read)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))


def test_files_under_paths_use_only_name_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in manifest["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), cells.ROOT)
                assert ok.match(rel), rel


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        cell = cells.load_cell(w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_every_per_layer_metric_moves_a_metric_its_cells_report(manifest):
    for w in manifest["workloads"]:
        cell = cells.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"], m["moves"])
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in manifest["workloads"]}
