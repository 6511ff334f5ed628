"""The cells' own limits against readings taken on the chip at the
cells' own sizes (`data/chip_readings.json`; PERF.md gives their
origin): every sound window keeps to every limit, and every control, the
program's own `--kv-dtype int8` path and the reference computed in int8,
comes out not correct through the run's own `decide`."""

import json
import os

import pytest

from benchmark import cells, run

DATA = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(DATA, "chip_readings.json")) as f:
    BY_CONFIG = json.load(f)["configs"]
MANIFEST = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
# One model, one set of lengths: a cell is held to every reading of its
# configuration, whichever cell's load it was read at.
READINGS = {w["name"]: BY_CONFIG[w["config"]] for w in MANIFEST["workloads"]}


def limits(cell):
    return cells.load_cell(cell).traffic["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_readings_behind_its_limits(cell):
    got = READINGS[cell]
    assert len(got["sound"]) >= 3
    assert any(r["at"] == cell for r in got["sound"] + got["kv_int8"])
    assert len(got["kv_int8"]) >= 3 and len(got["reference_int8"]) >= 3
    assert set(limits(cell)) - {"requests_to_a_pass"} <= set(run.LIMITED)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_windows_come_out_correct(cell):
    for read in READINGS[cell]["sound"]:
        checks, correct = run.decide(read, limits(cell))
        assert correct, (read, checks)


@pytest.mark.parametrize("control", ["kv_int8", "reference_int8"])
@pytest.mark.parametrize("cell", CELLS)
def test_controls_come_out_not_correct(cell, control):
    for read in READINGS[cell][control]:
        checks, correct = run.decide(read, limits(cell))
        assert not correct, (read, checks)


def test_decide_holds_each_number_to_its_own_limit():
    lim = {"requests_to_a_pass": 4, "logit_gap_max": 0.1}
    read = {"logit_gap_max": 0.1, "logit_gap_mean": 9.0,
            "not_best_share": 0.5, "tokens_compared": 7}
    checks, correct = run.decide(read, lim)
    assert correct and checks == {
        "logit_gap_max": {"value": 0.1, "limit": 0.1}}
    assert not run.decide(dict(read, logit_gap_max=0.1001), lim)[1]
    assert not run.decide(read, dict(lim, logit_gap_mean=8.9))[1]
