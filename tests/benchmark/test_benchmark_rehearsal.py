"""The one command, end to end, on the CPU at the `tiny` preset through
a test-only configuration (not in BENCHMARK.json): the shape of the last
line, and no result off the TPU. (`test_benchmark_faults.py` breaks the
timed path underneath and sees `correct` come out false.)"""

import json
import os

import jax
import pytest

from benchmark import cells, peaks, run

DATA = os.path.join(os.path.dirname(__file__), "data")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def drive(monkeypatch, capfd, traffic_file, seed, seconds="4", extra=(),
          config_file="tiny.config.json"):
    """The whole of a run but the look for a chip."""
    dev = jax.devices()[0]
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())})
    monkeypatch.setitem(peaks.PEAKS, dev.device_kind,
                        peaks.Peak(1e12, 1e11, 1e10, "CPU rehearsal"))
    rc = run.main(["--workload", "tiny.rehearsal", "--seed", str(seed),
                   "--seconds", seconds, "--trace", "0",
                   "--config-file", os.path.join(DATA, config_file),
                   "--traffic-file", os.path.join(DATA, traffic_file),
                   *extra])
    out, err = capfd.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1]), lines, err


def test_open_loop_run_prints_the_contracts_last_line(monkeypatch, capfd):
    rc, last, lines, err = drive(monkeypatch, capfd, "tiny.chat.json",
                                 2**31 + 101)
    assert rc == 0
    assert KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] == 8 and last["failed"] == 0
    # The end-to-end metrics no `workloads` list keeps to the cells.
    assert set(last["metrics"]) == {"token_gap_p95_ms", "setup_s"}
    for name, m in last["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str), name
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # Each number compared stands beside its limit, in the line and as
    # the last lines of standard error.
    assert set(last["checks"]) == {"logit_gap_max", "logit_gap_mean"}
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    assert err.rstrip().splitlines()[-1].startswith("check logit_gap_mean:")
    # Nothing compiled inside the window, and the earlier lines say what
    # the window held.
    assert last["compiles_in_window"] == 0
    said = [json.loads(ln) for ln in lines[:-1] if ln.startswith("{")]
    phases = [ln.get("phase") for ln in said]
    assert phases == ["traffic", "setup", "window", "requests", "reference"]
    rows = said[3]["rows"]
    assert len(rows) == 8 and all(r[2] > 0 and r[3] > r[1] for r in rows)


def test_closed_loop_run(monkeypatch, capfd):
    rc, last, lines, _ = drive(monkeypatch, capfd, "tiny.closed.json",
                               2**31 + 102, seconds="3")
    assert rc == 0 and last["correct"] is True, last["checks"]
    assert 3 <= last["attempted"] <= 12 and last["failed"] == 0
    assert last["metrics"]["token_gap_p95_ms"]["value"] > 0


def test_a_second_architecture_runs_by_files_alone(monkeypatch, capfd):
    """The program's `tiny-moe` preset (sparse experts), served and
    judged through the same harness: its configuration under `data/`
    names its own reference and its own work counts, and nothing under
    `benchmark/` knows of it."""
    rc, last, _, _ = drive(monkeypatch, capfd, "tiny.closed.json",
                           2**31 + 106, seconds="3",
                           config_file="tiny-moe.config.json")
    assert rc == 0 and last["failed"] == 0 and last["attempted"] >= 3
    assert last["correct"] is True, last["checks"]
    assert last["compiles_in_window"] == 0
    for root, _, files in os.walk(cells.HERE):
        for f in files:
            if f.endswith((".py", ".json")):
                with open(os.path.join(root, f)) as fh:
                    text = fh.read()
                assert "tiny-moe" not in text and "tiny_moe" not in text, f


def test_the_wrong_reference_for_an_architecture_is_not_correct(
        monkeypatch, capfd, tmp_path):
    """The sparse configuration handed the dense reference: the run ends
    with an error that names the key the dense one needs; and given that
    key (as a dense model of the experts' width), `correct` is false."""
    moe = cells.load_json(os.path.join(DATA, "tiny-moe.config.json"))
    dense = cells.load_json(os.path.join(DATA, "tiny.config.json"))
    swapped = dict(moe, reference=dense["reference"])
    path = tmp_path / "swapped.config.json"
    path.write_text(json.dumps(swapped))
    with pytest.raises(KeyError, match="intermediate_size"):
        drive(monkeypatch, capfd, "tiny.closed.json", 2**31 + 107,
              seconds="3", config_file=str(path))
    capfd.readouterr()
    path.write_text(json.dumps(dict(
        swapped, intermediate_size=moe["moe_intermediate_size"])))
    rc, last, _, _ = drive(monkeypatch, capfd, "tiny.closed.json",
                           2**31 + 107, seconds="3", config_file=str(path))
    assert rc == 0 and last["failed"] == 0
    assert last["correct"] is False
    assert last["checks"]["logit_gap_max"]["value"] > 1e-2


def test_off_the_tpu_there_is_no_result(capfd):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "qwen3-4b.chat", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    out, _ = capfd.readouterr()
    assert '"correct"' not in out and '"metrics"' not in out
