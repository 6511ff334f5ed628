"""bench.py tuned-config resolution: the sweep→ladder handoff contract.

The ladder must apply a sweep-written ``perf/MEGA_TUNED.json`` only
when it matches this chip AND model, must honor an explicit env
override, and must REFUSE (loudly) a malformed override rather than
silently timing defaults."""

import importlib.util
import json
import os

import pytest


@pytest.fixture
def bench(tmp_path, monkeypatch):
    """Load a COPY of bench.py from tmp_path so the tests' tuning file
    lives under tmp_path/perf/ — never the repo's real
    perf/MEGA_TUNED.json, which a chip sweep may have written (and
    which pre-existing state would also break these tests)."""
    import shutil

    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")
    dst = tmp_path / "bench.py"
    shutil.copy(src, dst)
    (tmp_path / "perf").mkdir()
    spec = importlib.util.spec_from_file_location("bench_under_test", dst)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.delenv("TDT_BENCH_MEGA_CFG", raising=False)
    return mod


@pytest.fixture
def tuned_file(bench):
    path = os.path.join(
        os.path.dirname(os.path.abspath(bench.__file__)),
        "perf", "MEGA_TUNED.json",
    )

    def write(rec):
        with open(path, "w") as f:
            json.dump(rec, f)
        return path

    return write


def test_no_file_means_defaults(bench, tuned_file):
    cfg, note = bench._tuned_mega_config("TPU v5 lite", "Qwen/Qwen3-0.6B")
    assert cfg is None and "no tuning" in note


def test_matching_file_applies(bench, tuned_file):
    tuned_file({"config": "2048:1024:4", "device": "TPU v5 lite",
                "model": "Qwen/Qwen3-0.6B"})
    cfg, note = bench._tuned_mega_config("TPU v5 lite", "Qwen/Qwen3-0.6B")
    assert cfg.tile_n == 2048 and cfg.tile_k == 1024 and cfg.nbuf == 4
    assert "MEGA_TUNED" in note


@pytest.mark.parametrize("device,model", [
    ("TPU v4", "Qwen/Qwen3-0.6B"),          # other chip
    ("TPU v5 lite", "Qwen/Qwen3-4B"),         # other model
])
def test_mismatched_file_ignored(bench, tuned_file, device, model):
    tuned_file({"config": "2048:1024:4", "device": "TPU v5 lite",
                "model": "Qwen/Qwen3-0.6B"})
    cfg, note = bench._tuned_mega_config(device, model)
    assert cfg is None and "defaults" in note


def test_env_override_wins(bench, tuned_file, monkeypatch):
    tuned_file({"config": "2048:1024:4", "device": "TPU v5 lite",
                "model": "m"})
    monkeypatch.setenv("TDT_BENCH_MEGA_CFG", "1024:1024:3")
    cfg, note = bench._tuned_mega_config("TPU v5 lite", "m")
    assert cfg.nbuf == 3 and "env" in note


def test_malformed_env_raises(bench, monkeypatch):
    monkeypatch.setenv("TDT_BENCH_MEGA_CFG", "2048:2048")
    with pytest.raises(ValueError, match="malformed"):
        bench._tuned_mega_config("TPU v5 lite", "m")


def test_malformed_file_ignored(bench, tuned_file):
    tuned_file({"config": "not-a-config", "device": "TPU v5 lite",
                "model": "m"})
    cfg, note = bench._tuned_mega_config("TPU v5 lite", "m")
    assert cfg is None and "malformed" in note
