"""Find a cell's files by the names ``BENCHMARK.json`` gives.

One file per configuration, per traffic mix and per per-layer metric;
the harness never names one in code, so a later PR adds a cell or a
metric by adding files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict       # the configuration file
    traffic: dict      # the traffic mix's parameters
    end_to_end: list   # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(workload: str, manifest_path: str | None = None,
              config_file: str | None = None,
              traffic_file: str | None = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``. ``config_file`` and
    ``traffic_file`` override the files (tests run a tiny configuration
    that the manifest does not list)."""
    manifest = load_json(manifest_path or os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == workload),
                 None)
    if entry is None and not (config_file and traffic_file):
        raise SystemExit(
            f"no workload {workload!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in manifest['workloads']]}")
    if entry is not None:
        cfg_entry = next(c for c in manifest["configs"]
                         if c["name"] == entry["config"])
        config_file = config_file or os.path.join(ROOT, cfg_entry["file"])
        traffic_file = traffic_file or os.path.join(
            HERE, "workloads", f"{workload}.json")
    config = load_json(config_file)
    traffic = load_json(traffic_file)
    chips = entry["chips"] if entry else int(config.get("chips", 1))
    return Cell(
        name=workload, chips=chips,
        config_name=entry["config"] if entry else traffic["config"],
        config=config, traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, workload)],
    )


def load_reader(metric_name: str):
    """The reader module ``metrics/<name>.py`` of a per-layer metric."""
    path = os.path.join(HERE, "metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
