"""Find a cell's files by the names ``BENCHMARK.json`` gives.

One file per configuration, per traffic mix and per per-layer metric;
the harness never names one in code, so a later PR adds a cell or a
metric by adding files and entries only. A configuration's file names
its own plain reference and its own work counts (``"reference"`` and
``"work"``: paths of modules under one of ``BENCHMARK.json``'s
``paths``), so a new architecture brings both as files too.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

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
    reference: object = None  # the configuration's plain reference (module)
    work: object = None       # the configuration's work counts (module)


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
    modules = {}
    for key in ("reference", "work"):
        if key not in config:
            raise SystemExit(f"{config_file} names no {key!r} module")
        modules[key] = load_module(config[key], manifest["paths"])
    return Cell(
        name=workload, chips=chips,
        config_name=entry["config"] if entry else traffic["config"],
        config=config, traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, workload)],
        **modules,
    )


def load_module(rel_path: str, paths: list):
    """The module at ``rel_path`` (from the checkout's root), which has
    to lie under one of ``paths``. A file of a package is imported as
    that package's module, so whoever imports it by name holds the same
    object; any other file is loaded from its location, once."""
    rel = os.path.normpath(rel_path)
    if not any(rel.startswith(os.path.normpath(p) + os.sep) for p in paths):
        raise SystemExit(f"{rel_path} lies under none of {paths}")
    parts = rel[:-len(".py")].split(os.sep)
    if all(os.path.exists(os.path.join(ROOT, *parts[:k], "__init__.py"))
           for k in range(1, len(parts))):
        return importlib.import_module(".".join(parts))
    return _from_location("benchmark_file_" + "_".join(parts),
                          os.path.join(ROOT, rel))


def _from_location(name: str, path: str):
    """The module in the file ``path``, executed once under ``name``."""
    name = name.replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_reader(metric_name: str):
    """The reader module ``metrics/<name>.py`` of a per-layer metric."""
    return _from_location("benchmark_metric_" + metric_name,
                          os.path.join(HERE, "metrics", f"{metric_name}.py"))
