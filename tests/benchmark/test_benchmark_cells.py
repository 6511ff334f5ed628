"""A configuration names its reference and its work counts, and the
harness reaches both through the cell alone."""

import ast
import glob
import json
import os

import pytest

from benchmark import cells

DATA = os.path.join(os.path.dirname(__file__), "data")
MANIFEST = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))


def _named_by_configs() -> set:
    """Modules some configuration file names as its reference or work."""
    out = set()
    for path in (glob.glob(os.path.join(cells.HERE, "configs", "*.json"))
                 + glob.glob(os.path.join(DATA, "*.config.json"))):
        body = cells.load_json(path)
        out |= {os.path.normpath(body[k]) for k in ("reference", "work")}
    return out


def test_no_harness_module_imports_a_reference_or_work_module_by_name():
    named = _named_by_configs()
    banned = {os.path.splitext(os.path.basename(p))[0] for p in named
              if p.startswith("benchmark" + os.sep)}
    assert {"reference", "work"} <= banned
    files = (glob.glob(os.path.join(cells.HERE, "*.py"))
             + glob.glob(os.path.join(cells.HERE, "metrics", "*.py")))
    assert len(files) > 20
    for path in files:
        if os.path.relpath(path, cells.ROOT) in named:
            continue
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "benchmark":
                got = {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                got = {(node.module or "").removeprefix("benchmark.")} if (
                    node.module or "").startswith("benchmark.") else set()
            elif isinstance(node, ast.Import):
                got = {a.name.removeprefix("benchmark.") for a in node.names
                       if a.name.startswith("benchmark.")}
            else:
                continue
            assert not got & banned, (path, got & banned)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_a_cell_carries_its_configurations_modules(cell):
    c = cells.load_cell(cell)
    from benchmark import reference, work

    # A file of the package is that package's module: whoever imports it
    # by name (the tests do) holds the object the harness calls.
    assert c.reference is reference and c.work is work
    assert callable(c.reference.make_weights) and callable(c.reference.judge)
    for fn in ("decode_flops", "prefill_flops", "decode_least_seconds",
               "prefill_least_seconds"):
        assert callable(getattr(c.work, fn))


def test_a_file_outside_a_package_is_loaded_from_its_location_once():
    kw = dict(config_file=os.path.join(DATA, "tiny-moe.config.json"),
              traffic_file=os.path.join(DATA, "tiny.closed.json"))
    a = cells.load_cell("tiny.rehearsal", **kw)
    b = cells.load_cell("tiny.rehearsal", **kw)
    assert a.reference is b.reference and a.work is b.work
    assert a.reference.__file__.endswith("tiny_moe_reference.py")
    assert a.work.decode_flops(a.config, 1, 0) != cells.load_cell(
        "qwen3-4b.chat").work.decode_flops


def test_a_configuration_without_the_keys_or_outside_paths_is_refused(
        tmp_path):
    body = cells.load_json(os.path.join(DATA, "tiny.config.json"))
    traffic = os.path.join(DATA, "tiny.closed.json")
    for broken, word in (
            ({k: v for k, v in body.items() if k != "work"}, "'work'"),
            (dict(body, reference="prof/described.py"), "under none of"),
            (dict(body, work="benchmark/../bench.py"), "under none of")):
        path = tmp_path / "c.json"
        path.write_text(json.dumps(broken))
        with pytest.raises(SystemExit, match=word):
            cells.load_cell("tiny.rehearsal", config_file=str(path),
                            traffic_file=traffic)
