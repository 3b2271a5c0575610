"""BENCHMARK.json against the contract's limits, and every file it names."""

import json
import os
import re

import pytest

from benchmark.harness import manifest, readers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest.load_json(os.path.join(manifest.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(json.dumps(M)) < 64 * 1024
    assert M["paths"] == ["benchmark"]
    assert all(not w.startswith("/") and ".." not in w for w in M["command"])


def test_names_units_and_lines():
    metrics = M["end_to_end"] + M["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in M["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmark/")
    assert "setup_s" in {e["name"] for e in M["end_to_end"]}
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == len(M["workloads"])
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}


@pytest.mark.parametrize("workload", CELLS)
def test_cell_resolves_by_name(workload):
    cell = manifest.Cell(workload)
    assert cell.config["name"] == cell.entry["config"]
    assert cell.traffic["name"] == cell.entry["traffic"]
    ref = cell.reference_module()
    assert ref.NAME == cell.config["type"] and hasattr(ref, "Reference")
    listed = {c["name"]: c for c in M["configs"]}[cell.config["name"]]
    assert set(listed["reduced"]) == set(cell.config["reduced"])
    assert listed["source"] == cell.config["source"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        spec = cell.layer_spec(m["name"])
        assert spec["name"] == m["name"]
        assert spec["reader"] in readers.READERS
        assert m["moves"] in e2e, f"{m['name']} moves a metric {workload} does not report"


def test_files_under_paths_are_named_from_name_characters():
    for base, _dirs, files in os.walk(os.path.join(manifest.ROOT, "benchmark")):
        if "/out" in base or "__pycache__" in base or ".pytest_cache" in base:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(base, f)
