"""Lint of BENCHMARK.json against the contract's limits that a CPU can
check, and of the data files it names."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer",
                             "trace_in_run"}
    # per-layer metrics are read in the run that measures (`--trace 2`)
    assert manifest["trace_in_run"] is True
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert manifest["paths"] == ["benchmarks", "tests/chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(manifest):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind in ("end_to_end", "per_layer"), entry["name"]))
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert 1 <= len(entry[key]) <= 200
                    assert "\n" not in entry[key] and "\t" not in entry[key]
    assert len(names) == len(set(names)), "duplicate name"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}


def test_files_exist_and_configs_are_used(manifest):
    used = {w["config"] for w in manifest["workloads"]}
    files = set()
    for c in manifest["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for kind in ("families", "references"):
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", kind, cfg["family"] + ".py"))
    pairs = set()
    for w in manifest["workloads"]:
        assert w["config"] in {c["name"] for c in manifest["configs"]}
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, "benchmarks", "workloads",
                               w["name"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["chips"] == w["chips"] and traffic["why"] == w["why"]
    for m in manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))


def test_at_most_one_four_chip_cell(manifest):
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_every_moves_is_reported_by_each_of_its_cells(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    reports = {c: {m["name"] for m in manifest["end_to_end"]
                   if "workloads" not in m or c in m["workloads"]}
               for c in cells}
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert any("workloads" not in m or c in m["workloads"]
                   for m in manifest["per_layer"])
    for m in manifest["per_layer"]:
        for c in m.get("workloads", cells):
            assert c in cells and m["moves"] in reports[c], (m["name"], c)


def test_layers_are_the_ones_perf_md_lists(manifest):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in manifest["per_layer"]:
        assert f"**{m['layer']}**" in perf, m["layer"]
