"""Reads `BENCHMARK.json` and the data files it names.

Everything that belongs to one configuration, one cell or one per-layer
metric sits in a file of its own, found by name:
    benchmarks/configs/<configuration>.json    (BENCHMARK.json gives the path)
    benchmarks/workloads/<cell>.json           traffic of the cell
    benchmarks/families/<family>.py            named by the configuration
    benchmarks/references/<family>.py
    benchmarks/layer_metrics/<metric>.py       one reader each
"""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts, root=ROOT):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json("BENCHMARK.json", root=root)


def find_cell(manifest, name, root=ROOT):
    """(cell entry, configuration file, traffic file) of one workload."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    config = load_json(entry["file"], root=root)
    traffic = load_json("benchmarks", "workloads", name + ".json", root=root)
    return cell, config, traffic


def metrics_of(manifest, kind, cell_name):
    """The `end_to_end` or `per_layer` metrics this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks_for(device_kind, root=ROOT):
    table = load_json("benchmarks", "peaks.json", root=root)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"benchmarks/peaks.json; add it with its source")
    return table[device_kind]
