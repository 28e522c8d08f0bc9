"""The `inception_v3_ae` cell end to end on the CPU at a tiny size (75
pixels, 10 classes), traced; see test_rehearsal_bert.py."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import rehearse  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]
             if w["config"] == "inception_v3_ae"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, tmp_path, monkeypatch):
    from benchmarks import harness
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    result = rehearse(cell, True)
    assert result["correct"] is True
    assert result["metrics"]["compile.window_compiles"]["value"] == 0
    assert "executor.dispatch_ms" in result["metrics"]
