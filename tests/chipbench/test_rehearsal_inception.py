"""The `inception_v3_ae` cell end to end on the CPU at a tiny size (75
pixels, 10 classes), traced in both ways; see test_rehearsal_bert.py."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import rehearse, send_output_to  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]
             if w["config"] == "inception_v3_ae"]


@pytest.mark.parametrize("trace", [1, 2])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch):
    send_output_to(monkeypatch, tmp_path)
    result = rehearse(cell, trace)
    assert result["correct"] is True
    assert result["metrics"]["compile.window_compiles"]["value"] == 0
    assert "executor.dispatch_ms" in result["metrics"]
    both = {"throughput", "step_ms_p95", "setup_s", "input.stage_ms",
            "executor.host_step_ms", "compile.param_init_s"}
    assert (both <= set(result["metrics"])) == (trace == 2)
