"""Every `bert_ae` cell end to end on the CPU at a tiny size, through
the test-only entry: the family's reference against the system on one
device and on four virtual devices, with and without the traced extras.
The benchmark lists no four-chip cell yet, so the four-device case adds
one (a data file and an entry, nothing else) in a temporary copy.
Nothing here is a device number."""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import rehearse  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]
         if w["config"] == "bert_ae"]
FOUR = "bert_ae.s512_b128.4chip"


def copy_with_a_four_chip_cell(tmp_path):
    root = tmp_path / "copy"
    root.mkdir()
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "native"), root / "native")
    why = "the one-chip cell's tokens a chip on four chips"
    (root / "benchmarks" / "workloads" / (FOUR + ".json")).write_text(
        json.dumps(dict(name=FOUR, config="bert_ae", chips=4, seq=512,
                        batch=128, steps_per_epoch=4, reference_chunk=8,
                        why=why)))
    m = json.loads(json.dumps(MANIFEST))
    m["workloads"].append(dict(name=FOUR, config="bert_ae",
                               traffic="s512_b128", chips=4, why=why))
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return str(root)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS + [FOUR])
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch):
    from benchmarks import harness
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    root = copy_with_a_four_chip_cell(tmp_path) if cell == FOUR else ROOT
    result = rehearse(cell, trace, root=root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    if trace:
        # device readers find no TPU lane in a CPU trace and return nothing
        assert {"search.search_s", "compile.model_compile_s",
                "compile.window_compiles", "executor.dispatch_ms"} <= names
        assert not names & {"device.idle_pct", "device.mfu_pct",
                            "kernels.flash_roofline"}
        assert result["metrics"]["compile.window_compiles"]["value"] == 0
    else:
        assert names == {m["name"] for m in MANIFEST["end_to_end"]}
        assert "setup_s" in names and "throughput" in names
