"""A later PR adds a configuration, a cell and a per-layer metric by
adding files and entries only: done here in a temporary copy, and the
harness finds all three by name."""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def test_dummy_config_cell_and_metric_are_found(tmp_path, monkeypatch):
    import time

    from benchmarks import harness

    root = tmp_path / "copy"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "native"), root / "native")
    before = {p: (root / p).read_bytes() for p in
              ("benchmarks/harness.py", "benchmarks/run.py",
               "benchmarks/manifest.py")}

    # new files
    cfg = json.loads((root / "benchmarks/configs/bert_ae.json").read_text())
    cfg.update(name="dummy_cfg", num_hidden_layers=1, hidden_size=32,
               num_attention_heads=2, head_dim=16, intermediate_size=128,
               search_budget=1)
    (root / "benchmarks/configs/dummy_cfg.json").write_text(json.dumps(cfg))
    (root / "benchmarks/workloads/dummy_cfg.s16_b4.1chip.json").write_text(
        json.dumps(dict(name="dummy_cfg.s16_b4.1chip", config="dummy_cfg",
                        chips=1, seq=16, batch=4, steps_per_epoch=2,
                        reference_chunk=4, why="a dummy")))
    (root / "benchmarks/layer_metrics/dummy.answer.py").write_text(
        "def read(ctx):\n    return 42.0 + 0 * ctx['counters']['batch']\n")
    # new entries
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append(dict(name="dummy_cfg", source="none",
                             file="benchmarks/configs/dummy_cfg.json",
                             reduced=[], why="a dummy"))
    m["workloads"].append(dict(name="dummy_cfg.s16_b4.1chip",
                               config="dummy_cfg", traffic="s16_b4", chips=1,
                               why="a dummy"))
    m["per_layer"].append(dict(name="dummy.answer", unit="count",
                               better="higher", source="program_counter",
                               layer="search", moves="setup_s",
                               workloads=["dummy_cfg.s16_b4.1chip"]))
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    monkeypatch.setattr(harness, "OUT_DIR", "out")
    result = harness.run_cell("dummy_cfg.s16_b4.1chip", 5, 0.3, True,
                              t_start=time.perf_counter(), root=str(root),
                              rehearsal={})
    assert result["correct"] is True
    assert result["metrics"]["dummy.answer"] == {"value": 42.0,
                                                 "unit": "count"}
    assert "search.search_s" in result["metrics"]
    # the new metric is not read in cells that do not list it
    assert "dummy.answer" not in [
        x["name"] for x in harness.mf.metrics_of(m, "per_layer",
                                                 "bert_ae.s512_b32.1chip")]
    for p, content in before.items():
        assert (root / p).read_bytes() == content
