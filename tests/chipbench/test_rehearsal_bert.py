"""Every `bert_ae` cell end to end on the CPU at a tiny size, through
the test-only entry: the family's reference against the system on one
device and on four virtual devices, in each trace mode (0: the window
alone, 1: the traced extras, 2: the window, then the traced tail).
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

from rehearse import TINY, rehearse, send_output_to  # noqa: E402

TINY_STEPS = TINY["bert_ae"]["steps_per_epoch"]

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


SPAN_METRICS = {"input.stage_ms", "executor.host_step_ms",
                "compile.param_init_s"}


@pytest.mark.parametrize("trace", [0, 1, 2])
@pytest.mark.parametrize("cell", CELLS + [FOUR])
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch, capsys):
    send_output_to(monkeypatch, tmp_path / "out")
    root = copy_with_a_four_chip_cell(tmp_path) if cell == FOUR else ROOT
    result = rehearse(cell, trace, root=root)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    old = {"search.search_s", "compile.model_compile_s",
           "compile.window_compiles", "executor.dispatch_ms"}
    # device readers find no TPU lane in a CPU trace and return nothing
    device = {"device.idle_pct", "device.mfu_pct", "kernels.flash_roofline",
              "executor.epoch_gap_ms", "device.idle_staging_pct",
              "device.idle_unnamed_pct"}
    if trace == 0:
        assert names == end_to_end
        assert "breakdown" not in result
    elif trace == 1:
        # no session is open in this mode: its readers find no artifact
        assert names == old
    else:
        assert names == end_to_end | old | SPAN_METRICS
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps",
                                            "idle_shares_pct"}
    assert not names & device
    if trace:
        assert result["metrics"]["compile.window_compiles"]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert lines[-1] == json.loads(json.dumps(result, default=float))
    assert phases["start"]["trace"] == {0: False, 1: True, 2: 2}[trace]
    if trace != 1:
        # the end-to-end values are the closed window's own, whatever
        # follows it
        window, metrics = phases["window"], result["metrics"]
        assert metrics["throughput"]["value"] == window["throughput"]
        assert metrics["step_ms_p95"]["value"] == window["step_ms_p95"]
        assert metrics["setup_s"]["value"] == phases["setup"]["setup_s"]
        assert result["attempted"] == window["epochs"] + window["steps"]
    if trace == 2:
        tail = phases["trace"]
        order = [ln.get("phase") for ln in lines]
        assert order.index("window") < order.index("trace") \
            < order.index("reference")
        assert tail["traced_epochs"] >= 2 and tail["tail_s"] > 0
        assert tail["clock_tie_markers"] == 10
        assert set(tail["compile_phases"]) >= {"param_init_s",
                                               "state_placement_s"}
        assert tail["set_parameter_s"] > 0   # the weights were installed
        # the spans stay for the readers; the profile is gone
        from benchmarks import session_reduce as sr
        session = sr.load(sr.out_dir(root, cell))
        fenced = sr.load(sr.out_dir(root, cell, sr.FENCED))
        steps = TINY_STEPS
        assert len(sr.durations_ms(session, "fit")) == tail["traced_epochs"]
        assert len(sr.durations_ms(session, "step")) \
            == steps * tail["traced_epochs"]
        assert len(sr.durations_ms(fenced, "dispatch")) == steps
        assert not sr.durations_ms(session, "device_wait")
        left = os.listdir(sr.out_dir(root, cell))
        assert not [f for f in left if f.endswith(".jaxprof")]


def test_a_traced_run_reads_no_earlier_runs_session(tmp_path, monkeypatch):
    """The session's readers find the artifact on disk on their own: in
    one output directory, a `--trace 1` run after a `--trace 2` run prints
    what it prints in an empty one."""
    from benchmarks import session_reduce as sr
    send_output_to(monkeypatch, tmp_path)
    cell = CELLS[0]
    assert SPAN_METRICS <= set(rehearse(cell, 2)["metrics"])
    assert sr.load(sr.out_dir(ROOT, cell)) is not None
    after = rehearse(cell, 1, seed=8)
    assert not SPAN_METRICS & set(after["metrics"])
    assert "executor.dispatch_ms" in after["metrics"]
    for which in (sr.SESSION, sr.FENCED):
        assert not os.path.exists(sr.out_dir(ROOT, cell, which))
