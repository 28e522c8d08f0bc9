"""The `ouro_2_6b` cell end to end on the CPU at a tiny size (two blocks
with sandwich norms applied three times with one set of leaves, the exit
gate, the expected loss over the passes; tests/test_ouro.py has the
mechanisms one by one), in each trace mode; see test_rehearsal_bert.py.
`rehearse.py`'s table of tiny sizes is PR 24's file, so the sizes are
here. Nothing here is a device number."""

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import send_output_to  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]
         if w["config"] == "ouro_2_6b"]
# three layers: at two layers and three passes the search's thirty
# rounds take half a minute on this graph (PERF.md section 7)
TINY = dict(num_hidden_layers=3, vocab_size=64, hidden_size=32,
            num_attention_heads=4, num_key_value_heads=4, head_dim=8,
            intermediate_size=48, total_ut_steps=3, initializer_range=0.2,
            seq=32, batch=2, steps_per_epoch=2)
NEW = {"layers.looped_stack_share_pct", "layers.loop_pass_max_over_min",
       "layers.exit_heads_share_pct", "kernels.causal_flash_roofline"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["ouro_2_6b.s4096_b1.1chip"]


@pytest.mark.parametrize("trace", [0, 1, 2])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch, capsys):
    from benchmarks import harness as hs
    send_output_to(monkeypatch, tmp_path)
    result = hs.run_cell(cell, 2147483777, 0.5, trace,
                         t_start=time.perf_counter(),
                         rehearsal=dict(sizes=TINY))
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    assert (end_to_end <= names) == (trace != 1)
    # a CPU trace has no TPU lane: the device-trace readers find nothing
    assert not names & NEW
    if trace:
        assert result["metrics"]["compile.window_compiles"]["value"] == 0
        assert "executor.dispatch_ms" in names
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert checks["weights_installed"]["ok"] is True
    # 3 layers of 7 leaf-holding ops and the final norm, read twice more
    assert checks["shared_weight_ops"]["detail"] == 2 * (7 * 3 + 1)
    assert checks["layer_applications"]["detail"] == 9
    assert checks["parameters_held_once"]["ok"] is True
    assert checks["pred_nrmse"]["value"] < 1e-4
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    assert counters["executor.shared_weight_ops"] == 44
    assert counters["executor.shared_leaves"] == 3 * 10 + 1
    assert counters["executor.layer_applications"] == 9
    assert "executor.tied_head_ops" not in counters   # no tied head
    # the exit distribution's masses add up to the positions
    masses = [counters[f"loss/exit_mass_ut{t}"] for t in range(3)]
    assert sum(masses) == pytest.approx(counters["loss/target_positions"],
                                        rel=1e-5)
    assert all(m > 0 for m in masses)
    assert all(counters[f"loss/exit_nll_ut{t}"] > 0 for t in range(3))
    assert counters["loss/exit_entropy"] > 0
    if trace != 2:
        return
    # the join table the session wrote tells pass from pass, and the four
    # readers, given a TPU lane, would find their rows in it
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell)
    table = next(f for f in os.listdir(where)
                 if f.endswith(".step_scopes.json"))
    with open(os.path.join(where, table)) as f:
        rows = list(json.load(f)["instructions"].values())
    for scope in ("ut0", "ut1", "ut2", "exit", "attention_full", "head",
                  "loss", "op_rmsnorm"):
        assert any(f"jit({scope})" in r["op_name"] for r in rows), scope
    parts = {r["part"] for r in rows}
    assert {"ut0", "ut1", "ut2", "exit", "loss",
            "optimizer_update"} <= parts
    assert not any("jit(ut3)" in r["op_name"] for r in rows)
    # an op of a pass belongs to the pass, whatever its kind
    assert {r["part"] for r in rows
            if "jit(ut1)" in r["op_name"]} == {"ut1"}
    with open(os.path.join(where, next(
            f for f in os.listdir(where)
            if f.endswith(".events.jsonl")))) as f:
        header = json.loads(f.readline())
    meta = header.get("meta", header)
    assert meta["shared_weight_ops"] == 44
    assert meta["shared_leaves"] == 31 and meta["layer_applications"] == 9


def test_the_float8_control_is_not_correct():
    """The reference with float8 operands in the program's place fails
    the comparison, by the logits' limit, and reads over three times what
    bfloat16 operands (the configuration's) read."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks import seeds_check
    rows = seeds_check.check_seeds(CELLS[0], [2147483777],
                                   rehearsal=dict(sizes=TINY))
    for row in rows:
        assert row["program_correct"] is True
        assert row["fp8_correct"] is False
        assert row["fp8"]["pred_nrmse"] > 3 * row["bf16"]["pred_nrmse"]
