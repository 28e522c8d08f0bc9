"""The `lfm2_8b_a1b` cell end to end on the CPU at a tiny size (the
cell's first two layers: a gated short convolution with the dense MLP,
grouped-query attention with the heads' norm and experts; ONE table; 4 of
16 experts held; tests/test_lfm2.py has the five), in each trace mode;
see test_rehearsal_bert.py. `rehearse.py`'s table of tiny sizes is PR
24's file, so the sizes are here. Nothing here is a device number."""

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
         if w["config"] == "lfm2_8b_a1b"]
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=8, num_key_value_heads=2, head_dim=8,
            intermediate_size=48, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, embedding_std=0.2, seq=32, batch=2,
            steps_per_epoch=2)
NEW = {"layers.short_conv_share_pct", "kernels.gated_conv_roofline",
       "layers.head64_attention_share_pct",
       "layers.unshared_experts_share_pct"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["lfm2_8b_a1b.s16384_b1.1chip"]


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
    assert checks["mixers_by_layer"]["detail"] == ["conv", "full_attention"]
    assert checks["one_table"]["detail"] == ["embed_tokens"]
    assert checks["weights_installed"]["ok"] is True
    assert checks["pred_nrmse"]["value"] < 1e-4
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["executor.short_conv_ops"] == 1
    assert counters["executor.tied_head_ops"] == 1
    if trace != 2:
        return
    # the join table the session wrote names the new scopes, and the four
    # readers, given a TPU lane, would find their rows in it
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell)
    table = next(f for f in os.listdir(where)
                 if f.endswith(".step_scopes.json"))
    with open(os.path.join(where, table)) as f:
        rows = json.load(f)["instructions"].values()
    for scope in ("op_short_conv", "gated_conv", "attention_full",
                  "rotary_whole", "moe_layer", "head"):
        assert any(f"jit({scope})" in r["op_name"] for r in rows), scope
    assert {r["part"] for r in rows
            if "jit(gated_conv)" in r["op_name"]} == {"op_short_conv"}
    with open(os.path.join(where, next(
            f for f in os.listdir(where)
            if f.endswith(".events.jsonl")))) as f:
        header = json.loads(f.readline())
    meta = header.get("meta", header)
    assert meta["short_conv_ops"] == 1 and meta["tied_head_ops"] == 1


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
