"""The `nemotron3_nano_30b_a3b` cell end to end on the CPU at a tiny size
(the cell's nine letters, tiny widths, 4 of 16 experts held), in each
trace mode; see test_rehearsal_bert.py. `rehearse.py`'s table of tiny
sizes is PR 24's file, so the sizes are here. Nothing here is a device
number."""

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
         if w["config"] == "nemotron3_nano_30b_a3b"]
TINY = dict(num_hidden_layers=9, vocab_size=64, hidden_size=32,
            num_attention_heads=2, num_key_value_heads=1, head_dim=16,
            mamba_num_heads=2, mamba_head_dim=8, n_groups=1,
            ssm_state_size=16, chunk_size=8, n_routed_experts=4,
            n_routed_experts_published=16, num_experts_per_tok=3,
            moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
            slot_slack=3.0, initializer_range=0.2, seq=24, batch=2,
            steps_per_epoch=2)
SCOPED = {"layers.ssm_share_pct", "layers.moe_share_pct",
          "kernels.ssd_roofline", "kernels.grouped_matmul_roofline"}


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
    # a CPU trace has no TPU lane: the scope readers find nothing to read
    assert not names & SCOPED
    if trace:
        assert result["metrics"]["compile.window_compiles"]["value"] == 0
        assert "executor.dispatch_ms" in names
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert 0.0 <= checks["routing_flip_share_first_expert_layer"][
        "detail"] <= 0.05
    observed = next(ln for ln in lines if ln.get("phase") == "observed")
    # the routing counts of the window's last epoch, and the compiled
    # step's scopes, were there for the readers
    assert observed["op_counters"]["moe/overflow_slots"] == 0
    assert observed["op_counters"]["moe/slots_held"] > 0
    assert observed["scoped_instructions"] > 0
    assert observed["scopes_error"] is None
