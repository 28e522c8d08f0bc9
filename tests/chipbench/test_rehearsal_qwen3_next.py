"""The `qwen3_next_80b_a3b` cell end to end on the CPU at a tiny size (a
delta layer and the attention layer, tiny widths, 2 key and 4 value heads of 8 in the
delta mixers with chunks of 8, 4 : 2 attention heads of 16 of which 4
lanes rotate, 4 of 16 experts held, samples of 36 tokens, which no chunk
divides), with the traced tail (`--trace 2`); see test_rehearsal_bert.py.
`rehearse.py`'s table of tiny sizes is PR 24's file, so the sizes are
here. Nothing here is a device number."""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import send_output_to  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]
         if w["config"] == "qwen3_next_80b_a3b"]
TINY = dict(num_hidden_layers=2, full_attention_interval=2, vocab_size=64,
            hidden_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=8,
            delta_chunk_size=8, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            shared_expert_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, qk_norm_scale=4.0,
            seq=36, batch=2, steps_per_epoch=2)
SCOPED = {"layers.delta_mixer_share_pct", "kernels.delta_rule_roofline",
          "layers.gated_attention256_share_pct",
          "kernels.head256_flash_roofline", "layers.top10_experts_share_pct"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["qwen3_next_80b_a3b.s16384_b1.1chip"]


def test_cell_end_to_end_tiny(tmp_path, monkeypatch, capsys):
    from benchmarks import harness as hs
    send_output_to(monkeypatch, tmp_path)
    result = hs.run_cell(CELLS[0], 2147483777, 0.5, 2,
                         t_start=time.perf_counter(),
                         rehearsal=dict(sizes=TINY))
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    names = set(result["metrics"])
    assert {m["name"] for m in MANIFEST["end_to_end"]} <= names
    # a CPU trace has no TPU lane: the scope readers find nothing to read
    assert not names & SCOPED
    assert result["metrics"]["compile.window_compiles"]["value"] == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert checks["parameters_as_counted"]["ok"]
    assert checks["mixers_by_layer"]["detail"] == [
        "linear_attention", "full_attention"]
    assert checks["pred_nrmse"]["value"] < 1e-4
    assert checks["loss0_rel"]["value"] < 1e-5
    assert checks["later_loss_rel"]["value"] < 1e-5
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    # 4 value heads x ceil(36 / 8) chunks x 2 samples: one fenced step
    assert counters["delta/chunks"] == 4 * 5 * 2
    assert 0 < counters["delta/decay_min"] < counters["delta/decay_mean"] < 1
    assert counters["executor.delta_mixer_ops"] == 1
    assert counters["executor.delta_rule_kernel_ops"] == 0     # the CPU
    assert counters["moe/overflow_slots"] == 0
