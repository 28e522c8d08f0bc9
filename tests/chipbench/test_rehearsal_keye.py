"""The `keye_vl2_30b_a3b` cell end to end on the CPU at a tiny size (two
layers, tiny widths, 4 of 16 experts held, samples of 96 tokens of which
a query keeps 24 keys, an indexer of 4 heads of 16), with the traced tail
(`--trace 2`); see test_rehearsal_bert.py. `rehearse.py`'s table of tiny
sizes is PR 24's file, so the sizes are here. Nothing here is a device
number."""

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
         if w["config"] == "keye_vl2_30b_a3b"]
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=4, num_key_value_heads=1, head_dim=16,
            num_experts=4, num_local_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            qk_norm_scale=4.0,
            sa_config=dict(indexer_num_heads=4, indexer_head_dim=16,
                           indexer_num_kv_heads=1, topk=24,
                           q_chunk_size=512, kv_chunk_size=512),
            rope_scaling=dict(mrope_section=[2, 3, 3]),
            seq=96, batch=2, steps_per_epoch=2)
SCOPED = {"layers.sparse_attention_share_pct",
          "layers.sparse_indexer_share_pct", "kernels.sparse_flash_roofline",
          "kernels.index_select_roofline"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["keye_vl2_30b_a3b.s16384_b1.1chip"]


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
    # the causal triangle's pairs are not the kernels': no flash here
    assert "kernels.selected_keys_visited_ratio" not in names
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert checks["parameters_as_counted"]["ok"]
    assert checks["sparse_attention_ops"]["detail"] == 2
    assert checks["pred_nrmse"]["value"] < 1e-4
    assert checks["loss0_rel"]["value"] < 1e-5
    assert checks["later_loss_rel"]["value"] < 1e-5
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    # min(t + 1, 24) a query, 2 layers, 2 samples: one fenced step
    assert counters["attention/selected_pairs"] == 2 * 2 * (
        24 * 25 // 2 + (96 - 24) * 24)
    assert counters["loss/index_kl"] > 0
    assert counters["loss/target_positions"] == 2 * 96
    assert counters["moe/overflow_slots"] == 0
