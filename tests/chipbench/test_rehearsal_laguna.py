"""The `laguna_xs2` cell end to end on the CPU at a tiny size (the cell's
first two layers: full attention with 6 heads and the dense MLP, window
attention with 8 heads and experts; 2 key/value heads, tiny widths, 4 of
16 experts held; tests/test_laguna.py has the five), in each trace mode,
in the traced one with the flash kernels interpreted so that the window
layer runs the blocked kernels at a window of 128 keys under sequences of
1,536, narrower than a chunk; see test_rehearsal_bert.py. `rehearse.py`'s table of tiny sizes is PR
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
         if w["config"] == "laguna_xs2"]
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=6,
            num_attention_heads_per_layer=[6, 8, 8, 8, 6],
            num_key_value_heads=2, head_dim=16, sliding_window=128,
            intermediate_size=48, num_experts=4, num_experts_published=16,
            num_experts_per_tok=3, moe_intermediate_size=24,
            shared_expert_intermediate_size=24, slot_slack=3.0,
            initializer_range=0.2, seq=1536, batch=1, steps_per_epoch=2)
SMALL = dict(TINY, sliding_window=8, seq=32, batch=2)
NEW = {"layers.gated_window_attention_share_pct",
       "layers.gated_full_attention_share_pct",
       "layers.attention_gate_share_pct",
       "kernels.narrow_window_flash_roofline"}
RATIO = "kernels.window_keys_visited_ratio"


def test_one_cell_of_the_configuration():
    assert CELLS == ["laguna_xs2.s8192_b1.1chip"]


@pytest.mark.parametrize("trace", [0, 1, 2])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch, capsys):
    from benchmarks import harness as hs
    from flexflow_tpu.ops import pallas_kernels as pk
    send_output_to(monkeypatch, tmp_path)
    # the kernels interpreted in the one run that reads their counts
    sizes = TINY if trace == 2 else SMALL
    if trace == 2:
        monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    result = hs.run_cell(cell, 2147483777, 0.5, trace,
                         t_start=time.perf_counter(),
                         rehearsal=dict(sizes=sizes))
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
    assert checks["attention_heads_by_layer"]["detail"] == [6, 8]
    assert checks["pred_nrmse"]["value"] < 1e-4
    counters = next(ln for ln in lines
                    if ln.get("phase") == "observed")["op_counters"]
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["executor.window_attention_ops"] == 1
    assert [counters[f"attention/heads_by_op/b{i}_attn"]
            for i in range(2)] == [6, 8]
    if trace != 2:
        # the einsum core visits no tile, and the counter's reader finds
        # nothing to divide
        assert counters["attention/window_keys_visited"] == 0
        assert RATIO not in names
        return
    # the window op ran the blocked kernels in chunks of 256 under a
    # window of 128, and the program counter's reader reports the ratio
    seq, window = TINY["seq"], TINY["sliding_window"]
    assert pk._seq_block(seq, None, window) == 256
    assert counters["executor.flash_lane_dense_ops"] == 2
    assert counters["attention/window_keys_visited"] == pk.visited_pairs(
        seq, True, window)
    assert counters["attention/window_keys_visible"] == 2 * (
        seq * window - window * (window - 1) // 2)
    ratio = result["metrics"][RATIO]["value"]
    assert ratio == pytest.approx(
        counters["attention/window_keys_visited"]
        / counters["attention/window_keys_visible"])
    assert 2 < ratio < 4
    # the join table the session wrote names the new scopes
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell)
    table = next(f for f in os.listdir(where)
                 if f.endswith(".step_scopes.json"))
    with open(os.path.join(where, table)) as f:
        rows = json.load(f)["instructions"].values()
    for scope in ("attention_gate", "rotary_partial_yarn", "rotary_whole",
                  "flash_window", "flash_full"):
        assert any(f"jit({scope})" in r["op_name"] for r in rows), scope
    with open(os.path.join(where, next(
            f for f in os.listdir(where)
            if f.endswith(".events.jsonl")))) as f:
        header = json.loads(f.readline())
    meta = header.get("meta", header)
    assert meta["window_attention_ops"] == 1
    assert meta["attention_window_keys_visited"] == counters[
        "attention/window_keys_visited"]


def test_an_older_program_ends_at_once(monkeypatch):
    """Under these files a program whose decoder has no per-layer head
    counts (the parent commit's) is refused by `sizes`, before any weight
    is made: a clean exit, soon."""
    import dataclasses

    from benchmarks import harness as hs
    from benchmarks import manifest as mf
    from flexflow_tpu import models
    _, config, traffic = mf.find_cell(MANIFEST, CELLS[0])
    family = hs.load_by_path("families", config["family"])

    @dataclasses.dataclass
    class Older:
        hidden_size: int = 64
    monkeypatch.setattr(models, "DecoderConfig", Older)
    with pytest.raises(SystemExit, match="per-layer head counts"):
        family.sizes(config, traffic)


def test_the_float8_control_is_not_correct():
    """The reference with float8 operands in the program's place fails
    the comparison, by the logits' limit, and reads over three times what
    bfloat16 operands (the configuration's) read."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks import seeds_check
    rows = seeds_check.check_seeds(CELLS[0], [2147483777],
                                   rehearsal=dict(sizes=SMALL))
    for row in rows:
        assert row["program_correct"] is True
        assert row["fp8_correct"] is False
        assert row["fp8"]["pred_nrmse"] > 3 * row["bf16"]["pred_nrmse"]
