"""The `sdar_30b_a3b` cell end to end on the CPU at a tiny size (two
layers, tiny widths, 4 of 16 experts held, samples of 128 tokens in blocks
of 4; the mask token's row small and the query/key norms' scale large, as
in the cell, so that float8 operands read past the cell's own limit at
this size too), in each trace mode; see test_rehearsal_bert.py. `rehearse.py`'s
table of tiny sizes is PR 24's file, so the sizes are here. Nothing here
is a device number."""

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
         if w["config"] == "sdar_30b_a3b"]
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=4, num_key_value_heads=1, head_dim=16,
            num_experts=4, num_experts_published=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            mask_embedding_std=0.02, qk_norm_scale=4.0, seq=128, batch=2,
            steps_per_epoch=2)
SCOPED = {"layers.block_diffusion_attention_share_pct",
          "kernels.block_diffusion_flash_roofline"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["sdar_30b_a3b.s8192_b1.1chip"]


@pytest.mark.parametrize("trace", [0, 1, 2])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_tiny(cell, trace, tmp_path, monkeypatch, capsys):
    from benchmarks import harness as hs
    send_output_to(monkeypatch, tmp_path)
    # the searched flash kernels run (interpreted), mask and all: at 256
    # positions the shape is one the kernels take
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
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
    assert checks["pred_nrmse"]["value"] < 1e-4
    observed = next(ln for ln in lines if ln.get("phase") == "observed")
    # the routing counts and the targets of the window's last epoch, what
    # the attention ops' traced forwards recorded, and the compiled
    # step's scopes, were there for the readers
    counters = observed["op_counters"]
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["executor.block_diffusion_attention_ops"] == 2
    assert counters["loss/target_positions"] in observed[
        "target_positions_by_batch"]
    assert counters["attention/kv_blocks_visited"] == 2    # whole tiles
    assert counters["executor.flash_lane_dense_ops"] == 2
    assert observed["scoped_instructions"] > 0
    assert observed["scopes_error"] is None


def test_the_scoped_metrics_are_reported_where_the_trace_has_the_scopes():
    """With a device lane whose events carry the family's observed scopes
    (as a chip run's do), the cell's line holds both new metrics."""
    from benchmarks import harness as hs
    from benchmarks import manifest as mf
    from benchmarks import trace_reduce as tr
    cell, config, traffic = mf.find_cell(MANIFEST, CELLS[0])
    family = hs.load_by_path("families", config["family"])
    family.observed["scopes"] = {
        "a.1": "jit(train_step)/jvp(jit(attention_block_diffusion))/"
               "jit(flash_block_diffusion)/pallas_call",
        "a.2": "jit(train_step)/jvp(jit(attention_block_diffusion))/"
               "dot_general",
        "a.3": "jit(train_step)/jvp(jit(moe_layer))/dot_general"}
    dev = tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 0.1)],
        tr.OPS: [("a.1", 0.0, 0.04), ("a.2", 0.04, 0.01),
                 ("a.3", 0.05, 0.05)]})
    ctx = dict(devices=[dev], family=family, cell=cell, config=config,
               traffic=traffic, counters=dict(
                   sizes=family.sizes(config, traffic),
                   peaks=dict(bf16_flops_per_s=197e12,
                              hbm_bytes_per_s=819e9)))
    got = {m["name"]: hs.load_by_path("layer_metrics", m["name"]).read(ctx)
           for m in mf.metrics_of(MANIFEST, "per_layer", CELLS[0])
           if m["name"] in SCOPED}
    assert got["layers.block_diffusion_attention_share_pct"] == \
        pytest.approx(50.0)
    # 3.300 TFLOP at 197 TFLOP/s is 16.75 ms of the made-up 40
    assert got["kernels.block_diffusion_flash_roofline"] == pytest.approx(
        41.88, rel=1e-3)


def test_the_float8_control_is_not_correct(monkeypatch):
    """The reference with float8 operands in the program's place fails
    the comparison, by the logits' limit, and reads over three times what
    bfloat16 operands (the configuration's) read. (At this size and the
    configuration's rate of 1e-7 Adam's rule hardly shows in three losses:
    the wrong-Adam control is the chip's, PERF.md.)"""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks import seeds_check
    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    rows = seeds_check.check_seeds(CELLS[0], [5, 2147483777],
                                   rehearsal=dict(sizes=TINY))
    for row in rows:
        assert row["program_correct"] is True
        assert row["fp8_correct"] is False
        assert row["fp8"]["pred_nrmse"] > 3 * row["bf16"]["pred_nrmse"]
