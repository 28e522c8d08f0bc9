"""The `xing4_0_29b_a4b` cell end to end on the CPU at a tiny size (one
dense and one expert layer and the multi-token-prediction module, each
sublayer behind a hyper-connection over 4 streams, tiny widths that keep
the query/key head wider than the value head, YaRN over a short original
length, 4 of 16 experts held, sequences of 128 tokens), in each trace
mode; see test_rehearsal_bert.py. `rehearse.py`'s table of tiny sizes is
PR 24's file, so the sizes are here. Nothing here is a device number."""

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
         if w["config"] == "xing4_0_29b_a4b"]
TINY = dict(num_hidden_layers=2, vocab_size=64, hidden_size=32,
            num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            intermediate_size=48, n_routed_experts=4,
            n_routed_experts_published=16, num_experts_per_tok=3,
            moe_intermediate_size=24, slot_slack=3.0, initializer_range=0.2,
            rope_scaling=dict(type="yarn", factor=64, beta_fast=32,
                              beta_slow=1, mscale=1, mscale_all_dim=1,
                              original_max_position_embeddings=32),
            seq=128, batch=2, steps_per_epoch=2)
NEW = {"layers.hyper_connection_share_pct",
       "kernels.hyper_connection_roofline"}


def test_one_cell_of_the_configuration():
    assert CELLS == ["xing4_0_29b_a4b.s4096_b1.1chip"]


# the two modes the driver passes (`--trace 1`'s readers are the other
# cells' rehearsals'; a mode here is 100 s of the suite's clock)
@pytest.mark.parametrize("trace", [0, 2])
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
    # a CPU trace has no TPU lane: the new readers find nothing to read
    assert not names & NEW
    if trace:
        assert result["metrics"]["compile.window_compiles"]["value"] == 0
        assert "executor.dispatch_ms" in names
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    checks = {ln["name"]: ln for ln in lines if ln.get("phase") == "check"}
    assert checks["no_kernel_fallback"]["detail"] == {}
    assert checks["attention_all_latent"]["detail"] == [
        "b0_attn", "b1_attn", "mtp_attn"]
    assert checks["streams_as_stated"]["detail"] == dict(
        sublayers=6, of=[[4, 20]])
    assert checks["parameters_as_counted"]["ok"]
    assert checks["routers_as_stated"]["ok"]
    assert checks["rope_scaling_as_stated"]["ok"]
    assert checks["pred_nrmse"]["value"] < 1e-4
    observed = next(ln for ln in lines if ln.get("phase") == "observed")
    # the routing counts, the two loss terms and the targets of the
    # window's last epoch, and what the attention ops' traced forwards
    # recorded
    counters = observed["op_counters"]
    assert counters["moe/overflow_slots"] == 0
    assert counters["moe/slots_held"] > 0
    assert counters["executor.latent_attention_ops"] == 3
    assert counters["loss/target_positions"] in observed[
        "target_positions_by_batch"] == [2 * (127 + 126)]
    assert counters["loss/main_nll"] > 0 and counters["loss/mtp_nll"] > 0
    # the hyper-connections as traced, and the witness that the
    # projection onto the doubly stochastic matrices ran to its end
    assert counters["hc/streams"] == 4 and counters["hc/sublayers"] == 6
    assert counters["hc/sinkhorn_iters"] == 20
    assert counters["hc/kernel_fallbacks"] == 12     # the CPU: every op
    assert 0 <= counters["hc/res_row_sum_err_max"] < 1e-3
    assert 0 <= counters["hc/res_col_sum_err_max"] < 1e-3
    if trace == 2:
        # the join table the session wrote names the new part and scopes
        from benchmarks import session_reduce as sr
        tables = [f for f in os.listdir(sr.out_dir(ROOT, cell))
                  if f.endswith(".step_scopes.json")]
        with open(os.path.join(sr.out_dir(ROOT, cell), tables[0])) as f:
            rows = json.load(f)["instructions"].values()
        assert any(r["part"] == "mtp" for r in rows)
        assert any("jit(attention_latent)" in r["op_name"] for r in rows)
        rows = list(rows)
        assert any(r["part"] == "hyper_connection" for r in rows)
        for scope in ("hc_read", "hc_maps", "hc_write"):
            for direction in ("jvp(", "transpose("):
                assert any(f"jit({scope})" in r["op_name"]
                           and f"jit(hyper_connection)" in r["op_name"]
                           and direction in r["op_name"] for r in rows), (
                    scope, direction)
        with open(os.path.join(sr.out_dir(ROOT, cell), next(
                f for f in os.listdir(sr.out_dir(ROOT, cell))
                if f.endswith(".events.jsonl")))) as f:
            header = json.loads(f.readline())
        meta = header.get("meta", header)
        assert meta["latent_attention_ops"] == 3
        assert meta["loss_main_nll"] > 0 and meta["loss_mtp_nll"] > 0
        assert meta["hc_sublayers"] == 6 and meta["hc_streams"] == 4


def test_an_older_program_ends_at_once(monkeypatch):
    """Under these files a program whose decoder has no hyper-connections
    (the parent commit's) is refused by `sizes`, before any weight is
    made: a clean exit, soon."""
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
    with pytest.raises(SystemExit, match="hyper-connections"):
        family.sizes(config, traffic)


def test_the_float8_control_is_not_correct():
    """The reference with float8 operands in the program's place fails
    the comparison, by the logits' limit, and reads over three times what
    bfloat16 operands (the configuration's) read. (At this size and the
    configuration's rate of 1e-7 Adam's rule hardly shows in three losses:
    the wrong-Adam control is the chip's, PERF.md.)"""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from benchmarks import seeds_check
    rows = seeds_check.check_seeds(CELLS[0], [2147483777],
                                   rehearsal=dict(sizes=TINY))
    for row in rows:
        assert row["program_correct"] is True
        assert row["fp8_correct"] is False
        assert row["fp8"]["pred_nrmse"] > 3 * row["bf16"]["pred_nrmse"]
