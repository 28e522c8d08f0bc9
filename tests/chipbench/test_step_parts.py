"""The readers of the program's join table (`benchmarks/step_parts.py`)
on a made-up device lane and table: shares by direction and by part over
the busy time of the train-step programs' own spans, another program's
colliding `fusion.1` left out, and nothing where there is no table.
Nothing here is a device number."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import send_output_to  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
ALL_CELLS = [w["name"] for w in MANIFEST["workloads"]]
DECODER_CELLS = [w["name"] for w in MANIFEST["workloads"] if w["config"] in (
    "nemotron3_nano_30b_a3b", "smallthinker_21b_a3b", "sdar_30b_a3b")]
EVERY_CELL = ["executor.forward_share_pct", "executor.backward_share_pct",
              "executor.optimizer_share_pct", "device.unscoped_share_pct"]
DECODERS = ["layers.head_loss_share_pct", "layers.moe_combine_share_pct"]
CELL = "nemotron3_nano_30b_a3b.s8192_b1.1chip"

STEP = "jit(train_step)/"
TABLE = {
    "fusion.1": dict(op_name=STEP + "jvp(jit(op_rmsnorm))/mul",
                     part="op_rmsnorm", direction="forward",
                     parts={"op_rmsnorm": 3, "op_ew_add": 1},
                     directions={"forward": 4}),
    "fusion.2": dict(op_name=STEP + "transpose(jvp(jit(moe_layer)))/"
                     "jit(moe_combine)/gather", part="experts",
                     direction="backward", parts={"experts": 2},
                     directions={"backward": 2}),
    "fusion.3": dict(op_name=STEP + "transpose(jvp(jit(head)))/dot_general",
                     part="head", direction="backward",
                     parts={"head": 2, "optimizer_update": 5},
                     directions={"backward": 2, "optimizer": 5}),
    "fusion.4": dict(op_name=STEP + "jvp(jit(loss))/reduce_sum", part="loss",
                     direction="forward"),
    "fusion.5": dict(op_name=STEP + "jit(optimizer_update)/sqrt",
                     part="optimizer_update", direction="optimizer"),
    "copy.6": dict(op_name="", part=None, direction="none"),
    "gmm.7": dict(op_name=STEP + "jvp(jit(moe_layer))/jit("
                  "moe_grouped_matmul)/jit(gmm)/pallas_call", part="experts",
                  direction="forward"),
}


def device():
    """Two train steps of 0.1 s with a `jit_add` between them whose
    `fusion.1` is not the step's; step 2 also runs an instruction the
    table lacks and a loop whose span encloses its body's."""
    from benchmarks import trace_reduce as tr
    step = [("fusion.1", 0.00, 0.010), ("gmm.7", 0.01, 0.020),
            ("fusion.4", 0.03, 0.005), ("fusion.3", 0.035, 0.015),
            ("fusion.2", 0.05, 0.020), ("fusion.5", 0.07, 0.020),
            ("copy.6", 0.09, 0.005)]
    ops = step + [("fusion.1", 0.15, 0.04)] + [
        (n, s + 0.2, d) for n, s, d in step] + [
        ("while.9", 0.2, 0.1), ("fusion.77", 0.295, 0.005)]
    return tr.Device("/device:TPU:0", {
        tr.MODULES: [(tr.STEP_MODULE + "(1)", 0.0, 0.1),
                     ("jit_add(2)", 0.15, 0.04),
                     (tr.STEP_MODULE + "(1)", 0.2, 0.1)],
        tr.OPS: sorted(ops, key=lambda e: e[1])})


def context(cell_name):
    from benchmarks import manifest as mf
    cell, config, traffic = mf.find_cell(MANIFEST, cell_name)
    return dict(devices=[device()], cell=cell, config=config,
                traffic=traffic, family=None, counters={})


def write_table(directory, cell_name, table=TABLE):
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell_name)
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "session_r00_host00.step_scopes.json"),
              "w") as f:
        json.dump(dict(header=dict(kind="step_scopes"), instructions=table),
                  f)
    return where


def read(name, ctx):
    from benchmarks import harness as hs
    return hs.load_by_path("layer_metrics", name).read(ctx)


def test_the_six_metrics_are_listed_last_and_where_they_read():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-6:] == EVERY_CELL + DECODERS
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in EVERY_CELL + DECODERS:
        m = by_name[name]
        assert (m["unit"], m["source"], m["moves"]) == (
            "%", "device_trace", "throughput")
        assert m["workloads"] == (ALL_CELLS if name in EVERY_CELL
                                  else DECODER_CELLS)
    assert {by_name[n]["layer"] for n in EVERY_CELL[:3]} == {"executor step"}
    assert by_name["device.unscoped_share_pct"]["layer"] == "device"
    assert {by_name[n]["layer"] for n in DECODERS} == {"model ops"}


def test_shares_over_the_train_steps_own_spans(tmp_path, monkeypatch):
    send_output_to(monkeypatch, tmp_path)
    where = write_table(tmp_path, CELL)
    ctx = context(CELL)
    got = {name: read(name, ctx) for name in EVERY_CELL + DECODERS}
    # a step is busy 0.095 s of its 0.1 (0.1 in the second, whose last
    # event the table lacks): 0.195 s over both; `jit_add`'s `fusion.1`
    # of 0.04 s, which would be a fifth more forward, is in no share
    busy = 0.195
    assert got["executor.forward_share_pct"] == pytest.approx(
        100 * 2 * (0.010 + 0.020 + 0.005) / busy)
    assert got["executor.backward_share_pct"] == pytest.approx(
        100 * 2 * (0.015 + 0.020) / busy)
    assert got["executor.optimizer_share_pct"] == pytest.approx(
        100 * 2 * 0.020 / busy)
    # the copy without a scope and the instruction without a row
    assert got["device.unscoped_share_pct"] == pytest.approx(
        100 * (2 * 0.005 + 0.005) / busy)
    assert got["layers.head_loss_share_pct"] == pytest.approx(
        100 * 2 * (0.015 + 0.005) / busy)
    assert got["layers.moe_combine_share_pct"] == pytest.approx(
        100 * 2 * 0.020 / busy)
    with open(os.path.join(where, "step_parts.json")) as f:
        breakdown = json.load(f)
    assert breakdown["steps"] == 2
    assert breakdown["busy_ms_a_step"] == pytest.approx(97.5)
    assert breakdown["unknown_pct"] == pytest.approx(100 * 0.005 / busy)
    # fusion.1 and fusion.3 hold two parts each; fusion.3 two directions
    assert breakdown["mixed_pct"] == pytest.approx(
        100 * 2 * (0.010 + 0.015) / busy)
    assert breakdown["mixed_direction_ms_a_step"] == [
        ["backward", "backward+optimizer", pytest.approx(15.0)]]
    rows = {(p, d): ms for p, d, ms in breakdown["part_direction_ms_a_step"]}
    assert rows[("experts", "forward")] == pytest.approx(20.0)
    assert rows[("None", "none")] == pytest.approx(5.0)
    stems = {(s, p): ms for s, p, ms in breakdown["stem_part_ms_a_step"]}
    assert stems[("fusion", "not in the table")] == pytest.approx(2.5)
    assert stems[("copy", "None")] == pytest.approx(5.0)


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_no_table_nothing_reported(cell, tmp_path, monkeypatch):
    """The parent's program writes no table: every reader returns None
    and raises nothing, with or without a device lane."""
    send_output_to(monkeypatch, tmp_path)
    ctx = context(cell)
    wanted = EVERY_CELL + (DECODERS if cell in DECODER_CELLS else [])
    assert [read(name, ctx) for name in wanted] == [None] * len(wanted)
    ctx["devices"] = []
    assert [read(name, ctx) for name in wanted] == [None] * len(wanted)


def test_a_table_without_a_device_lane_reports_nothing(tmp_path,
                                                       monkeypatch):
    """A CPU rehearsal writes the table and has no TPU lane."""
    send_output_to(monkeypatch, tmp_path)
    write_table(tmp_path, CELL)
    ctx = dict(context(CELL), devices=[])
    assert [read(n, ctx) for n in EVERY_CELL + DECODERS] == [None] * 6


def test_the_tables_rule_is_the_programs(tmp_path, monkeypatch):
    """The reader takes part and direction from the table; the table's
    are those of `flexflow_tpu.obs.step_scopes` for the same `op_name`."""
    from flexflow_tpu.obs import step_scopes as ss
    for row in TABLE.values():
        assert ss.classify(row["op_name"]) == (row["part"], row["direction"])
