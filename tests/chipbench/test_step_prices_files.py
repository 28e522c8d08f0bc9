"""The three readers of the search's prices (`benchmarks/step_prices.py`)
on a made-up device lane, join table, `prices` object and session
header: the numbers they give, the join they leave, and nothing where
the program wrote no prices; then one traced rehearsal of the cheapest
tiny cell, which leaves `step_prices.json`. Nothing here is a device
number."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from rehearse import rehearse, send_output_to  # noqa: E402
from test_step_parts import (ALL_CELLS, CELL, MANIFEST, TABLE,  # noqa: E402
                             context, read)

NEW = {"search.step_price_error_pct": ("lower", "device_trace"),
       "search.memory_price_error_pct": ("lower", "program_counter"),
       "search.priced_within_2x_share_pct": ("higher", "device_trace")}
LAST_ACCEPTED = "kernels.causal_flash_roofline"     # PR 48's

# seconds a step, against `test_step_parts.device()`: a step's 10 ms of
# `op_rmsnorm` forward, 20 + 20 of the experts, 15 of the head's backward,
# 20 of the update, 5 of the loss, 5 without a part; busy 97.5 ms
PRICES = dict(
    step_s=0.130, fwd_s=0.060, bwd_s=0.0375, comm_s=0.0, gradsync_s=0.004,
    update_s=0.045, hidden_comm_s=0.003, memory_bytes=12e9,
    search_predicted_s=0.125, search_predicted_memory_bytes=13e9,
    cost_sources=dict(analytic=7),
    by_part=[["op_rmsnorm", "forward", 0.008, 2],      # of 10: within
             ["experts", "forward", 0.050, 1],         # of 20: beyond
             ["experts", "backward", 0.030, 1],        # of 20: within
             ["head", "forward", 0.002, 1],            # no such events
             ["head", "backward", 0.0075, 1],          # of 15: on the edge
             ["optimizer_update", "optimizer", 0.045, 1],      # of 20: beyond
             ["collectives", "gradsync", 0.004, 3, 0.003]])
HEADER = dict(kind="events", clock_shift_us=0.0, device_peak_bytes=10e9,
              device_peak_bytes_in_use=4e9)


def write_session(cell_name, prices=PRICES, header=HEADER):
    from benchmarks import session_reduce as sr
    where = sr.out_dir(ROOT, cell_name)
    os.makedirs(where, exist_ok=True)
    body = dict(header=dict(kind="step_scopes"), instructions=TABLE)
    if prices is not None:
        body["prices"] = prices
    with open(os.path.join(where, "session_r00_host00.step_scopes.json"),
              "w") as f:
        json.dump(body, f)
    with open(os.path.join(where, "session_r00_host00.events.jsonl"),
              "w") as f:
        f.write(json.dumps(header) + "\n")
    return where


def test_the_three_entries_come_after_the_accepted_ones():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert len(ALL_CELLS) == 10
    for name, (better, source) in NEW.items():
        m = by_name[name]
        assert names.index(name) > names.index(LAST_ACCEPTED)
        assert m["workloads"] == ALL_CELLS
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", better, source, "search", "throughput")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert by_name["search.search_s"]["layer"] == "search"


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_gives_the_joins_number(name, tmp_path, monkeypatch):
    send_output_to(monkeypatch, tmp_path)
    write_session(CELL)
    want = {"search.step_price_error_pct": 100 * (130 - 97.5) / 97.5,
            "search.memory_price_error_pct": 20.0,
            "search.priced_within_2x_share_pct": 100 * (10 + 20 + 15) / 97.5}
    assert read(name, context(CELL)) == pytest.approx(want[name])


def test_the_first_reader_leaves_the_join(tmp_path, monkeypatch):
    send_output_to(monkeypatch, tmp_path)
    where = write_session(CELL)
    read("search.priced_within_2x_share_pct", context(CELL))
    with open(os.path.join(where, "step_prices.json")) as f:
        got = json.load(f)
    assert got["cell"] == CELL
    assert [r[:2] for r in got["rows"]] == [
        ["experts", "forward"], ["experts", "backward"],
        ["optimizer_update", "optimizer"], ["head", "backward"],
        ["op_rmsnorm", "forward"]]      # the largest measured first
    rows = {(p, d): (priced, ms, ratio)
            for p, d, priced, ms, ratio in got["rows"]}
    assert rows[("experts", "forward")] == pytest.approx((50.0, 20.0, 2.5))
    assert rows[("head", "backward")] == pytest.approx((7.5, 15.0, 0.5))
    assert got["priced_only"] == [["collectives", "gradsync",
                                   pytest.approx(4.0)],
                                  ["head", "forward", pytest.approx(2.0)]]
    assert got["measured_only"] == [["loss", "forward", pytest.approx(5.0)],
                                    ["None", "none", pytest.approx(5.0)]]
    totals = got["totals"]
    assert totals["priced_step_ms"] == pytest.approx(130.0)
    assert totals["busy_ms_a_step"] == pytest.approx(97.5)
    assert totals["search_predicted_step_ms"] == pytest.approx(125.0)
    assert totals["priced_ms"] == pytest.approx(
        dict(forward=60.0, backward=37.5, optimizer=45.0))
    assert totals["measured_ms"] == pytest.approx(
        dict(forward=35.0, backward=35.0, optimizer=20.0))
    assert totals["priced_compute_joined_pct"] == pytest.approx(
        100 * 140.5 / 142.5)
    # `test_step_parts.TABLE` gives every priced part an instruction
    assert totals["priced_compute_in_table_pct"] == pytest.approx(100.0)
    assert totals["priced_gradsync_ms"] == pytest.approx(4.0)
    assert (totals["priced_memory_bytes"], totals["device_peak_bytes"],
            totals["search_predicted_memory_bytes"]) == (12e9, 10e9, 13e9)
    # the breakdown by part stands beside it, as before
    assert os.path.exists(os.path.join(where, "step_parts.json"))


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_no_prices_nothing_reported(cell, tmp_path, monkeypatch):
    """The parent's program writes a table without `prices` and a header
    without the peak, a model no search compiled likewise, a `--trace 1`
    run no session at all: every reader returns None and raises nothing,
    and no join is left."""
    send_output_to(monkeypatch, tmp_path)
    assert [read(n, context(cell)) for n in NEW] == [None] * 3
    where = write_session(cell, prices=None,
                          header=dict(kind="events", clock_shift_us=0.0))
    assert [read(n, context(cell)) for n in NEW] == [None] * 3
    assert not os.path.exists(os.path.join(where, "step_prices.json"))


def test_prices_without_a_device_lane_or_a_peak(tmp_path, monkeypatch):
    """A CPU rehearsal: no TPU lane, no allocator counters. The join is
    left (every price in `priced_only`), no number is reported."""
    send_output_to(monkeypatch, tmp_path)
    where = write_session(CELL, header=dict(
        kind="events", device_peak_bytes=None))
    ctx = dict(context(CELL), devices=[])
    assert [read(n, ctx) for n in NEW] == [None] * 3
    with open(os.path.join(where, "step_prices.json")) as f:
        got = json.load(f)
    assert got["rows"] == [] and len(got["priced_only"]) == 7
    assert got["totals"]["busy_ms_a_step"] is None


def test_a_traced_rehearsal_leaves_the_join(tmp_path, monkeypatch, capsys):
    """The cheapest tiny cell end to end: the program's session writes
    `prices` beside its table, and the readers, which the harness finds
    by name, leave `step_prices.json` (no number on the CPU)."""
    from benchmarks import session_reduce as sr
    send_output_to(monkeypatch, tmp_path)
    cell = "bert_ae.s512_b32.1chip"
    result = rehearse(cell, 2)
    assert result["correct"] is True
    assert not set(NEW) & set(result["metrics"])
    session = sr.load(sr.out_dir(ROOT, cell))
    assert session.header["search_predicted_step_s"] > 0
    assert session.header["step_prices_s"] > 0
    assert "device_peak_bytes" in session.header
    with open(os.path.join(sr.out_dir(ROOT, cell), "step_prices.json")) as f:
        got = json.load(f)
    parts = {p for p, _, _ in got["priced_only"]}
    assert {"attention", "op_linear", "head", "optimizer_update"} <= parts
    assert got["totals"]["priced_step_ms"] > 0
    assert got["totals"]["priced_memory_bytes"] > 0
    assert got["totals"]["priced_ms"]["forward"] == pytest.approx(
        sum(ms for _, d, ms in got["priced_only"] if d == "forward"))
