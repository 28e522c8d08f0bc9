"""The readers of the session's spans (`benchmarks/session_reduce.py`, six
files under `benchmarks/layer_metrics/`) on a hand-made span file: alone,
over a hand-made device trace with round numbers, and over the trace the
v5e recorded (`benchmarks/fixtures/`). Nothing here is a device number of
this run."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks import session_reduce as sr  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")
CELL = "bert_ae.s512_b32.1chip"
SHIFT_US = 5000.0    # tracer timeline = profiler clock + 5 ms
NEW = ("input.stage_ms", "executor.host_step_ms", "executor.epoch_gap_ms",
       "device.idle_staging_pct", "device.idle_unnamed_pct",
       "compile.param_init_s")

# (name, start ms, end ms, children) on the profiler's clock: two `fit`
# calls of one step each around the fixture's two train steps (51.283 to
# 83.686 ms and 120.762 to 153.170 ms), 5 ms apart
CALLS = [
    ("fit", 40.0, 95.0, [
        ("fit_setup", 40.0, 41.0, []),
        ("step", 45.0, 52.0, [
            ("data_load", 45.0, 45.5, []),
            ("device_put", 45.5, 50.0, []),
            ("rng_split", 50.0, 50.4, []),
            ("dispatch", 50.5, 51.0, []),
            ("metric_accumulate", 51.0, 51.1, [])]),
        ("metrics_sync", 60.0, 94.0, [])]),
    ("fit", 100.0, 160.0, [
        ("fit_setup", 100.0, 101.0, []),
        ("step", 101.0, 121.0, [
            ("data_load", 101.0, 102.0, []),
            ("device_put", 102.0, 118.0, []),
            ("rng_split", 118.0, 119.0, []),
            ("dispatch", 119.5, 120.5, []),
            ("metric_accumulate", 120.6, 120.7, [])]),
        ("metrics_sync", 125.0, 159.0, [])]),
]


def rows_of(calls):
    rows = []

    def walk(node, parent, call):
        name, start, end, children = node
        me = len(rows)
        rows.append(dict(name=name, ts=start * 1e3 + SHIFT_US,
                         dur=(end - start) * 1e3, id=me, parent=parent,
                         call=me if call is None else call))
        for child in children:
            walk(child, me, rows[me]["call"])

    for node in calls:
        walk(node, None, None)
    return rows


@pytest.fixture
def artifact(tmp_path, monkeypatch):
    """The span file where a `--trace 2` run leaves it."""
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    directory = sr.out_dir(ROOT, CELL)
    os.makedirs(directory)
    header = dict(record="header", clock_shift_us=SHIFT_US,
                  compile_phases=dict(search_s=13.0, executor_build_s=1.0,
                                      lint_s=0.0, param_init_s=6.5,
                                      state_placement_s=0.25))
    with open(os.path.join(directory, "session_r00_host00.events.jsonl"),
              "w") as f:
        for row in [header] + rows_of(CALLS):
            f.write(json.dumps(row) + "\n")
    return directory


def read(metric, devices):
    ctx = dict(devices=devices, cell=dict(name=CELL), counters={})
    return harness.load_by_path("layer_metrics", metric).read(ctx)


def test_without_an_artifact_every_reader_reports_nothing(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(sr, "OUT_DIR", str(tmp_path))
    devices = tr.load_chrome(FIXTURE)
    for metric in NEW:
        assert read(metric, devices) is None


def test_span_readers_need_no_device(artifact):
    assert read("input.stage_ms", []) == pytest.approx((4.5 + 16.0) / 2)
    assert read("executor.host_step_ms", []) == pytest.approx((7 + 20) / 2)
    assert read("compile.param_init_s", []) == pytest.approx(6.75)
    for metric in ("executor.epoch_gap_ms", "device.idle_staging_pct",
                   "device.idle_unnamed_pct"):
        assert read(metric, []) is None


def test_an_untied_session_gives_no_device_reading(artifact):
    path = os.path.join(artifact, "session_r00_host00.events.jsonl")
    lines = open(path).read().splitlines()
    header = json.loads(lines[0])
    del header["clock_shift_us"]
    open(path, "w").write("\n".join([json.dumps(header)] + lines[1:]) + "\n")
    devices = tr.load_chrome(FIXTURE)
    assert read("device.idle_staging_pct", devices) is None
    assert read("executor.epoch_gap_ms", devices) is None
    assert read("input.stage_ms", devices) is not None


def handmade_device():
    """Two train steps, 50 to 80 and 120.8 to 150 ms, busy throughout but
    for 1 ms in the first (60 to 61); one helper op at 105 ms."""
    ms = 1e-3
    ops = [("fusion.1", 50 * ms, 10 * ms), ("fusion.2", 61 * ms, 19 * ms),
           ("add.3", 105 * ms, 0.5 * ms), ("fusion.1", 120.8 * ms, 29.2 * ms)]
    modules = [("jit_train_step(1)", 50 * ms, 30 * ms),
               ("jit_add(2)", 105 * ms, 0.5 * ms),
               ("jit_train_step(1)", 120.8 * ms, 29.2 * ms)]
    return tr.Device("/device:TPU:0", {tr.OPS: ops, tr.MODULES: modules})


def test_idle_time_by_span_on_round_numbers(artifact):
    dev = handmade_device()
    session = sr.load(artifact)
    assert sr.tied(session)
    got, window = sr.idle_by_span(dev, session.spans)
    assert window == pytest.approx(0.100)
    want = {"idle_in_metrics_sync": 1 + 14,      # 60-61, 80-94
            "idle_in_fit": 1,                     # 94-95
            sr.BETWEEN_CALLS: 5,                  # 95-100
            "idle_in_fit_setup": 1,
            "idle_in_data_load": 1,
            "idle_in_device_put": 16 - 0.5,       # less the helper op
            "idle_in_rng_split": 1,
            "idle_in_step": 0.5 + 0.1 + 0.1,      # 119-119.5, 120.5-.6, .7-.8
            "idle_in_dispatch": 1,
            "idle_in_metric_accumulate": 0.1}
    assert {k: round(v * 1e3, 6) for k, v in got.items()} == pytest.approx(
        want)
    assert read("device.idle_staging_pct", [dev]) == pytest.approx(16.5)
    assert read("device.idle_unnamed_pct", [dev]) == pytest.approx(6.7)
    # 80 to 120.8 less the helper op
    assert read("executor.epoch_gap_ms", [dev]) == pytest.approx(40.3)
    assert sr.dispatch_leads_s(dev, session.spans) == pytest.approx(
        [(50 - 50.5) * 1e-3, (120.8 - 119.5) * 1e-3])
    labelled = sr.labelled_idle_gaps([dev], session.spans, n=3)
    assert [g[0] for g in labelled] == [
        "idle_in_metrics_sync", "idle_in_device_put", "idle_in_metrics_sync"]
    assert labelled[0][1] == pytest.approx(0.025)   # 80 to 105: by its middle


def test_two_devices_are_averaged(artifact):
    """The second device is idle from 80 ms to the window's end."""
    one = handmade_device()
    ms = 1e-3
    other = tr.Device("/device:TPU:1", {
        tr.OPS: [("fusion.1", 50 * ms, 30 * ms),
                 ("fusion.9", 149.9 * ms, 0.1 * ms)],
        tr.MODULES: [("jit_train_step(1)", 50 * ms, 30 * ms),
                     ("jit_train_step(1)", 120.8 * ms, 29.2 * ms)]})
    alone = read("device.idle_staging_pct", [other])
    assert alone == pytest.approx(17.0)
    assert read("device.idle_staging_pct", [one, other]) == pytest.approx(
        (16.5 + 17.0) / 2)


def test_on_the_trace_the_v5e_recorded(artifact):
    devices = tr.load_chrome(FIXTURE)
    (dev,) = devices
    session = sr.load(artifact)
    idle_pct = read("device.idle_pct", devices)
    shares = sr.idle_shares_pct(devices, session.spans)
    # the named shares, the frames' and the stretch between the calls add
    # up to the window's idle share
    assert sum(shares.values()) == pytest.approx(idle_pct, abs=1e-9)
    staging = read("device.idle_staging_pct", devices)
    unnamed = read("device.idle_unnamed_pct", devices)
    named = sum(v for k, v in shares.items()
                if k not in ("idle_in_fit", "idle_in_step", sr.BETWEEN_CALLS))
    assert named + unnamed == pytest.approx(idle_pct, abs=1e-9)
    # the device is idle from the first step's last op to the second's first
    # (83.683 to 120.811 ms), so all 17 ms of the second call's staging are
    # idle time, and so are the 5 ms between the calls
    w = tr.window(dev)
    assert staging == pytest.approx(100 * 0.017 / (w[1] - w[0]), rel=1e-9)
    assert shares[sr.BETWEEN_CALLS] == pytest.approx(
        100 * 0.005 / (w[1] - w[0]), rel=1e-9)
    assert unnamed >= shares[sr.BETWEEN_CALLS] + shares["idle_in_fit"]
    steps = tr.step_spans(dev)
    gap = read("executor.epoch_gap_ms", devices)
    assert gap == pytest.approx((steps[1][0] - steps[0][1]) * 1e3, abs=0.01)
    # every dispatch span starts before its program does
    assert all(v > 0 for v in sr.dispatch_leads_s(dev, session.spans))
    assert sr.labelled_idle_gaps(devices, session.spans, n=1)[0][0] \
        == "idle_in_device_put"
