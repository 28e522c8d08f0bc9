"""Device-trace attribution tests (flexflow_tpu/obs/devtrace, ISSUE 6).

Acceptance: a deviceless CPU ``fit(..., profile_steps=...)`` produces a
merged Perfetto trace containing device lanes plus per-step
compute/comms/exposed-comms attribution, and ``scripts/calibrate.py
--ingest-drift`` folds the measured-vs-priced collective drift into
CALIBRATION.json per-collective corrections.

The parser core is pinned by a committed fixture trace
(tests/fixtures/devtrace_small.trace.json.gz — the exact Chrome-trace
shape ``jax.profiler`` emits on the CPU backend: ``ff_step``
annotations, ``args.hlo_op`` device spans, python-tracer noise) with
hand-computed interval arithmetic the bucket math must reproduce.
"""

import glob
import gzip
import json
import os

import numpy as np
import pytest

from flexflow_tpu import (
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.ffconst import ActiMode
from flexflow_tpu.obs.devtrace import (
    attribute_steps,
    attribution_report,
    classify_hlo_op,
    extract_device_events,
    extract_step_windows,
    intersect_total,
    interval_total,
    load_chrome_trace,
    merge_intervals,
    parse_profile_steps,
)

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "devtrace_small.trace.json.gz")


def build_mlp(batch_size=32):
    ff = FFModel(FFConfig(batch_size=batch_size))
    t = ff.create_tensor((batch_size, 8))
    t = ff.dense(t, 16, activation=ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    return ff


def make_blobs(n=128, d=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.randn(classes, d) * 3
    y = rs.randint(0, classes, n)
    x = centers[y] + rs.randn(n, d)
    return x.astype(np.float32), y.astype(np.int32)


class TestParseProfileSteps:
    def test_window(self):
        assert parse_profile_steps("2:4") == (2, 4)
        assert parse_profile_steps("0:1") == (0, 1)

    def test_single_step(self):
        assert parse_profile_steps("3") == (3, 4)

    def test_unset(self):
        assert parse_profile_steps(None) is None
        assert parse_profile_steps("") is None

    def test_invalid(self):
        for bad in ("4:2", "-1:2", "a:b", "2:2"):
            with pytest.raises(ValueError):
                parse_profile_steps(bad)


class TestClassifyHloOp:
    def test_collective_kinds(self):
        for kind in ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute",
                     "collective-broadcast"):
            assert classify_hlo_op(kind) == ("collective", kind)
            assert classify_hlo_op(f"{kind}.17") == ("collective", kind)
            # async pairs keep the kind
            assert classify_hlo_op(f"{kind}-start.2") == ("collective",
                                                          kind)

    def test_host_ops(self):
        for name in ("infeed.1", "outfeed", "send.2", "recv-done",
                     "host-call.3"):
            assert classify_hlo_op(name)[0] == "host"

    def test_compute_default(self):
        for name in ("dot.4", "fusion.12", "convert.9", "copy.1",
                     "broadcast_add_fusion.clone",
                     # embedded-but-not-prefix collective substrings
                     # must NOT classify as comms
                     "fused_all_reduce_epilogue"):
            assert classify_hlo_op(name) == ("compute", None)


class TestIntervalMath:
    def test_merge(self):
        assert merge_intervals([(3, 5), (1, 2), (4, 7)]) == [(1, 2),
                                                             (3, 7)]
        assert merge_intervals([(1, 2), (2, 3)]) == [(1, 3)]
        assert merge_intervals([(1, 1), (2, 1)]) == []
        assert interval_total(merge_intervals([(0, 2), (1, 4)])) == 4

    def test_intersect(self):
        a = merge_intervals([(0, 10)])
        b = merge_intervals([(2, 4), (8, 12)])
        assert intersect_total(a, b) == 4
        assert intersect_total(b, a) == 4
        assert intersect_total(a, merge_intervals([(20, 30)])) == 0


class TestFixtureAttribution:
    """Hand-computed interval arithmetic over the committed fixture."""

    def _parsed(self):
        trace = load_chrome_trace(FIXTURE)
        return (extract_device_events(trace),
                extract_step_windows(trace))

    def test_device_events_and_noise_filter(self):
        events, windows = self._parsed()
        # 8 hlo-op spans; python-tracer frames + runtime bookkeeping
        # (no hlo args, host pid) are dropped
        assert len(events) == 8
        assert windows == {0: (1000.0, 2000.0), 1: (2000.0, 3000.0)}

    def test_step0_buckets(self):
        events, windows = self._parsed()
        rows = attribute_steps(events, windows)
        s0 = rows[0]
        assert s0["step"] == 0
        # compute: [1100,1600) u [1950,2000) = 550us (convert.9 clipped
        # at the step boundary)
        assert s0["compute_s"] == pytest.approx(550e-6)
        # comms: AR [1500,1800) + RS [1850,1950) = 400us
        assert s0["comms_s"] == pytest.approx(400e-6)
        # AR overlaps compute on [1500,1600) only
        assert s0["overlapped_comms_s"] == pytest.approx(100e-6)
        assert s0["exposed_comms_s"] == pytest.approx(300e-6)
        assert s0["host_s"] == pytest.approx(50e-6)
        assert s0["idle_s"] == pytest.approx(100e-6)
        assert s0["per_kind"]["all-reduce"]["count"] == 1
        assert s0["per_kind"]["all-reduce"]["time_s"] == pytest.approx(
            300e-6)
        assert s0["per_kind"]["reduce-scatter"]["time_s"] == pytest.approx(
            100e-6)

    def test_step1_fully_overlapped(self):
        events, windows = self._parsed()
        s1 = attribute_steps(events, windows)[1]
        assert s1["compute_s"] == pytest.approx(550e-6)
        assert s1["comms_s"] == pytest.approx(300e-6)
        # the all-gather sits entirely under dot.2: nothing exposed
        assert s1["overlapped_comms_s"] == pytest.approx(300e-6)
        assert s1["exposed_comms_s"] == pytest.approx(0.0, abs=1e-12)
        assert s1["idle_s"] == pytest.approx(450e-6)

    def test_aggregate_report(self):
        rep = attribution_report([FIXTURE])
        assert rep["steps"] == 2
        assert rep["device_events"] == 8
        assert rep["totals"]["compute_s"] == pytest.approx(1100e-6)
        assert rep["totals"]["exposed_comms_s"] == pytest.approx(300e-6)
        # per-kind measured seconds: the drift join's measured half
        coll = rep["collectives"]
        ar = coll["all-reduce"]
        assert ar["time_s"] == pytest.approx(300e-6)
        assert ar["count"] == 1
        assert ar["per_step_s"] == pytest.approx(150e-6)
        # per-kind hidden/exposed split (ISSUE 9): each kind's measured
        # time partitions into overlapped-under-compute + exposed
        for e in coll.values():
            assert e["overlapped_s"] + e["exposed_s"] == pytest.approx(
                e["time_s"])
            assert (e["overlapped_per_step_s"] + e["exposed_per_step_s"]
                    == pytest.approx(e["per_step_s"]))
        # the all-gather sits entirely under compute in the fixture
        assert coll["all-gather"]["per_step_s"] == pytest.approx(150e-6)
        assert coll["all-gather"]["exposed_per_step_s"] == pytest.approx(
            0.0, abs=1e-12)
        assert coll["reduce-scatter"]["per_step_s"] == pytest.approx(50e-6)


class TestRealTpuTrace:
    """A trace a TPU v5e wrote (two BERT-proxy train steps, chip_smoke.py,
    PR 21), thinned to every 25th op span. Its ``/device:TPU:0`` process
    has three threads: ``XLA Ops`` and two roll-ups of the same time,
    ``XLA Modules`` and ``Steps``."""

    TPU_FIXTURE = os.path.join(os.path.dirname(FIXTURE),
                               "devtrace_tpu_v5e.trace.json.gz")

    @pytest.fixture(scope="class")
    def trace(self):
        return load_chrome_trace(self.TPU_FIXTURE)

    def test_only_the_op_lane_counts(self, trace):
        lanes = {(e["pid"], e["tid"]): e["args"]["name"]
                 for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "thread_name"}
        spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        on = lambda lane: [e for e in spans
                           if lanes.get((e["pid"], e["tid"])) == lane]
        assert on("Steps") and on("XLA Modules")
        events = extract_device_events(trace)
        assert len(events) == len(on("XLA Ops")) == 446
        assert not any(ev["name"].startswith("jit_") for ev in events)

    def test_device_time_is_the_ops_not_the_step(self, trace):
        rep = attribution_report([self.TPU_FIXTURE])
        assert rep["steps"] == 2
        module_s = max(e["dur"] for e in trace["traceEvents"]
                       if e.get("ph") == "X"
                       and e["name"].startswith("jit_train_step")) / 1e6
        for row in rep["per_step"]:
            # with the roll-up lanes counted this was the whole window
            assert 0 < row["compute_s"] <= module_s < row["wall_s"]
            assert row["idle_s"] > 0


    def test_busy_time_agrees_with_the_benchmarks_reduction(self, trace):
        """One device: `attribute_window` (per device, asynchronous line
        counted) and `benchmarks/trace_reduce.py` give the same busy
        time over the same window."""
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if root not in sys.path:
            sys.path.insert(0, root)
        from benchmarks import trace_reduce as tr
        from flexflow_tpu.obs.devtrace import attribute_window
        (dev,) = tr.load_chrome(self.TPU_FIXTURE)
        busy_s, window_s = tr.busy_and_window(dev)
        t0, t1 = tr.window(dev)
        events = extract_device_events(trace)
        assert {ev["device"] for ev in events} == {
            e["pid"] for e in trace["traceEvents"]
            if e.get("name") == "process_name"
            and e["args"]["name"].startswith("/device:")}
        row = attribute_window(events, t0 * 1e6, t1 * 1e6)
        assert row["wall_s"] == pytest.approx(window_s, rel=1e-9)
        assert row["busy_s"] == pytest.approx(busy_s, rel=1e-9)
        assert row["idle_s"] == pytest.approx(window_s - busy_s, rel=1e-9)


def _two_device_trace():
    """Device 1 computes in the first half of a 100 us step, device 2 in
    the second half and copies asynchronously for 10 us of its first."""
    meta = []
    for pid in (1, 2):
        meta += [dict(ph="M", name="process_name", pid=pid,
                      args=dict(name=f"/device:TPU:{pid - 1}")),
                 dict(ph="M", name="thread_name", pid=pid, tid=1,
                      args=dict(name="XLA Ops")),
                 dict(ph="M", name="thread_name", pid=pid, tid=2,
                      args=dict(name="Async XLA Ops")),
                 dict(ph="M", name="thread_name", pid=pid, tid=3,
                      args=dict(name="XLA Modules"))]
    spans = [dict(ph="X", name="fusion.1", pid=1, tid=1, ts=0.0, dur=50.0),
             dict(ph="X", name="fusion.1", pid=2, tid=1, ts=50.0, dur=50.0),
             dict(ph="X", name="copy-start.1", pid=2, tid=2, ts=20.0,
                  dur=10.0),
             dict(ph="X", name="all-gather-start.1", pid=1, tid=2, ts=40.0,
                  dur=20.0),
             dict(ph="X", name="jit_train_step(1)", pid=1, tid=3, ts=0.0,
                  dur=100.0)]
    return dict(traceEvents=meta + spans)


class TestPerDeviceReduction:
    def test_devices_are_reduced_apart_then_averaged(self):
        events = extract_device_events(_two_device_trace())
        assert {(e["device"], e["bucket"]) for e in events} == {
            (1, "compute"), (2, "compute"), (2, "async"),
            (1, "collective")}
        (row,) = attribute_steps(events, {0: (0.0, 100.0)})
        # a union over the devices would call the step fully busy
        assert row["compute_s"] == pytest.approx(50e-6)
        assert row["busy_s"] == pytest.approx((60e-6 + 60e-6) / 2)
        assert row["idle_s"] == pytest.approx(40e-6)
        # device 1's all-gather: 10 us under its compute, 10 us exposed;
        # device 2 ran none, so the mean halves both
        assert row["comms_s"] == pytest.approx(10e-6)
        assert row["exposed_comms_s"] == pytest.approx(5e-6)
        assert row["per_kind"]["all-gather"]["exposed_s"] \
            == pytest.approx(5e-6)
        assert row["per_kind"]["all-gather"]["count"] == 0.5


class TestRegistryReservoir:
    def test_percentiles_bounded_memory(self):
        from flexflow_tpu.obs.registry import (RESERVOIR_SIZE,
                                               CounterRegistry)
        r = CounterRegistry()
        for i in range(2000):
            r.observe("lat", float(i))
        o = r.to_dict()["observations"]["lat"]
        # streaming summary is exact
        assert o["count"] == 2000.0
        assert o["min"] == 0.0 and o["max"] == 1999.0
        # reservoir percentiles approximate the uniform stream
        assert 600 < o["p50"] < 1400
        assert o["p99"] > o["p50"]
        assert len(r._samples["lat"]) <= RESERVOIR_SIZE

    def test_small_series_exact(self):
        from flexflow_tpu.obs.registry import CounterRegistry
        r = CounterRegistry()
        for v in (1.0, 2.0, 3.0, 4.0):
            r.observe("x", v)
        o = r.to_dict()["observations"]["x"]
        assert o["p50"] == 2.0
        assert o["p99"] == 4.0


class TestMergeClockAlignment:
    """Satellite: per-host traces stamp a shared wall-clock epoch and
    merge shifts events onto it — including devtrace lanes."""

    def test_cross_host_shift_and_lane_rows(self, tmp_path):
        from flexflow_tpu.obs.tracer import StepTracer, merge_host_traces
        td = str(tmp_path)
        trs = []
        for host in (0, 1):
            tr = StepTracer(td, host_id=host, run_name="fit")
            trs.append(tr)
        # same monotonic-relative event on both hosts, but host 1's
        # clock pair says it STARTED 0.25s later in wall time
        trs[1]._wall_origin = trs[0]._wall_origin + 0.25
        for tr in trs:
            with tr.step():
                pass
        # host 0 also carries a devtrace lane event
        trs[0].add_trace_events(
            [dict(name="dot.1", ph="X", tid=64, ts=100.0, dur=10.0,
                  cat="devtrace")],
            {64: "device:compute"})
        for tr in trs:
            assert tr._clock_pair_spread_us >= 0.0
            tr.export()
        data = json.load(open(merge_host_traces(td)))
        steps = {e["pid"]: e for e in data["traceEvents"]
                 if e.get("name") == "step" and e.get("ph") == "X"}
        # host 1's step shifted ~0.25s later on the merged timeline
        assert steps[1]["ts"] - steps[0]["ts"] == pytest.approx(
            0.25e6, rel=0.05)
        # the device lane kept its own thread row, labeled through
        labels = {(e["pid"], e["tid"]): e["args"]["name"]
                  for e in data["traceEvents"]
                  if e.get("name") == "thread_name"}
        lane = [e for e in data["traceEvents"] if e.get("name") == "dot.1"]
        assert len(lane) == 1
        assert labels[(0, lane[0]["tid"])].endswith(":device:compute")
        assert lane[0]["tid"] != steps[0]["tid"]


class TestProfiledFit:
    """The acceptance path: deviceless CPU fit with --profile-steps."""

    @pytest.fixture(scope="class")
    def profiled_run(self, tmp_path_factory):
        # run_t1.sh points FFS_T1_TRACE_DIR at a stable dir so its obs
        # stage can render OBS_REPORT.json from this run's artifacts
        td = os.environ.get("FFS_T1_TRACE_DIR") or str(
            tmp_path_factory.mktemp("devtrace"))
        os.makedirs(td, exist_ok=True)
        x, y = make_blobs()
        ff = build_mlp()
        ff.fit(x, y, epochs=2, verbose=False, trace_dir=td,
               profile_steps="2:4")
        return td, ff

    def _one(self, td, pattern):
        paths = glob.glob(os.path.join(td, pattern))
        assert len(paths) >= 1, f"{pattern}: {paths}"
        return paths[0]

    def test_devtrace_artifact(self, profiled_run):
        td, _ = profiled_run
        dv = json.load(open(self._one(td, "fit_*.devtrace.json")))
        assert dv["window"] == [2, 4]
        assert dv["steps"] == 2
        for row in dv["per_step"]:
            for key in ("compute_s", "comms_s", "overlapped_comms_s",
                        "exposed_comms_s", "host_s", "idle_s", "wall_s"):
                assert key in row
            assert row["compute_s"] > 0
            # dp=8 over the virtual CPU mesh: the grad sync is real
            assert row["comms_s"] > 0
            assert row["exposed_comms_s"] + row["overlapped_comms_s"] == \
                pytest.approx(row["comms_s"])
        assert dv["collectives"]["all-reduce"]["count"] > 0
        assert dv["collectives"]["all-reduce"]["per_step_s"] > 0

    def test_device_lanes_in_trace(self, profiled_run):
        td, _ = profiled_run
        trace = json.load(open(self._one(td, "fit_*.trace.json")))
        events = trace["traceEvents"]
        lanes = {e["args"]["name"] for e in events
                 if e.get("name") == "thread_name"}
        assert {"train_loop", "device:compute", "device:comms"} <= lanes
        comms = [e for e in events if e.get("cat") == "devtrace"
                 and (e.get("args") or {}).get("kind") == "all-reduce"]
        assert comms, "no all-reduce spans on the device lane"
        # per-step attribution counter track
        counters = [e for e in events
                    if e.get("name") == "step_attribution"
                    and e.get("ph") == "C"]
        assert len(counters) == 2
        assert "exposed_comms_ms" in counters[0]["args"]
        # device lanes rebased onto the tracer timeline: each lane span
        # falls inside the host-side span of SOME step
        step_spans = [(e["ts"], e["ts"] + e["dur"]) for e in events
                      if e.get("name") == "step" and e.get("ph") == "X"]
        mid = comms[0]["ts"] + comms[0]["dur"] / 2
        assert any(s - 1e3 <= mid <= e + 1e3 for s, e in step_spans)

    def test_merged_trace_keeps_lanes(self, profiled_run):
        td, _ = profiled_run
        from flexflow_tpu.obs import merge_host_traces
        merged = merge_host_traces(td)
        assert merged is not None
        data = json.load(open(merged))
        labels = {e["args"]["name"] for e in data["traceEvents"]
                  if e.get("name") == "thread_name"}
        assert any(l.endswith(":device:compute") for l in labels)
        assert any(l.endswith(":device:comms") for l in labels)

    def test_drift_report_collective_join(self, profiled_run):
        td, _ = profiled_run
        rep = json.load(open(self._one(td, "fit_*.drift.json")))
        cd = rep["collective_drift"]
        assert "all-reduce" in cd
        assert cd["all-reduce"]["measured_s"] > 0
        assert cd["all-reduce"]["predicted_s"] > 0
        assert cd["all-reduce"]["ratio"] > 0
        sm = rep["step_metrics"]
        assert 0 < sm["goodput"] <= 1.0
        assert sm["mfu"] > 0
        assert sm["step_time_p50"] <= sm["step_time_p99"]

    def test_registry_histograms(self, profiled_run):
        td, _ = profiled_run
        counters = json.load(open(self._one(td, "fit_*.counters.json")))
        obs = counters["observations"]
        st = obs["fit/step_time_s"]
        assert st["count"] >= 7  # 8 steps minus the jit-carrying first
        assert st["p50"] <= st["p99"]
        assert "fit/devtrace_exposed_comms_s" in obs
        assert counters["gauges"]["fit/goodput"] > 0

    def test_obs_report_renders(self, profiled_run, tmp_path):
        td, _ = profiled_run
        import importlib.util
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "obs_report", os.path.join(repo, "scripts", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = str(tmp_path / "OBS_REPORT.json")
        md = str(tmp_path / "OBS_REPORT.md")
        assert mod.main([td, "--out", out, "--md", md]) == 0
        report = json.load(open(out))
        runs = {r["run_name"]: r for r in report["runs"]}
        assert "fit" in runs
        r = runs["fit"]
        assert r["step_time_p50_s"] > 0
        assert r["devtrace"]["exposed_comms_frac"] >= 0
        assert "all-reduce" in r["collective_drift"]
        assert "Measured vs priced collectives" in open(md).read()

    def test_obs_report_empty_dir_nonfatal(self, tmp_path):
        import importlib.util
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "obs_report2", os.path.join(repo, "scripts", "obs_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = str(tmp_path / "empty" / "OBS_REPORT.json")
        assert mod.main([str(tmp_path / "empty"), "--out", out]) == 0
        assert json.load(open(out))["runs"] == []

    def test_drift_rows_marked_uningestable_on_cpu(self, profiled_run):
        # deviceless capture: the measured half is host-CPU wall time,
        # the predicted half analytic ICI — the rows must carry
        # ingestable: false so calibration never eats the 400-600x
        # backend-mismatch "drift"
        td, _ = profiled_run
        rep = json.load(open(self._one(td, "fit_*.drift.json")))
        for row in rep["collective_drift"].values():
            assert row["ingestable"] is False

    def test_ingest_skips_cpu_collective_drift(self, profiled_run,
                                               tmp_path, monkeypatch,
                                               capsys):
        """CPU-platform collective-drift rows are skipped with a warning
        by calibrate.py --ingest-drift: no collective_corrections bucket
        is derived from a deviceless run (op_corrections, which ARE
        platform-meaningful, still land in the cpu bucket)."""
        import importlib.util
        td, _ = profiled_run
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "calibrate", os.path.join(repo, "scripts", "calibrate.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        fake_repo = tmp_path / "repo"
        (fake_repo / "scripts").mkdir(parents=True)
        monkeypatch.setattr(mod.os.path, "abspath",
                            lambda p: str(fake_repo / "scripts" / "x.py"))
        assert mod.ingest_drift(td) == 0
        cal = json.load(open(fake_repo / "CALIBRATION.json"))
        assert "cpu" not in (cal.get("collective_corrections") or {})
        assert "cpu" in cal["op_corrections"]
        assert "non-ingestable collective-drift" in capsys.readouterr().out

    def test_ingest_chip_collective_drift_still_lands(self, tmp_path,
                                                      monkeypatch):
        """A TPU-platform drift report (ingestable rows) still derives
        per-kind collective corrections — the skip is CPU-only."""
        import importlib.util
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "calibrate2", os.path.join(repo, "scripts", "calibrate.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        td = tmp_path / "trace"
        td.mkdir()
        rep = dict(
            header=dict(run_name="fit", platform="tpu"),
            predicted=dict(total_s=1e-3), measured=dict(step_s=1.2e-3),
            ratio=1.2, per_op=[],
            collective_drift={"all-reduce": dict(
                predicted_s=1e-4, measured_s=1.3e-4, ratio=1.3,
                ingestable=True)})
        (td / "fit_r00_host00.drift.json").write_text(json.dumps(rep))
        fake_repo = tmp_path / "repo"
        (fake_repo / "scripts").mkdir(parents=True)
        monkeypatch.setattr(mod.os.path, "abspath",
                            lambda p: str(fake_repo / "scripts" / "x.py"))
        assert mod.ingest_drift(str(td)) == 0
        cal = json.load(open(fake_repo / "CALIBRATION.json"))
        corr = cal["collective_corrections"]["tpu"]
        assert corr["all-reduce"]["factor"] == pytest.approx(1.3)

    def test_profile_without_trace_dir_degrades(self, capsys):
        # --profile-steps without --trace-dir must warn and train, not
        # raise mid-fit
        x, y = make_blobs(64)
        ff = build_mlp()
        ff.fit(x, y, epochs=1, verbose=False, profile_steps="0:1")
        assert "profiling skipped" in capsys.readouterr().err


class TestCollectiveCorrectionHook:
    """The machine-model side of the drift closure: measured per-kind
    factors scale collective_time (the wus_rs/ag_time measured hook)."""

    def test_factor_scales_kind(self):
        from flexflow_tpu.machine import MachineSpec
        spec = MachineSpec(chip="tpu-v5e", chips_per_slice=4)
        b = 1 << 20
        base_ar = spec.collective_time("all-reduce", b, 4)
        base_ag = spec.collective_time("all-gather", b, 4)
        spec.collective_corrections = {"all-reduce": 2.0}
        assert spec.collective_time("all-reduce", b, 4) == pytest.approx(
            2.0 * base_ar)
        # uncalibrated kinds are untouched
        assert spec.collective_time("all-gather", b, 4) == pytest.approx(
            base_ag)

    def test_drift_ratio_from_uncorrected_base(self):
        # a run priced with corrections already applied must re-derive
        # the ABSOLUTE factor (measured / uncorrected-analytic), not the
        # ~1.0 residual — otherwise re-ingest would un-calibrate
        from flexflow_tpu.obs.drift import collective_drift
        pred = {"all-reduce": dict(predicted_s=2e-3,
                                   predicted_uncorrected_s=1e-3)}
        meas = {"all-reduce": dict(per_step_s=2e-3)}
        cd = collective_drift(pred, meas)
        assert cd["all-reduce"]["ratio"] == pytest.approx(2.0)
        assert cd["all-reduce"]["predicted_s"] == pytest.approx(2e-3)

    def test_loader_platform_bucketed(self, tmp_path):
        from flexflow_tpu.machine import load_collective_corrections
        cal = tmp_path / "CALIBRATION.json"
        cal.write_text(json.dumps(dict(collective_corrections=dict(
            tpu={"all-reduce": dict(factor=1.3, weight=0.9),
                 "reduce-scatter": dict(factor=0.8, weight=0.4)},
            cpu={"all-reduce": dict(factor=500.0, weight=1.0)}))))
        corr = load_collective_corrections("tpu", path=str(cal))
        assert corr == {"all-reduce": 1.3, "reduce-scatter": 0.8}
        assert load_collective_corrections("v5e", path=str(cal)) == {}
        assert load_collective_corrections(
            "tpu", path=str(tmp_path / "missing.json")) == {}
