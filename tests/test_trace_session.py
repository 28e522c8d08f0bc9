"""The process-wide trace session (flexflow_tpu/obs/session.py).

`obs.start_trace` / `obs.stop_trace` around several `fit` calls: one
artifact, spans with ids and parents on one timeline, nothing fenced,
nothing compiled at the end; no session, no tracer; the clock tie on a
CPU profiler trace; `compile_phases` in the header.
"""

import functools
import glob
import json
import os
import time

import pytest

import jax

from flexflow_tpu import obs
from flexflow_tpu.obs import session as obs_session
from flexflow_tpu.obs import tracer as obs_tracer

from test_observability import build_mlp, make_blobs

STEPS = 4   # 128 samples / batch 32


@pytest.fixture(scope="module")
def model():
    x, y = make_blobs()
    ff = build_mlp()
    ff.fit(x, y, epochs=1, verbose=False)   # compiles the step
    ff.evaluate(x, y)
    return ff, x, y


@pytest.fixture
def no_open_session():
    yield
    if obs.session_tracer() is not None:
        obs.stop_trace()


def read_events(path):
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return rows[0], rows[1:]


@pytest.fixture(scope="module")
def session_run(model, tmp_path_factory):
    """Two `fit` calls and an `evaluate` inside one session, no profiler."""
    ff, x, y = model
    td = str(tmp_path_factory.mktemp("session"))
    before = obs.get_registry().to_dict()["counters"]
    obs.start_trace(td, device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    time.sleep(0.01)   # a stretch between the calls
    ff.fit(x, y, epochs=2, verbose=False, trace_dir=os.path.join(td, "own"))
    ff.evaluate(x, y)
    paths = obs.stop_trace()
    after = obs.get_registry().to_dict()["counters"]
    header, events = read_events(paths["events"])
    return dict(td=td, paths=paths, header=header, events=events,
                before=before, after=after)


class TestSessionArtifact:
    def test_one_artifact_for_all_calls(self, session_run):
        td, paths = session_run["td"], session_run["paths"]
        assert paths["xplane"] is None
        assert sorted(os.listdir(td)) == sorted(
            os.path.basename(paths[k])
            for k in ("trace", "events", "counters"))
        # no report of the per-call form: no recompile, no replay, no drift
        assert not glob.glob(os.path.join(td, "**", "*.summary.json"),
                             recursive=True)
        assert session_run["header"]["run_name"] == "session"

    def test_fit_spans_ids_and_parents(self, session_run):
        events = session_run["events"]
        by_id = {e["id"]: e for e in events}
        assert len(by_id) == len(events)
        fits = [e for e in events if e["name"] == "fit"]
        assert len(fits) == 2
        for f in fits:
            assert f["parent"] is None and f["call"] == f["id"]
        assert fits[0]["args"] == dict(epochs=1, steps=STEPS)
        assert fits[1]["args"] == dict(epochs=2, steps=2 * STEPS)
        parents = {"fit_setup": "fit", "step": "fit", "metrics_sync": "fit",
                   "data_load": "step", "device_put": "step",
                   "rng_split": "step", "dispatch": "step",
                   "metric_accumulate": "step"}
        train = [e for e in events if e["call"] in {f["id"] for f in fits}]
        assert {e["name"] for e in train} == set(parents) | {"fit"}
        for e in train:
            if e["name"] == "fit":
                continue
            parent = by_id[e["parent"]]
            assert parent["name"] == parents[e["name"]], e
            assert parent["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3
        steps = [e for e in train if e["name"] == "step"]
        assert [e["step"] for e in sorted(steps, key=lambda e: e["ts"])] \
            == list(range(3 * STEPS))
        put = next(e for e in train if e["name"] == "device_put")
        # every array of the step was a host array, handed over raw
        assert put["args"] == dict(bytes=32 * (8 + 1) * 4,
                                   raw_bytes=32 * (8 + 1) * 4)

    def test_raw_bytes_leave_out_what_was_on_the_device(self, model, tmp_path,
                                                        no_open_session):
        ff, x, y = model
        obs.start_trace(str(tmp_path), device=False)
        ff.fit(jax.numpy.asarray(x), y, epochs=1, verbose=False)
        _, events = read_events(obs.stop_trace()["events"])
        puts = [e["args"] for e in events if e["name"] == "device_put"]
        assert puts == [dict(bytes=32 * (8 + 1) * 4,
                             raw_bytes=32 * 1 * 4)] * STEPS

    def test_stretch_between_calls_is_on_the_timeline(self, session_run):
        fits = sorted((e for e in session_run["events"]
                       if e["name"] == "fit"), key=lambda e: e["ts"])
        gap_us = fits[1]["ts"] - (fits[0]["ts"] + fits[0]["dur"])
        assert 10e3 <= gap_us < 1e6   # the 10 ms slept between the calls
        setup = [e for e in session_run["events"]
                 if e["name"] == "fit_setup"]
        assert [s["ts"] for s in setup] == [f["ts"] for f in fits]

    def test_evaluate_records_into_the_session(self, session_run):
        events = session_run["events"]
        (ev,) = [e for e in events if e["name"] == "evaluate"]
        inside = [e for e in events if e["call"] == ev["id"] and e is not ev]
        assert {e["name"] for e in inside} == {"step", "device_put",
                                               "dispatch", "metrics_sync"}

    def test_trace_dir_inside_a_session_is_the_sessions(self, session_run):
        assert not os.path.exists(os.path.join(session_run["td"], "own"))

    def test_header_holds_compile_phases(self, session_run):
        header = session_run["header"]
        assert set(header["compile_phases"]) == {
            "search_s", "executor_build_s", "lint_s", "param_init_s",
            "state_placement_s"}
        assert header["compile_phases"]["param_init_s"] > 0
        assert header["set_parameter_s"] == 0.0
        assert "clock_shift_us" not in header   # no profiler, no tie

    def test_counters_snapshot_is_the_registrys(self, session_run):
        """The session writes the process registry as it stands and adds
        no counter of its own: a call's totals are its span's args."""
        assert set(session_run["after"]) == set(session_run["before"])
        snapshot = json.load(open(session_run["paths"]["counters"]))
        assert snapshot["counters"] == session_run["after"]
        assert "executor.train_step_jits" in snapshot["counters"]


class CountingFence:
    def __init__(self, monkeypatch):
        self.calls = 0
        self.real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready", self)

    def __call__(self, x):
        self.calls += 1
        return self.real(x)


class TestNoFence:
    def test_a_session_records_no_fence(self, model, tmp_path, monkeypatch,
                                        no_open_session):
        ff, x, y = model
        fence = CountingFence(monkeypatch)
        obs.start_trace(str(tmp_path), device=False)
        ff.fit(x, y, epochs=2, verbose=False)
        _, events = read_events(obs.stop_trace()["events"])
        names = [e["name"] for e in events]
        assert names.count("dispatch") == 2 * STEPS
        assert "device_wait" not in names
        assert fence.calls == 0

    def test_the_per_call_form_still_fences_every_step(
            self, model, tmp_path, monkeypatch):
        ff, x, y = model
        fence = CountingFence(monkeypatch)
        ff.fit(x, y, epochs=1, verbose=False, trace_dir=str(tmp_path))
        (path,) = glob.glob(str(tmp_path / "fit_*.events.jsonl"))
        _, events = read_events(path)
        names = [e["name"] for e in events]
        assert names.count("device_wait") == names.count("dispatch") == STEPS
        assert fence.calls >= STEPS
        assert glob.glob(str(tmp_path / "fit_*.summary.json"))


class Compiles:
    """Counts what JAX lowers and compiles from now on."""

    def __init__(self):
        self.n = 0
        self.on = True
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and ("compile" in event or "lower" in event
                        or "trace" in event):
            self.n += 1


def test_stop_trace_neither_lowers_nor_compiles(model, tmp_path,
                                                no_open_session):
    ff, x, y = model
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    jits = obs.get_registry().get("executor.train_step_jits")
    seen = Compiles()
    try:
        paths = obs.stop_trace()
    finally:
        seen.on = False
    assert seen.n == 0
    assert paths["step_scopes"] is None
    assert obs.get_registry().get("executor.train_step_jits") == jits
    seen.on = True   # the control: the listener does see a compile
    jax.jit(lambda a: a + 1)(1.0)
    seen.on = False
    assert seen.n > 0


def test_stop_trace_with_the_profiler_lowers_the_step_once_after_it(
        model, tmp_path, monkeypatch, no_open_session):
    """`device=True`: the session keeps the shapes of the first
    dispatched train step (and of no later one) and `stop_trace` lowers
    the step once for its join table, after the profiler has stopped."""
    ff, x, y = model
    order = []
    stop_profiler = obs_session.stop_profiler
    monkeypatch.setattr(obs_session, "stop_profiler",
                        lambda: (stop_profiler(), order.append("profiler")))
    session = obs.start_trace(str(tmp_path), device=True)
    ff.fit(x, y, epochs=1, verbose=False)
    kept = dict(session.step_scopes._steps)
    assert list(kept) == [id(ff.executor)]
    model_kept, step, args = kept[id(ff.executor)]
    assert model_kept is ff     # whose executed strategy is priced
    assert step is ff.executor.make_train_step()
    # shapes only: nothing of the call's arrays stays alive
    assert all(isinstance(a, jax.ShapeDtypeStruct)
               for a in jax.tree.leaves(args))
    ff.fit(x, y, epochs=1, verbose=False)
    assert session.step_scopes._steps[id(ff.executor)][2] is args
    jits = obs.get_registry().get("executor.train_step_jits")
    monkeypatch.setattr(
        type(session.step_scopes), "write", functools.partialmethod(
            lambda self, *a, _write=type(session.step_scopes).write, **kw: (
                order.append("table"), _write(self, *a, **kw))[1]))

    def seen(event, duration, **_):
        if event.endswith("jaxpr_to_mlir_module_duration"):
            order.append("lowering")

    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        paths = obs.stop_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    # (JAX answers the lowering from its caches where the shapes are
    # those of the call that ran: then it is not even one)
    assert order in (["profiler", "table"],
                     ["profiler", "table", "lowering"])
    assert os.path.exists(paths["step_scopes"])
    assert obs.get_registry().get("executor.train_step_jits") == jits
    header, _ = read_events(paths["events"])
    assert header["step_scopes_s"] > 0
    assert header["step_scopes_instructions"] > 100


def test_no_session_no_tracer(model, monkeypatch):
    ff, x, y = model

    def refuse(*a, **kw):
        raise AssertionError("an untraced fit made a tracer")

    monkeypatch.setattr(obs_tracer.StepTracer, "__init__", refuse)
    assert obs.session_tracer() is None
    assert ff._make_tracer(None, "fit") is obs.NULL_TRACER
    ff.fit(x, y, epochs=1, verbose=False)
    ff.evaluate(x, y)


def test_one_session_at_a_time(tmp_path, no_open_session):
    with pytest.raises(RuntimeError, match="no trace session"):
        obs.stop_trace()
    obs.start_trace(str(tmp_path), device=False)
    with pytest.raises(RuntimeError, match="already open"):
        obs.start_trace(str(tmp_path), device=False)
    obs.stop_trace()
    assert obs.session_tracer() is None


class TestClockTie:
    def test_shift_from_bracketed_markers(self):
        origin = 100.0
        # three host events at 1, 2 and 3 ms after the origin, which the
        # profiler stamped 250 us earlier on its own clock
        brackets = [(origin + t - 1e-6, origin + t + 1e-6)
                    for t in (1e-3, 2e-3, 3e-3)]
        stamped = [750.0, 1750.5, 2749.5]
        shift, spread = obs_session.clock_shift_us(origin, brackets, stamped)
        assert shift == pytest.approx(250.0, abs=1e-6)
        assert spread == pytest.approx(1.0, abs=1e-6)
        assert obs_session.clock_shift_us(origin, [], []) == (None, None)

    def test_tie_on_a_cpu_profiler_trace(self, model, tmp_path,
                                         no_open_session):
        """A marker of the test's own, bracketed by `perf_counter` like the
        session's, lands where the header's shift says it should."""
        ff, x, y = model
        session = obs.start_trace(str(tmp_path), device=True)
        ff.fit(x, y, epochs=1, verbose=False)
        p0, p1 = obs_session.tie_marker("test_probe_mark")
        ff.fit(x, y, epochs=1, verbose=False)
        paths = obs.stop_trace()
        header, events = read_events(paths["events"])
        assert header["clock_tie_markers"] == 2 * obs_session.TIE_MARKERS
        assert header["xplane"] == os.path.relpath(paths["xplane"],
                                                   str(tmp_path))
        # the ten markers agree with one another, and with the probe
        assert 0 <= header["clock_tie_spread_us"] < 500
        (stamped,) = obs_session.annotation_starts_us(paths["xplane"],
                                                      "test_probe_mark")
        host_us = ((p0 + p1) / 2 - session.tracer._origin) * 1e6
        assert stamped + header["clock_shift_us"] == pytest.approx(
            host_us, abs=500)
        # the probe lies between the two fit spans on the tracer's timeline
        fits = sorted((e for e in events if e["name"] == "fit"),
                      key=lambda e: e["ts"])
        assert fits[0]["ts"] + fits[0]["dur"] <= host_us <= fits[1]["ts"]


def test_compile_phases_and_set_parameter_seconds():
    ff = build_mlp()
    phases = ff.compile_phases
    assert all(v >= 0 for v in phases.values())
    assert phases["search_s"] == 0.0   # no search budget
    assert ff.set_parameter_s == 0.0
    name = ff.get_layer_names()[0]
    ff.set_parameter(name, ff.get_parameter(name))
    first = ff.set_parameter_s
    assert first > 0
    ff.set_parameter(name, ff.get_parameter(name))
    assert ff.set_parameter_s > first
    assert obs.model_context(ff)["set_parameter_s"] == ff.set_parameter_s
    assert obs.model_context(ff)["compile_phases"] == phases


@pytest.mark.parametrize("flash_layers,dropout_layers", [(2, 1), (1, 0),
                                                         (0, 1)])
def test_lane_dense_flash_ops_are_the_models_flash_ops(
        flash_layers, dropout_layers, tmp_path, monkeypatch, no_open_session):
    """`executor.flash_lane_dense_ops`: attention ops whose forward
    called the flash kernels with [B, S, heads*head_dim] operands. It is
    in the registry's snapshot and in the trace header of a session, and
    equals the number of the model's ops that run flash in training
    (an op with attention-probability dropout runs the einsum core)."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.ffconst import OperatorType

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, s, e = 2, 128, 32
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, s, e))
    for i in range(flash_layers + dropout_layers):
        t = ff.multihead_attention(
            t, t, t, e, 4, dropout=0.0 if i < flash_layers else 0.1,
            name=f"attn{i}")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    assert obs.model_context(ff)["flash_lane_dense_ops"] == 0  # not traced
    rs = np.random.RandomState(0)
    x = rs.randn(2 * b, s, e).astype(np.float32)
    y = rs.randn(2 * b, s, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    attention = [n.op for n in ff.executor.nodes
                 if n.op.op_type == OperatorType.MULTIHEAD_ATTENTION]
    flash = sum(op.selected_impl(training=True) == "flash"
                for op in attention)
    assert flash == flash_layers
    header, _ = read_events(paths["events"])
    assert header["flash_lane_dense_ops"] == flash
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert gauges["executor.flash_lane_dense_ops"] == flash


@pytest.mark.parametrize("lane_layers,view_layers,plain_layers",
                         [(2, 1, 1), (1, 0, 0), (0, 1, 1)])
def test_lane_dense_rotary_ops_are_the_ops_the_pass_took(
        lane_layers, view_layers, plain_layers, tmp_path, monkeypatch,
        no_open_session):
    """`executor.rotary_lane_dense_ops` (PR 42): attention ops whose
    forward ran the heads' norm and rotary as the lane-dense pass
    (`pallas_kernels.rotary_lanes`). A rotary op with heads of 128 takes
    it; one with heads of 32 keeps the [B, S, H, D] view; an op without
    rotary has nothing to pass. In the registry's snapshot, the trace
    header and `FFModel.op_counters`, beside `flash_lane_dense_ops`."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, s, e = 1, 128, 32        # one device: a bare kernel call
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, s, e))
    for i in range(lane_layers):
        t = ff.multihead_attention(t, t, t, e, 2, head_dim=128, rope=True,
                                   causal=True, name=f"lanes{i}")
    for i in range(view_layers):
        t = ff.multihead_attention(t, t, t, e, 2, head_dim=32, rope=True,
                                   causal=True, name=f"view{i}")
    for i in range(plain_layers):
        t = ff.multihead_attention(t, t, t, e, 2, head_dim=128,
                                   name=f"plain{i}")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    assert obs.model_context(ff)["rotary_lane_dense_ops"] == 0  # not traced
    rs = np.random.RandomState(0)
    x = rs.randn(2 * b, s, e).astype(np.float32)
    y = rs.randn(2 * b, s, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    header, _ = read_events(paths["events"])
    assert header["rotary_lane_dense_ops"] == lane_layers
    assert header["flash_lane_dense_ops"] == (lane_layers + view_layers
                                              + plain_layers)
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert gauges["executor.rotary_lane_dense_ops"] == lane_layers
    assert ff.executor.traced_gauges()[
        "executor.rotary_lane_dense_ops"] == lane_layers


@pytest.mark.parametrize("grouped_layers,narrow_layers,mha_layers",
                         [(2, 1, 1), (1, 0, 0), (0, 0, 2)])
def test_grouped_kv_flash_ops_are_the_ops_whose_keys_stay_at_the_kv_heads(
        grouped_layers, narrow_layers, mha_layers, tmp_path, monkeypatch,
        no_open_session):
    """`executor.flash_grouped_kv_ops` (PR 43): attention ops whose
    forward handed the flash kernels K and V as [B, S, Hk*D] with fewer
    KV heads than query heads. A grouped-query op with heads of 128
    takes it; one with heads of 64 in groups of 3 repeats its keys (a
    column block holds two heads, here of two KV heads; an even group
    takes it too since PR 47); an op with as many KV heads as query
    heads has no group: 0 for a model of those. In the registry's snapshot, the trace
    header and `FFModel.op_counters`, beside `flash_lane_dense_ops`."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, s, e = 1, 128, 32        # one device: a bare kernel call
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, s, e))
    for i in range(grouped_layers):
        t = ff.multihead_attention(t, t, t, e, 4, head_dim=128,
                                   num_kv_heads=2, causal=True,
                                   name=f"grouped{i}")
    for i in range(narrow_layers):
        t = ff.multihead_attention(t, t, t, e, 6, head_dim=64,
                                   num_kv_heads=2, causal=True,
                                   name=f"narrow{i}")
    for i in range(mha_layers):
        t = ff.multihead_attention(t, t, t, e, 2, head_dim=128,
                                   name=f"mha{i}")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    assert obs.model_context(ff)["flash_grouped_kv_ops"] == 0  # not traced
    rs = np.random.RandomState(0)
    x = rs.randn(2 * b, s, e).astype(np.float32)
    y = rs.randn(2 * b, s, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    header, _ = read_events(paths["events"])
    assert header["flash_grouped_kv_ops"] == grouped_layers
    assert header["flash_lane_dense_ops"] == (grouped_layers + narrow_layers
                                              + mha_layers)
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert gauges["executor.flash_grouped_kv_ops"] == grouped_layers
    assert ff.executor.traced_gauges()[
        "executor.flash_grouped_kv_ops"] == grouped_layers
    # `fit` publishes the op counters where a model's ops count on the
    # device (no op of this model does): what it would publish
    ff._publish_op_counters({})
    assert ff.op_counters["executor.flash_grouped_kv_ops"] == grouped_layers


@pytest.mark.parametrize("moe_layers", [2, 1, 0])
def test_expert_ops_counts_the_models_expert_layers(
        moe_layers, tmp_path, no_open_session):
    """`executor.expert_ops` (PR 27): the model's `MoELayer` count in the
    registry's snapshot (every one moves its rows by gathers: the
    scatter path went in PR 32, and the witness of it in PR 44). Each
    publishes `executor.moe_sum_rows_ops`: 0 until the step is traced
    and here, with the kernels off; in the header of a session, the
    registry's snapshot and `FFModel.op_counters` (which exist where an
    op counts something: a model with such a layer). A model without
    such a layer publishes no such key. Beside it, and the same way,
    `executor.moe_spread_rows_ops` (PR 49): the layers whose traced
    backward of the combine took the kernel `moe_spread_rows`."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.ffconst import OperatorType

    b, s, e = 2, 16, 32
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, s, e))
    for i in range(moe_layers):
        t = ff.moe_layer(t, 8, 2, 16, experts_held=4, slot_slack=7.0,
                         scoring="softmax" if i else "sigmoid",
                         gated=bool(i), name=f"experts{i}")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    assert sum(n.op.op_type == OperatorType.MOE_LAYER
               for n in ff.executor.nodes) == moe_layers
    assert obs.model_context(ff).get("moe_sum_rows_ops") == (
        0 if moe_layers else None)
    rs = np.random.RandomState(0)
    x = rs.randn(2 * b, s, e).astype(np.float32)
    y = rs.randn(2 * b, s, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    header, _ = read_events(paths["events"])
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert gauges["executor.expert_ops"] == moe_layers
    assert ("moe_sum_rows_ops" in header) == bool(moe_layers)
    assert ("moe_spread_rows_ops" in header) == bool(moe_layers)
    if moe_layers:
        assert header["moe_sum_rows_ops"] == 0
        assert header["moe_spread_rows_ops"] == 0
        assert gauges["executor.moe_sum_rows_ops"] == 0
        assert gauges["executor.moe_spread_rows_ops"] == 0
        assert ff.op_counters["executor.moe_sum_rows_ops"] == 0
        assert ff.op_counters["executor.moe_spread_rows_ops"] == 0
        assert ff.op_counters["moe/overflow_slots"] == 0


@pytest.mark.parametrize("mode,held,sums", [
    ("interpret", 1, True), ("interpret", 8, False), ("off", 1, False)],
    ids=["an_eighth_held", "all_held", "kernels_off"])
def test_moe_sum_rows_ops_counts_the_layers_that_sum_by_the_kernel(
        mode, held, sums, tmp_path, monkeypatch, no_open_session):
    """`executor.moe_sum_rows_ops` (PR 37): of the `MoELayer` ops, those
    whose traced forward added the buffer's rows into their tokens by the
    kernel `moe_sum_rows`. 0 until the step is traced; then the layers'
    count where the kernels run (here interpreted) and a layer holds a
    small share of its experts, 0 where it holds them all (a row a pair:
    the k gathers stay) and where the kernels are off, as on the CPU.
    `executor.moe_spread_rows_ops` (PR 49) counts those whose traced
    BACKWARD of the combine took the kernel `moe_spread_rows`: the same
    layers, by the same rule."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", mode)
    b, s, e, layers = 2, 64, 128, 2
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, s, e))
    for i in range(layers):
        t = ff.moe_layer(t, 8, 2, 16, experts_held=held, slot_slack=1.0,
                         scoring="softmax", name=f"experts{i}")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    assert obs.model_context(ff)["moe_sum_rows_ops"] == 0     # not traced
    assert obs.model_context(ff)["moe_spread_rows_ops"] == 0
    rs = np.random.RandomState(0)
    x = rs.randn(b, s, e).astype(np.float32)
    y = rs.randn(b, s, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    header, _ = read_events(paths["events"])
    expected = layers if sums else 0
    assert header["moe_sum_rows_ops"] == expected
    assert header["moe_spread_rows_ops"] == expected
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert gauges["executor.expert_ops"] == layers
    assert gauges["executor.moe_sum_rows_ops"] == expected
    assert gauges["executor.moe_spread_rows_ops"] == expected
    assert ff.op_counters["executor.moe_sum_rows_ops"] == expected
    assert ff.op_counters["executor.moe_spread_rows_ops"] == expected
    assert ff.op_counters["moe/overflow_slots"] == 0


# What a decoder with an attention op and a `MoELayer` publishes, by key.
# The benchmark's `observed` lines and `tests/chipbench/test_rehearsal_*`
# read these by name, and no PR of another kind may edit those readers: a
# key is added here by the PR that adds it to an op's `traced_gauges`, and
# none is renamed. (PR 44 retired the witness of the experts' gathers.)
WITNESS_KEYS = [
    "attention/kv_blocks_masked", "attention/kv_blocks_total",
    "attention/kv_blocks_visited", "attention/window_keys_visible",
    "attention/window_keys_visited",
    "executor.block_diffusion_attention_ops",
    "executor.embedding_sum_kernel_ops",
    "executor.flash_grouped_kv_ops", "executor.flash_lane_dense_ops",
    "executor.flash_one_span_ops", "executor.flash_super_block_ops",
    "executor.latent_attention_ops",
    "executor.layer_applications", "executor.loss_own_vjp",
    "executor.moe_resident_weight_products", "executor.moe_row_tile",
    "executor.moe_spread_rows_ops", "executor.moe_sum_rows_ops",
    "executor.rotary_lane_dense_ops",
    "executor.shared_leaves", "executor.shared_weight_ops",
    "executor.window_attention_ops"]
DEVICE_COUNTER_KEYS = ["moe/load_max_over_mean", "moe/overflow_slots",
                       "moe/slots_held"]
CONTEXT_KEYS = [
    "attention_kv_blocks_masked", "attention_kv_blocks_total",
    "attention_kv_blocks_visited", "attention_window_keys_visible",
    "attention_window_keys_visited", "batch_size",
    "block_diffusion_attention_ops", "compile_phases",
    "embedding_sum_kernel_ops", "flash_grouped_kv_ops", "flash_lane_dense_ops", "flash_one_span_ops",
    "flash_super_block_ops",
    "latent_attention_ops", "layer_applications",
    "loss_own_vjp", "loss_target_positions", "mesh_axes",
    "moe_resident_weight_products", "moe_row_tile",
    "moe_spread_rows_ops", "moe_sum_rows_ops", "num_ops",
    "rotary_lane_dense_ops", "search_predicted_memory_bytes",
    "search_predicted_step_s",
    "set_parameter_s", "shared_leaves", "shared_weight_ops",
    "window_attention_ops"]


def test_the_published_keys_are_these(tmp_path, no_open_session):
    """The KEY SETS of the three publishers of what the ops witnessed
    (the registry's gauges, `FFModel.op_counters`, the trace header's
    model context), held to the literal lists above: each is one loop
    over `GraphExecutor.traced_gauges()`, so they cannot differ from one
    another, and a renamed or dropped key fails here and not in a reader
    on the chip."""
    import numpy as np
    from flexflow_tpu import AdamOptimizer, FFConfig, LossType
    from flexflow_tpu.models import DecoderConfig, create_decoder

    cfg = DecoderConfig(hybrid_override_pattern="GW", batch_size=2,
                        seq_length=16, sliding_window_size=8)
    ff = create_decoder(cfg, FFConfig(batch_size=2))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    assert sorted(ff.executor.traced_gauges()) == WITNESS_KEYS
    assert sorted(obs.model_context(ff)) == CONTEXT_KEYS      # not traced
    obs.get_registry().reset()
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1)
    ff.fit([ids], labels, epochs=1, verbose=False)   # traces the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit([ids], labels, epochs=1, verbose=False)
    paths = obs.stop_trace()
    header, _ = read_events(paths["events"])
    gauges = json.load(open(paths["counters"]))["gauges"]
    assert sorted(gauges) == sorted(
        WITNESS_KEYS + DEVICE_COUNTER_KEYS
        + ["executor.expert_ops", "executor.num_ops", "executor.ssm_ops"])
    assert sorted(ff.op_counters) == sorted(WITNESS_KEYS
                                            + DEVICE_COUNTER_KEYS)
    assert set(CONTEXT_KEYS) <= set(header)
    assert sorted(obs.model_context(ff)) == CONTEXT_KEYS
    # one value under the three names of a key
    for key in WITNESS_KEYS:
        field = key.split(".")[-1].replace("/", "_")
        assert gauges[key] == ff.op_counters[key] == header[field], key
    assert header["window_attention_ops"] == 1
    assert gauges["executor.expert_ops"] == 2


@pytest.mark.parametrize("seq,window", [(2048, 512), (2048, 0), (128, 32)])
def test_window_attention_gauges_show_that_the_skip_engaged(
        seq, window, tmp_path, monkeypatch, no_open_session):
    """`executor.window_attention_ops`, `attention/kv_blocks_visited` and
    `attention/kv_blocks_total` (PR 31) and `attention/kv_blocks_masked`
    (PR 35), set when the train step is traced: in the registry's
    snapshot and in the header of a session.
    A blocked causal layer visits the K blocks up to the diagonal and a
    windowed one fewer, and masks only those that hold a hidden pair;
    the whole-tile kernels hold one tile and mask."""
    import numpy as np
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.ops.pallas_kernels import kv_blocks, kv_blocks_masked

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, e = 1, 32
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, seq, e))
    t = ff.multihead_attention(t, t, t, e, 2, causal=True, name="full")
    t = ff.multihead_attention(t, t, t, e, 2, causal=True, window=window,
                               name="windowed")
    ff.dense(t, 1)
    ff.compile(SGDOptimizer(lr=0.01), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    context = obs.model_context(ff)
    assert context["window_attention_ops"] == (1 if window else 0)
    assert context["attention_kv_blocks_total"] == 0      # not traced yet
    assert context["attention_kv_blocks_masked"] == 0
    rs = np.random.RandomState(0)
    x = rs.randn(b, seq, e).astype(np.float32)
    y = rs.randn(b, seq, 1).astype(np.float32)
    ff.fit(x, y, epochs=1, verbose=False)   # traces and compiles the step
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    full, total = kv_blocks(seq, True, 0)
    # a window narrower than 1024 takes narrower K chunks (PR 41): the
    # windowed layer's square then holds more tiles than the full one's
    part, total_windowed = kv_blocks(seq, True, window)
    masked = kv_blocks_masked(seq, True, 0) + kv_blocks_masked(
        seq, True, window)
    header, _ = read_events(paths["events"])
    gauges = json.load(open(paths["counters"]))["gauges"]
    for got in (
            (header["window_attention_ops"],
             header["attention_kv_blocks_visited"],
             header["attention_kv_blocks_total"],
             header["attention_kv_blocks_masked"]),
            (gauges["executor.window_attention_ops"],
             gauges["attention/kv_blocks_visited"],
             gauges["attention/kv_blocks_total"],
             gauges["attention/kv_blocks_masked"])):
        assert got == (1 if window else 0, full + part,
                       total + total_windowed, masked)
    if seq > 1024:
        assert full < total
        assert (part / total_windowed < full / total) == bool(window)
        # a full layer masks its diagonal alone; at this window no tile
        # of the windowed layer is wholly visible
        diagonal = seq // 256
        assert masked == diagonal + (part if window else diagonal)
        assert masked < full + part
    else:
        assert masked == 2


@pytest.mark.parametrize("seq,block", [(4096, 4), (256, 32)])
def test_block_diffusion_gauges_and_the_target_counter(
        seq, block, tmp_path, monkeypatch, no_open_session):
    """`executor.block_diffusion_attention_ops` and the
    `attention/kv_blocks_*` counts of the new tiles (PR 34), set when the
    train step is traced, and `loss/target_positions`, which leaves the
    step with the ops' counters and is read once an epoch: in the header
    of a session, the registry's snapshot and `FFModel.op_counters`."""
    import numpy as np
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.dataloader import block_diffusion_batch
    from flexflow_tpu.ops.pallas_kernels import kv_blocks, kv_blocks_masked

    monkeypatch.setenv("FLEXFLOW_TPU_PALLAS", "interpret")
    b, e, half = 1, 32, seq // 2
    ff = FFModel(FFConfig(batch_size=b))
    t = ff.create_tensor((b, seq, e))
    t = ff.multihead_attention(t, t, t, e, 2, block_diffusion=(half, block),
                               rope=True, rope_wrap=half, qk_norm=True,
                               name="masked")
    t = ff.multihead_attention(t, t, t, e, 2, causal=True, name="causal")
    t = ff.split(t, [half, half], axis=1)[0]
    ff.dense(t, 16)
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    context = obs.model_context(ff)
    assert context["block_diffusion_attention_ops"] == 1
    assert context["attention_kv_blocks_total"] == 0      # not traced yet
    assert context["attention_kv_blocks_masked"] == 0
    assert context["loss_target_positions"] is None
    rs = np.random.default_rng(0)
    _, labels = block_diffusion_batch(rs.integers(0, 15, (2 * b, half)),
                                      block, 15, rs)
    x = rs.standard_normal((2 * b, seq, e)).astype(np.float32)
    targets = int((labels[..., 1] > 0).sum())
    ff.fit(x, labels, epochs=1, verbose=False)   # traces, compiles, counts
    assert ff.op_counters["loss/target_positions"] == targets
    obs.start_trace(str(tmp_path), device=False)
    ff.fit(x, labels, epochs=1, verbose=False)
    paths = obs.stop_trace()
    masked, total = kv_blocks(seq, False, 0, (half, block))
    causal, causal_total = kv_blocks(seq, True, 0)
    edge = kv_blocks_masked(seq, False, 0, (half, block)) + (
        kv_blocks_masked(seq, True, 0))
    header, _ = read_events(paths["events"])
    gauges = json.load(open(paths["counters"]))["gauges"]
    for got in (
            (header["block_diffusion_attention_ops"],
             header["window_attention_ops"],
             header["attention_kv_blocks_visited"],
             header["attention_kv_blocks_total"],
             header["attention_kv_blocks_masked"],
             header["loss_target_positions"]),
            (gauges["executor.block_diffusion_attention_ops"],
             gauges["executor.window_attention_ops"],
             gauges["attention/kv_blocks_visited"],
             gauges["attention/kv_blocks_total"],
             gauges["attention/kv_blocks_masked"],
             gauges["loss/target_positions"])):
        assert got == (1, 0, masked + causal, total + causal_total, edge,
                       targets)
    assert ff.op_counters["attention/kv_blocks_masked"] == edge
    if seq > 1024:
        # a quarter of the square and the tiles on its two diagonals,
        # where the causal layer visits the half under one
        assert masked / total < causal / causal_total < 1
        # of them the noised diagonal tile and the last clean chunk of a
        # Q block, and the causal layer's diagonal, run the masked body
        assert edge == 8 + 16 + 16 < masked + causal
    else:
        assert edge == 2                                  # whole tiles
