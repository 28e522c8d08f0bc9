"""The search's prices of the executed strategy, cut as the join table
cuts the compiled step (obs/simtrace.py `prices_by_part`, `step_prices`)
and written beside the table by a session with `device=True`
(obs/step_scopes.py `priced_step`); the one rule from an op to its part
(`GraphExecutor.scope_names` / `part_of_node`); the session header's
search gauges and allocator peak. CPU, tiny sizes: nothing here is a
device number.
"""

import functools
import json

import numpy as np
import pytest

import jax

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType, obs
from flexflow_tpu import executor as executor_module
from flexflow_tpu.ffconst import ActiMode
from flexflow_tpu.models import (DecoderConfig, TransformerConfig,
                                 create_decoder, create_transformer)
from flexflow_tpu.obs import session as obs_session
from flexflow_tpu.obs import simtrace
from flexflow_tpu.obs import step_scopes as ss
from flexflow_tpu.search.validate import simulate_strategy

SEARCHED = dict(search_budget=4)


def dense(**config):
    ff = FFModel(FFConfig(batch_size=8, **config))
    t = ff.create_tensor((8, 16))
    t = ff.dense(t, 32, activation=ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    rng = np.random.default_rng(0)
    return (ff, [rng.standard_normal((16, 16)).astype(np.float32)],
            rng.integers(0, 4, (16, 1)).astype(np.int32))


def looped():
    """Two passes of one block under `FFModel.scope("ut<t>")`, the heads
    under `exit`."""
    cfg = DecoderConfig(hybrid_override_pattern="U", total_ut_steps=2,
                        num_attention_heads=4, num_key_value_heads=4,
                        batch_size=2, seq_length=16)
    ff = create_decoder(cfg, FFConfig(batch_size=2, **SEARCHED))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 16)).astype(np.int32)
    return ff, [ids], np.roll(ids, -1, axis=1)


def transformer():
    tc = TransformerConfig(num_layers=1, hidden_size=32, num_heads=4,
                           seq_length=16, batch_size=8)
    ff = create_transformer(tc, FFConfig(batch_size=8, **SEARCHED))
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    rng = np.random.default_rng(0)
    return (ff, [rng.standard_normal((16, 16, 32)).astype(np.float32)],
            rng.standard_normal((16, 16, 1)).astype(np.float32))


MODELS = {"dense": lambda: dense(**SEARCHED), "looped": looped,
          "transformer": transformer}


@functools.lru_cache(maxsize=None)
def built(name):
    """(name, model, inputs, labels, the native replay of its executed
    strategy), once a module."""
    ff, xs, y = MODELS[name]()
    return name, ff, xs, y, simulate_strategy(ff)


@pytest.fixture(params=list(MODELS))
def priced(request):
    return built(request.param)


@pytest.fixture
def no_open_session():
    yield
    if obs.session_tracer() is not None:
        obs.stop_trace()


def seconds_of(resp, kind):
    return sum(t["finish"] - t["start"] for t in resp["tasks"]
               if t["kind"] == kind)


def test_the_rows_add_up_to_the_schedules_totals(priced):
    """Forward and backward rows add up to the response's `fwd_time` /
    `bwd_time` (on a mesh without a pipe axis those ARE the `fwd` / `bwd`
    tasks' durations: `ffs_sim.hpp` adds each task's to the total as it
    makes it), the optimizer row to the `update` tasks' durations, for
    which the response has no total."""
    _, ff, _, _, resp = priced
    rows = simtrace.prices_by_part(ff, resp)
    by_direction = {}
    for part, direction, seconds, ops, *hidden in rows:
        assert ops > 0 and seconds >= 0
        assert bool(hidden) == (part == simtrace.COLLECTIVES)
        by_direction[direction] = by_direction.get(direction, 0.0) + seconds
    assert by_direction["forward"] == pytest.approx(resp["fwd_time"])
    assert by_direction["forward"] == pytest.approx(seconds_of(resp, "fwd"))
    assert by_direction["backward"] == pytest.approx(resp["bwd_time"])
    assert by_direction["backward"] == pytest.approx(seconds_of(resp, "bwd"))
    update = seconds_of(resp, "update")
    assert update > 0
    assert by_direction["optimizer"] == pytest.approx(update)
    assert [r[0] for r in rows if r[1] == "optimizer"] == ["optimizer_update"]
    # every op is priced once a direction
    assert sum(r[3] for r in rows if r[1] == "forward") == len(
        ff.executor.nodes)
    prices = simtrace.step_prices(ff, resp)
    assert prices["update_s"] == pytest.approx(update)
    assert prices["step_s"] == resp["iteration_time"]
    assert prices["memory_bytes"] == resp["memory"] > 0
    assert prices["search_predicted_s"] == ff.search_info["predicted_time"]
    assert prices["search_predicted_memory_bytes"] == ff.search_info[
        "predicted_memory"]
    assert sum(prices["cost_sources"].values()) == len(ff.executor.nodes)
    assert prices["by_part"] == rows


def test_per_op_predicted_keeps_the_update_tasks(priced):
    _, ff, _, _, resp = priced
    per_op = simtrace.per_op_predicted(resp["tasks"])
    assert per_op[-1]["update_s"] == pytest.approx(seconds_of(resp, "update"))
    assert per_op[-1]["fwd_s"] == per_op[-1]["bwd_s"] == 0.0
    assert sorted(k for k in per_op if k >= 0) == list(
        range(len(ff.executor.nodes)))
    assert sum(r["fwd_s"] for r in per_op.values()) == pytest.approx(
        resp["fwd_time"])
    assert all(r["update_s"] == 0.0 for k, r in per_op.items() if k >= 0)


def test_priced_parts_are_the_parts_of_the_compiled_step(priced):
    """Every priced part but `collectives` holds an instruction of the
    same model's compiled step, and every part of the step but `loss`
    (and the part-less rows) has a price."""
    name, ff, xs, y, resp = priced
    batch = ff.config.batch_size
    text = ff.executor.make_train_step().lower(
        ff.params, ff.opt_state, ff.state,
        ff._stage_inputs([x[:batch] for x in xs]),
        ff._shard_batch(y[:batch]), jax.random.PRNGKey(0)).compile().as_text()
    in_step = {row["part"] for row in ss.table_of(text).values()}
    in_prices = {r[0] for r in simtrace.prices_by_part(ff, resp)}
    assert in_prices - {simtrace.COLLECTIVES} <= in_step
    assert in_step - {"loss", None} <= in_prices
    want = {"dense": {"op_linear", "head", "optimizer_update"},
            # (the op that makes the output lies under `exit`: the
            # outermost scope wins, on both sides)
            "looped": {"ut0", "ut1", "exit", "op_embedding"},
            "transformer": {"attention", "op_linear", "head"}}[name]
    assert want <= in_prices
    if name == "looped":     # every op of a pass is the pass, whatever its kind
        assert not {"attention", "op_rmsnorm", "head"} & in_prices


def test_scope_names_are_what_the_executor_nests(priced, monkeypatch):
    """`_scoped_forward` nests `scope_names(op)` outermost first, less
    the call an op makes around itself; `part_of_node` reads the names
    as `part_of` reads them back out of an `op_name`."""
    _, ff, _, _, _ = priced
    ex = ff.executor
    nested = []
    monkeypatch.setattr(executor_module, "scoped",
                        lambda name, fn: (nested.append(name), fn)[1])
    for node in ex.nodes:
        op, names = node.op, ex.scope_names(node.op)
        del nested[:]
        ex._scoped_forward(op, None)
        own = [op.scopes_itself] if op.scopes_itself else []
        assert nested[::-1] + own == names
        assert names[len(names) - len(own):] == own
        assert ("head" in names) == (op.guid == ex.final_ref[0])
        op_name = "jit(train_step)/" + "/".join(
            f"jvp(jit({n}{'full' if n == 'attention_' else ''}))"
            for n in names) + "/dot_general"
        assert ex.part_of_node(node) == ss.part_of(op_name) is not None


def traced(tmp_path, ff, xs, y):
    ff.fit(xs, y, epochs=1, verbose=False)
    obs.start_trace(str(tmp_path), device=True)
    ff.fit(xs, y, epochs=1, verbose=False)
    paths = obs.stop_trace()
    with open(paths["step_scopes"]) as f:
        artifact = json.load(f)
    with open(paths["events"]) as f:
        return artifact, json.loads(f.readline())


def test_a_session_writes_the_prices_beside_the_table(tmp_path,
                                                      no_open_session):
    _, ff, xs, y, resp = built("dense")
    obs.get_registry().reset()
    artifact, header = traced(tmp_path, ff, xs, y)
    assert artifact["instructions"]
    prices = artifact["prices"]
    assert prices["step_s"] == pytest.approx(resp["iteration_time"])
    assert prices["by_part"] == json.loads(json.dumps(
        simtrace.prices_by_part(ff, resp)))
    assert artifact["header"]["step_prices_s"] > 0
    assert header["step_prices_s"] == artifact["header"]["step_prices_s"]
    assert "step_prices_error" not in header
    # the search's own numbers in every header; the allocator's peak is
    # null on a backend without `memory_stats` (the CPU)
    assert header["search_predicted_step_s"] == ff.search_info[
        "predicted_time"]
    assert header["search_predicted_memory_bytes"] == ff.search_info[
        "predicted_memory"]
    assert header["device_peak_bytes"] is None
    assert header["device_peak_bytes_in_use"] is None


def test_compile_sets_the_search_gauges_once_a_search_ran():
    obs.get_registry().reset()
    ff, _, _ = dense()
    assert not [k for k in obs.get_registry().to_dict()["gauges"]
                if k.startswith("search/")]
    ff, _, _ = dense(**SEARCHED)
    gauges = obs.get_registry().to_dict()["gauges"]
    assert gauges["search/predicted_step_s"] == ff.search_info[
        "predicted_time"]
    assert gauges["search/predicted_memory_bytes"] == ff.search_info[
        "predicted_memory"]


def test_no_search_no_prices(tmp_path, no_open_session):
    ff, xs, y = dense()
    artifact, header = traced(tmp_path, ff, xs, y)
    assert artifact["instructions"] and "prices" not in artifact
    assert header["search_predicted_step_s"] is None
    assert header["search_predicted_memory_bytes"] is None
    assert "step_prices_s" not in header
    assert "step_prices_error" not in header


def test_a_failing_replay_leaves_its_error_and_the_table(
        tmp_path, monkeypatch, no_open_session):
    _, ff, xs, y, _ = built("dense")

    def fails(ff):
        raise RuntimeError("no native library")

    monkeypatch.setattr("flexflow_tpu.search.validate.simulate_strategy",
                        fails)
    artifact, header = traced(tmp_path, ff, xs, y)
    assert artifact["instructions"] and "prices" not in artifact
    assert "no native library" in header["step_prices_error"]
    assert "step_prices_s" not in header
    assert header["step_scopes_instructions"] == len(artifact["instructions"])


class FakeDevice:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats,want", [
    ([None, None], (None, None)),
    ([{}], (None, None)),
    # the fullest device by the sum; its own part in use beside it
    ([dict(peak_bytes_in_use=5, peak_bytes_reserved=70),
      dict(peak_bytes_in_use=50, peak_bytes_reserved=10), None], (75, 5)),
    ([dict(peak_bytes_in_use=7)], (7, 7)),
])
def test_device_peaks_is_the_fullest_devices_sum(stats, want, monkeypatch):
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [FakeDevice(s) for s in stats])
    assert obs_session.device_peaks() == dict(
        device_peak_bytes=want[0], device_peak_bytes_in_use=want[1])


def test_a_session_without_the_profiler_holds_the_peak_too(
        tmp_path, monkeypatch, no_open_session):
    monkeypatch.setattr(jax, "local_devices", lambda: [FakeDevice(
        dict(peak_bytes_in_use=3, peak_bytes_reserved=4))])
    obs.start_trace(str(tmp_path), device=False)
    paths = obs.stop_trace()
    with open(paths["events"]) as f:
        header = json.loads(f.readline())
    assert header["device_peak_bytes"] == 7
    assert header["device_peak_bytes_in_use"] == 3
    assert paths["step_scopes"] is None
