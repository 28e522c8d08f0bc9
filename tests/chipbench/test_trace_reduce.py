"""The benchmark's own trace reduction: on a copy of the one-chip trace
recorded on the v5e, and on hand-made traces of two devices."""

import os

import pytest

from benchmarks import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "devtrace_tpu_v5e.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return tr.load_chrome(FIXTURE)


def test_fixture_has_one_device_and_two_steps(recorded):
    (dev,) = recorded
    assert dev.name == "/device:TPU:0"
    assert len(tr.step_spans(dev)) == 2
    assert set(dev.lines) >= {tr.OPS, tr.MODULES}


def test_busy_is_below_the_window_and_roll_up_lines_do_not_count(recorded):
    (dev,) = recorded
    busy, window = tr.busy_and_window(dev)
    # first step from 51.28 ms, second step to 153.17 ms
    assert window == pytest.approx(0.10189, abs=1e-4)
    assert 0 < busy < window
    modules = sum(e - s for s, e in tr.step_spans(dev))
    assert busy <= modules + 1e-9   # ops lie inside their programs


def test_kernel_seconds_counts_only_tpu_custom_calls(recorded):
    (dev,) = recorded
    seconds, steps = tr.kernel_seconds(dev)
    by_hand = sum(d for n, _, d in dev.lines[tr.OPS]
                  if n.startswith("tpu_custom_call"))
    assert steps == 2 and seconds == pytest.approx(by_hand) and seconds > 0


def _two_devices(collective_line):
    """Device 0: compute 0-10, an all-reduce 10-20. Device 1: compute
    0-20. The all-reduce overlaps compute on device 1 only."""
    module = ("jit_train_step(1)", 0.0, 20.0)
    d0 = tr.Device("/device:TPU:0", {
        tr.MODULES: [module], tr.OPS: [("fusion.1", 0.0, 10.0)]})
    d0.lines.setdefault(collective_line, []).append(
        ("all-reduce.7", 10.0, 10.0))
    d1 = tr.Device("/device:TPU:1", {
        tr.MODULES: [module], tr.OPS: [("fusion.1", 0.0, 20.0)]})
    return [d0, d1]


@pytest.mark.parametrize("line", [tr.OPS, tr.ASYNC])
def test_collective_hidden_only_by_another_device_is_exposed(line):
    devices = _two_devices(line)
    assert tr.exposed_collective_seconds(devices[0]) == (10.0, 1)
    assert tr.exposed_collective_seconds(devices[1]) == (0.0, 1)
    seconds, steps = tr.mean_over_devices(devices,
                                          tr.exposed_collective_seconds)
    assert (seconds, steps) == (5.0, 1)


def test_async_collective_under_same_device_compute_is_hidden():
    dev = tr.Device("/device:TPU:0", {
        tr.MODULES: [("jit_train_step(1)", 0.0, 20.0)],
        tr.OPS: [("fusion.1", 0.0, 12.0), ("all-reduce-done.3", 12.0, 3.0)],
        tr.ASYNC: [("all-reduce-start.2", 5.0, 10.0)]})
    # 5-12 runs under the fusion; 12-15 waits on the main stream
    assert tr.exposed_collective_seconds(dev)[0] == pytest.approx(3.0)


def test_idle_is_per_device_not_a_union_over_devices():
    module = ("jit_train_step(1)", 0.0, 10.0)
    d0 = tr.Device("/device:TPU:0", {tr.MODULES: [module],
                                     tr.OPS: [("fusion.1", 0.0, 5.0)]})
    d1 = tr.Device("/device:TPU:1", {tr.MODULES: [module],
                                     tr.OPS: [("fusion.1", 5.0, 5.0)]})
    busy, window = tr.mean_over_devices([d0, d1], tr.busy_and_window)
    assert (busy, window) == (5.0, 10.0)     # a union would say 10 of 10


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5),
                                                        (7, 10)]
    assert tr.stem("fusion.123") == "fusion"
    assert tr.stem("tpu_custom_call.4") == "tpu_custom_call"


def test_breakdown_lists_are_short_and_named(recorded):
    ops = tr.top_device_ops(recorded)
    gaps = tr.top_idle_gaps(recorded)
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert gaps[0][0].startswith(("between", "inside"))
    assert max(g[1] for g in gaps) == gaps[0][1]
