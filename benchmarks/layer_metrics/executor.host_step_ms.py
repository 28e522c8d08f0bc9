"""Median host milliseconds of the `step` span over the unfenced, traced
tail of a `--trace 2` run: the wall time of one loop iteration on the
host thread (the batch's enqueue, key split, dispatch, metric
accumulation). Nothing is fenced, but the time the runtime keeps the
thread blocked inside `dispatch` is in it (most of Inception's 11-21 ms
on the v5e, PERF.md section 6), so this bounds the host's own work from
above: the loop is host-bound only where this nears the device's time
for a step."""

from benchmarks import session_reduce as sr


def read(ctx):
    return sr.median_ms(sr.find(ctx, __file__), "step")
