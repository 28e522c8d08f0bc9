"""Median host milliseconds of the `device_put` span over the unfenced,
traced tail of a `--trace 2` run, from the session's artifact: one batch
sliced, cast and handed to the runtime. The span ends when the copy is
enqueued, not when it has run: the runtime's layout conversion and the
transfer itself happen on its own threads, outside every program span
(PERF.md section 5), and a change that shortens or hides them moves
`executor.epoch_gap_ms` and `device.idle_pct`, not this."""

from benchmarks import session_reduce as sr


def read(ctx):
    return sr.median_ms(sr.find(ctx, __file__), "device_put")
