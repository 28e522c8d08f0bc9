"""Share of two back-to-back profiled part-A epochs in which no operation
ran on the device, from the first train-step program's start to the last
one's end (so the gap between the epochs counts); per device, then the
mean."""

from benchmarks import trace_reduce as tr


def read(ctx):
    got = tr.mean_over_devices(ctx["devices"], tr.busy_and_window)
    if got is None or not got[1]:
        return None
    busy, window = got
    return 100.0 * (1.0 - busy / window)
