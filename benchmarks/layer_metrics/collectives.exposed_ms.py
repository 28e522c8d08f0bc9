"""Milliseconds a step during which a collective runs on a device and no
compute op of that device covers it, inside the traced window; per device,
then the mean. Listed for cells on more than one chip."""

from benchmarks import trace_reduce as tr


def read(ctx):
    got = tr.mean_over_devices(ctx["devices"], tr.exposed_collective_seconds)
    if got is None or not got[1]:
        return None
    seconds, steps = got
    return 1e3 * seconds / steps
