"""Median milliseconds the device stands idle between the last train-step
program of one `fit` call and the first of the next, in the traced tail
of a `--trace 2` run; per device, then the mean. A step belongs to the
`fit` span whose `dispatch` span launched it."""

import statistics

from benchmarks import session_reduce as sr
from benchmarks import trace_reduce as tr


def read(ctx):
    session = sr.find(ctx, __file__)
    if not sr.tied(session):
        return None

    def median_gap(dev):
        gaps = sr.epoch_gaps_s(dev, session.spans)
        return (statistics.median(gaps),) if gaps else None

    got = tr.mean_over_devices(ctx["devices"], median_gap)
    return None if got is None else 1e3 * got[0]
