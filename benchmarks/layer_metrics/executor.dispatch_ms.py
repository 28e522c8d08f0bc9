"""Median host milliseconds inside the train-step call: the program's
`dispatch` phase over one epoch of fenced steps."""

import statistics


def read(ctx):
    samples = ctx["counters"]["dispatch_ms"]
    return statistics.median(samples) if samples else None
