"""The dearest pass's device time over the cheapest's in a looped model:
device milliseconds a step of the events whose part is `ut<t>`, forward
and backward, a pass, by the join table the program writes
(`benchmarks/step_parts.py`); the largest over the smallest. Every pass
runs the same layers at the same shapes on the same leaves, so what lifts
the ratio over 1 is what sharing costs a pass beyond its own work: the
sums of the passes' weight gradients (they land in the pass whose
product the compiler fuses them into), a layout copy of a shared leaf
made in one pass and read in the others, checkpointing that differs
between passes. Where the program names fewer than two passes the reader
returns nothing."""

import collections
import re

from benchmarks import step_parts

PASS = re.compile(r"ut\d+$")


def read(ctx):
    got = step_parts.reduced(ctx, __file__)
    if got is None:
        return None
    by_pass = collections.Counter()
    for (part, _), ms in got["ms_a_step"].items():
        if part and PASS.match(part):
            by_pass[part] += ms
    if len(by_pass) < 2 or min(by_pass.values()) <= 0:
        return None
    return max(by_pass.values()) / min(by_pass.values())
