"""Share of the traced train steps' device-busy time in the looped
stack: the events whose part is a pass of a looped model, `ut0`, `ut1`,
... (every op the builder put under `FFModel.scope("ut<t>")`: the
layers' T applications and the norm that closes each pass, forward and
backward), by the join table the program writes
(`benchmarks/step_parts.py`). The head, the exit gate and the loss are
`layers.exit_heads_share_pct`'s. Where the program names no such part
(a model that applies its layers once, a program without the table) the
reader returns nothing."""

import re

from benchmarks import step_parts

PASS = re.compile(r"ut\d+$")


def read(ctx):
    got = step_parts.reduced(ctx, __file__)
    if got is None:
        return None
    return sum(v for (part, _), v in got["share_pct"].items()
               if part and PASS.match(part)) or None
