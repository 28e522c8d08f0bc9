"""Share of the traced train steps' device-busy time under the expert
layers' `moe_combine` scope (PR 32): the gather of the buffer's rows out
of the tokens, the weighted sum of the rows back into them, and both
backwards (`benchmarks/step_parts.py`)."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "moe_combine")
