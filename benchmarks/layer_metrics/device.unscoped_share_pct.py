"""Share of the traced train steps' device-busy time that is neither
forward, backward nor optimizer: events whose instruction carries no
direction (no scope of the program, or a name the table lacks) and busy
time that is no op event. What the tracing cannot name
(`benchmarks/step_parts.py`)."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.unscoped_share_pct(ctx, __file__)
