"""Share of the traced train steps' device-busy time in instructions of
the backward pass (`transpose(...)` in their `op_name`, a checkpointed
op's recomputation with them), by the join table the program writes
(`benchmarks/step_parts.py`)."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.direction_share_pct(ctx, __file__, "backward")
