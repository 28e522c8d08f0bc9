"""Share of the traced train steps' device-busy time in the optimizer
update (the `optimizer_update` scope: moments, parameters, the cast of
the next step's compute copy; a `fused_adam` kernel by its name), by the
join table the program writes (`benchmarks/step_parts.py`)."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.direction_share_pct(ctx, __file__, "optimizer")
