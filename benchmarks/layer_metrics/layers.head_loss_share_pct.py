"""Share of the traced train steps' device-busy time in the op that
produces the model's output (`head`) and in the loss (`loss`), forward
and backward, by the join table the program writes
(`benchmarks/step_parts.py`)."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.part_share_pct(ctx, __file__, ("head", "loss"))
