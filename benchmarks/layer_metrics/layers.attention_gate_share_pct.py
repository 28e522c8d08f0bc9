"""Share of the traced train steps' device-busy time under the program's
`attention_gate` scope alone: the per-head gate's float32 projection,
its softplus, the multiply of the attention core's output and their
backward (the 128-lane sum of dO * o a head and position, the rescaled
cotangent on its way into the kernels), by the join table the program
writes (`benchmarks/step_parts.py`). A fusion counts by its root's
`op_name`, so a multiply that XLA fuses into the output projection's
operand is the projection's, not the gate's. Where the program has no
such scope (no gated op, an older program) the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "attention_gate") \
        or None
