"""Share of the traced train steps' device-busy time in the expert layers
of a model with 512 router outputs, top-10 and a GATED shared expert: the
four `moe_layer` ops, forward and backward (the float32 router over all
512 outputs, the routing sort, the grouped products over the 16 held
experts' rows, the combine, the shared expert and its gate), by the join
table the program writes (`benchmarks/step_parts.py`): the events whose
`op_name` holds `jit(moe_layer)`. Where the program has no such scope the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "moe_layer") or None
