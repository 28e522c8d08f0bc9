"""Share of the traced train steps' device-busy time under the program's
`gated_memory` scope: the gated memory units, forward and backward: the
product h W_1, silu, the multiply by the memory (the scan output that
another layer made) and the product with W_2, by the join table the
program writes (`benchmarks/step_parts.py`). Where the program has no
such scope the table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "gated_memory") or None
