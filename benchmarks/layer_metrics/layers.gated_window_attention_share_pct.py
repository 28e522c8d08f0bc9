"""Share of the traced train steps' device-busy time under the program's
`attention_window` scope in a model of gated, per-layer head counts: the
64-head window-512 ops, forward and backward, with their projections,
rotary embedding (`rotary_whole`), the repeat of K and V to the query
heads, the flash kernels (`flash_window`), the gate (`attention_gate`)
and the output projection, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope the
table holds no such row and the reader returns nothing. It also asks
for the whole part x direction breakdown, so that a traced run of the
cell leaves `step_parts.json` beside its session as the other cells'
runs do (the accepted readers that write it are not listed here)."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)
    return step_parts.scope_share_pct(ctx, __file__, "attention_window") \
        or None
