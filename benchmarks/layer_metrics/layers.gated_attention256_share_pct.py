"""Share of the traced train steps' device-busy time under the program's
`attention_full` scope in a model whose ONE attention op has heads of
256 lanes and a gate a lane: the 16 : 2 causal op, forward and backward,
with its projections (the query's carries the gate), the heads'
zero-centred norm and the rotary over 64 lanes, the wide-head flash
kernels (`flash_full`), the gate (`attention_gate`) and the output
projection, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "attention_full") \
        or None
