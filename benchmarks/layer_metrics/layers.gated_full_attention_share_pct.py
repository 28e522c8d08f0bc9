"""Share of the traced train steps' device-busy time under the program's
`attention_full` scope in a model of gated, per-layer head counts: the
48-head causal ops, forward and backward, with their projections, the
half-rotated YaRN rotary (`rotary_partial_yarn`), the repeat of K and V,
the flash kernels (`flash_full`), the gate (`attention_gate`) and the
output projection, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "attention_full") \
        or None
