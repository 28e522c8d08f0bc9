"""Share of the traced train steps' device-busy time under the program's
`attention_full` scope in a model whose heads are 64 lanes wide: the
causal 32 : 8 grouped-query op, forward and backward, with its
projections, the heads' norm and whole-head rotary over a [B, S, H, D]
view (`rotary_whole`), the repeat of K and V to the query heads, the
flash kernels at two heads a 128-lane block (`flash_full`) and the
output projection, by the join table the program writes
(`benchmarks/step_parts.py`). The lane-dense norm-and-rotary pass and
the K / V reads at the KV heads ask for heads of 128, so this is what
the forms they replaced cost where they still run. Where the program
has no such scope the table holds no such row and the reader returns
nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "attention_full") \
        or None
