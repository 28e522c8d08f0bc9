"""Share of the traced train steps' device-busy time under the program's
`attention_diff_*` scopes (`attention_diff_full`, `attention_diff_cross`,
`attention_diff_window`): the differential attention ops, forward and
backward, with their projections (a cross-attention op projects queries
alone), both softmax maps' flash kernels (`flash_diff`), the
difference, the pairs' norm and the output projection, by the join
table the program writes (`benchmarks/step_parts.py`). Where the
program has no such scope the table holds no such row and the reader
returns nothing."""

from benchmarks import step_parts

STEM = "jit(attention_diff_"


def read(ctx):
    table = step_parts.find_table(ctx, __file__)
    if not table:
        return None
    inside = {n: dict(part="attention_diff", direction=row["direction"])
              for n, row in table.items() if STEM in row["op_name"]}
    got = step_parts.reduce(ctx["devices"], inside) if inside else None
    return sum(got["share_pct"].values()) or None if got else None
