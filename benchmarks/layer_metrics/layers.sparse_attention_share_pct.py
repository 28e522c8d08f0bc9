"""Share of the traced train steps' device-busy time under the program's
`attention_sparse` scope, less whatever of it also lies under
`sparse_indexer`: the learned-sparse-attention ops' main attention,
forward and backward, with its projections, the query/key norms, the
rotary embedding, the flash kernels over the kept pairs (`flash_sparse`,
the mask's transpose and summaries among them) and the output
projection, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts

SCOPE, INDEXER = "jit(attention_sparse)", "jit(sparse_indexer)"


def read(ctx):
    table = step_parts.find_table(ctx, __file__)
    if not table:
        return None
    inside = {n: dict(part="attention_sparse", direction=row["direction"])
              for n, row in table.items()
              if SCOPE in row["op_name"] and INDEXER not in row["op_name"]}
    got = step_parts.reduce(ctx["devices"], inside) if inside else None
    return sum(got["share_pct"].values()) or None if got else None
