"""Share of the traced train steps' device-busy time under the program's
`hyper_connection` scope: the two halves of every hyper-connection
(`HC_PRE`, `HC_POST`), forward and backward, the multi-token-prediction
module's among them: the read pass (statistic, the products with phi,
the read map and the branch's input), the maps (sigmoids, the Sinkhorn
steps and their backward) and the write pass, by the join table the
program writes (`benchmarks/step_parts.py`): the events whose `op_name`
holds `jit(hyper_connection)`. The streams' birth (a concatenation) and
their merge (three adds) are not in it. Where the program has no such
scope (another family, an older program) the table holds no such row and
the reader returns nothing. It also asks for the whole part x direction
breakdown, so that a traced run of the cell leaves `step_parts.json`
beside its session as the other cells' runs do."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)
    return step_parts.scope_share_pct(ctx, __file__, "hyper_connection") \
        or None
