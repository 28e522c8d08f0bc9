"""Share of the traced train steps' device-busy time under the program's
`delta_mixer` scope: the gated delta-rule mixers, forward and backward,
with their two input projections, the causal convolution with SiLU over
q, k and v, the heads' L2 norms, the chunked rule (`delta_rule`, which
`kernels.delta_rule_roofline` reads alone), the gated head norm and the
output projection, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope (no
delta mixer, an older program) the table holds no such row and the reader
returns nothing. It also asks for the whole part x direction breakdown,
so that a traced run of the cell leaves `step_parts.json` beside its
session as the other cells' runs do."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)
    return step_parts.scope_share_pct(ctx, __file__, "delta_mixer") or None
