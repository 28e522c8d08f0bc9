"""Share of the traced train steps' device-busy time under the program's
`op_short_conv` scope: the gated short convolution ops, forward and
backward, with their input projection (E -> 3 E), the
gate-convolution-gate between (`gated_conv`, which
`kernels.gated_conv_roofline` reads alone) and the output projection,
by the join table the program writes (`benchmarks/step_parts.py`). The
op does not name itself: the executor wraps it in the nested call of
its kind, so an event's `op_name` holds `jit(op_short_conv)`. Where the
program has no such scope (no convolution op, an older program) the
table holds no such row and the reader returns nothing. It also asks
for the whole part x direction breakdown, so that a traced run of the
cell leaves `step_parts.json` beside its session as the other cells'
runs do (the accepted readers that write it are not listed here)."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)
    return step_parts.scope_share_pct(ctx, __file__, "op_short_conv") \
        or None
