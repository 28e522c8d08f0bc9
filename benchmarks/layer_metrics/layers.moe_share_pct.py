"""Share of the traced steps' device-busy time under the program's
`moe_layer` scope (router, grouped products, shared expert, forward and
backward)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "moe_layer")
