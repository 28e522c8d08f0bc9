"""Share of the traced steps' device-busy time under the program's
`attention_window` scope: the sliding-window attention ops, forward and
backward, with their projections, rotary embedding and flash kernels."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "attention_window")
