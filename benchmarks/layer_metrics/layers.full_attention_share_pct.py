"""Share of the traced steps' device-busy time under the program's
`attention_full` scope: the attention ops without a window (full causal
here), forward and backward, with their projections and flash kernels."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "attention_full")
