"""Share of the traced steps' device-busy time under the program's
`ssm_mixer` scope (the Mamba-2 mixers, forward and backward, projections
included)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "ssm_mixer")
