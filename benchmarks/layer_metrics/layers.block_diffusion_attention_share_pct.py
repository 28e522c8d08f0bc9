"""Share of the traced steps' device-busy time under the program's
`attention_block_diffusion` scope: the attention ops under the
block-diffusion mask, forward and backward, with their projections, the
query/key norms, rotary embedding, key/value repeat and flash kernels.
Where the program has no such scope (another family, an older program)
the reader finds nothing and returns nothing."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share_pct(ctx, "attention_block_diffusion")
