"""Share of the traced train steps' device-busy time under the program's
`attention_latent` scope: the latent-attention ops, forward and backward,
with both latents' projections and norms, rotary embedding, the flash
kernels and the output projection (the multi-token-prediction module's
op among them), by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope
(another family, an older program) the table holds no such row and the
reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.scope_share_pct(ctx, __file__, "attention_latent") \
        or None
