"""Share of the traced train steps' device-busy time whose part is `mtp`:
the multi-token-prediction module's own ops (the shift of the embedding,
its two norms and projection, its latent attention and experts, its final
norm), forward and backward, by the join table the program writes
(`benchmarks/step_parts.py`). The module's half of the shared head and of
the loss is NOT in it: one product and one cross-entropy run over both
halves of the logits, and no instruction belongs to one half. Where the
program names no such part the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    return step_parts.part_share_pct(ctx, __file__, ("mtp",)) or None
