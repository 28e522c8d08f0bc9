"""Share of the traced train steps' device-busy time under the program's
`sparse_indexer` scope: the indexers of the learned-sparse-attention
ops, forward and backward: their three projections, the key's LayerNorm
and the rotary embedding, the scores of every causal pair and the exact
selection (`index_select`), the loss with its gradient (`index_kl`, which
recomputes the main heads' probabilities over the kept pairs), and the
projections' backward, by the join table the program writes
(`benchmarks/step_parts.py`). Where the program has no such scope the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)   # leaves step_parts.json too
    return step_parts.scope_share_pct(ctx, __file__, "sparse_indexer") or None
