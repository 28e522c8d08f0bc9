"""Share of its roofline that the indexers' kernels reach: the least
time for a step's index scores over every causal pair and the
selection's one read of them, plus the loss's recomputation and the
three gradient products over the kept pairs
(`index_select_step_flops_and_bytes` of the family, at the bfloat16 peak
or the HBM peak, whichever binds), over the device time a step of the
KERNEL events under the program's `sparse_indexer` scope (`op_name`
holds `jit(sparse_indexer)` and `pallas_call`: `index_select` and
`index_kl`), read through the join table the program writes
(`benchmarks/step_parts.py`). The count is of the work the mechanism
requires and not of what implements it: float32 index products (several
MXU passes each), the selection's bisection passes, heads of 64 on a
128-wide MXU and the gradient products over every causal tile all lower
the share and none can lift it over 100. Where the family has no such
count or the program no such kernels the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "sparse_indexer"


def read(ctx):
    count = getattr(ctx["family"], "index_select_step_flops_and_bytes", None)
    table = step_parts.find_table(ctx, __file__)
    peaks = ctx["counters"]["peaks"]
    if count is None or not table or not peaks:
        return None
    inside = {n: dict(part=SCOPE, direction=row["direction"])
              for n, row in table.items()
              if f"jit({SCOPE})" in row["op_name"]
              and "pallas_call" in row["op_name"]}
    got = step_parts.reduce(ctx["devices"], inside) if inside else None
    seconds = sum(got["ms_a_step"].values()) / 1e3 if got else 0.0
    if not seconds:
        return None
    flops, nbytes = count(ctx["counters"]["sizes"])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
