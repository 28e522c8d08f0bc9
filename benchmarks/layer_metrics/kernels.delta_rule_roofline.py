"""Share of its roofline that the chunked gated delta rule reaches: the
least time for a step's rules, forward and backward
(`delta_rule_step_flops_and_bytes` of the family: the matrix products of
the chunked form at the shipped chunk size, three times the forward's,
against the bfloat16 peak, or q, k, v, o, their gradients and the
float32 decays once against the HBM's rate, whichever binds), over the
device time a step of the events whose `op_name` holds
`jit(delta_rule)`: the batched part that forms a chunk's operands (the
inverse among them, float32 products that cost several bfloat16 passes
and count once) and the kernels `delta_rule_fwd` / `delta_rule_bwd` that
walk the chunks, read through the join table the program writes
(`benchmarks/step_parts.py`). What recomputes, keeps a float32 copy or
waits on the chunk before lowers the share and can never lift it over
100. Where the family has no such count or the program no such scope the
reader returns nothing."""

from benchmarks import step_parts

SCOPE = "delta_rule"


def read(ctx):
    count = getattr(ctx["family"], "delta_rule_step_flops_and_bytes", None)
    table = step_parts.find_table(ctx, __file__)
    peaks = ctx["counters"]["peaks"]
    if count is None or not table or not peaks:
        return None
    inside = {n: dict(part=SCOPE, direction=row["direction"])
              for n, row in table.items()
              if f"jit({SCOPE})" in row["op_name"]}
    got = step_parts.reduce(ctx["devices"], inside) if inside else None
    seconds = sum(got["ms_a_step"].values()) / 1e3 if got else 0.0
    if not seconds:
        return None
    flops, nbytes = count(ctx["counters"]["sizes"])
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
