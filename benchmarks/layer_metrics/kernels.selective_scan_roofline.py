"""Share of its roofline that the selective scan of the Mamba-1 mixers
reaches: the least time for a step's scans, forward and backward, by
the BYTES `selective_scan_step_flops_and_bytes` of the family counts (x,
dt, B, C in and y out, their gradients back) at the HBM peak, over the
device time a step of the events whose `op_name` holds
`jit(selective_scan)`: the two kernels and the passes that lay their
operands out, read through the join table the program writes
(`benchmarks/step_parts.py`). The kernel is bound by a vector unit's
element-wise work (an exponential and seven multiply-adds a channel and
state), for which `peaks.json` has no peak, so the share stays far under
100 by construction; PERF.md says beside the number what it can reach.
The count is of the work and not of what implements it, so a pass that
reads an operand twice or keeps a float32 copy lowers the share and can
never lift it over 100. Where the family has no such count or the
program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "selective_scan"


def read(ctx):
    count = getattr(ctx["family"], "selective_scan_step_flops_and_bytes",
                    None)
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
    _, nbytes = count(ctx["counters"]["sizes"])
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
