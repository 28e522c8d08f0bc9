"""Share of its roofline that the gate-convolution-gate of the short
convolution ops reaches: the least time for a step's passes, forward
and backward (`gated_conv_step_flops_and_bytes` of the family: B, C, x
in and y out, then dy, B, C, x in and dB, dC, dx out, bfloat16: bytes,
the FLOPs are nothing beside them), over the device time a step of the
events whose `op_name` holds `jit(gated_conv)`, read through the join
table the program writes (`benchmarks/step_parts.py`). The count is of
the work and not of what implements it: a pass that reads an operand
twice, keeps a float32 copy or splits into several fusions costs time
and adds no work, so it lowers the share and can never lift it over
100. Where the family has no such count or the program no such scope the
reader returns nothing."""

from benchmarks import step_parts

SCOPE = "gated_conv"


def read(ctx):
    count = getattr(ctx["family"], "gated_conv_step_flops_and_bytes", None)
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
