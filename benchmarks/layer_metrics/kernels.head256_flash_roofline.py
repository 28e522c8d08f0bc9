"""Share of its roofline that the flash kernels reach at a head of 256
lanes: `kernels.causal_flash_roofline`'s formula with the family's count
for this head (`flash_step_flops_and_bytes`: 14 * pairs * heads * head
size FLOPs an op over the VISIBLE causal pairs, forward two products and
backward five; the bfloat16 q, k, v, o and their gradients beside them,
whichever binds), over the device time a step of the events whose
`op_name` holds `jit(flash_full)`, whatever implements it (three kernels
here: the forward, dQ, and dK with dV, which form the scores twice
backward: that lowers the share), read through the join table the
program writes (`benchmarks/step_parts.py`). Where the family has no such
count or the program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_full"


def read(ctx):
    count = getattr(ctx["family"], "flash_step_flops_and_bytes", None)
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
