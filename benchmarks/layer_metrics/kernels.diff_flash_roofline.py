"""Share of its roofline that the flash kernels of the differential
attention ops reach: the least time for a step's attention cores, both
softmax maps, forward and backward, over the VISIBLE pairs alone
(`diff_flash_step_flops_and_bytes` of the family: 12 * pairs * heads *
head size * 1.5 FLOPs an op, the values' products being twice as wide as
the scores'; the bfloat16 q, k, v, o and their gradients beside them,
whichever binds), over the device time a step of the events whose
`op_name` holds `jit(flash_diff)`, whatever implements it, read through
the join table the program writes (`benchmarks/step_parts.py`). The
program runs a map as a flash call at heads of twice the width with the
other query head's lanes zeroed, so the scores' products contract 128
lanes where the model's count has 64: that lowers the share and can
never lift it over 100. Where the family has no such count or the
program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_diff"


def read(ctx):
    count = getattr(ctx["family"], "diff_flash_step_flops_and_bytes", None)
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
