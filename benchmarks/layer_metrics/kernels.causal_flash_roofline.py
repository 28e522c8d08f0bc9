"""Share of its roofline that the flash kernels of the plain causal
attention ops reach (the chunk-loop `flash_full` kernels): the least
time for a step's attention cores, forward and backward, over the
VISIBLE pairs alone (`causal_flash_step_flops_and_bytes` of the family:
12 * pairs * heads * head size FLOPs an op, the bfloat16 q, k, v, o and
their gradients beside them, whichever binds), over the device time a
step of the events whose `op_name` holds `jit(flash_full)`, whatever
implements it, read through the join table the program writes
(`benchmarks/step_parts.py`). A kernel that visits a tile's hidden half,
keeps a float32 copy or recomputes lowers the share and can never lift
it over 100. Where the family has no such count or the program no such
scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_full"


def read(ctx):
    count = getattr(ctx["family"], "causal_flash_step_flops_and_bytes", None)
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
