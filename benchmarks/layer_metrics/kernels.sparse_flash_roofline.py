"""Share of its roofline that the main attention of the
learned-sparse-attention ops reaches: the least time for a step's
attention products over the KEPT pairs alone, forward and backward
(`sparse_flash_step_flops_and_bytes` of the family: 12 * kept pairs *
heads * head size FLOPs a layer, or the bfloat16 q, k, v, o and their
gradients, whichever binds), over the device time a step of the events
whose `op_name` holds `jit(flash_sparse)`, whatever implements it, read
through the join table the program writes (`benchmarks/step_parts.py`).
The program's kernels work through every causal tile under a mask that
is data (a query's kept keys lie scattered), so they visit 4.3 causal
pairs a kept one at 16,384 positions: that lowers the share and can
never lift it over 100, as `kernels.window_flash_roofline` reads a
kernel that visits hidden keys. Where the family has no such count or
the program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_sparse"


def read(ctx):
    count = getattr(ctx["family"], "sparse_flash_step_flops_and_bytes", None)
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
