"""Share of its roofline that the flash kernels of the latent-attention
ops reach: the least time for a step's attention cores, forward and
backward, over the causal pairs counted exactly
(`latent_flash_step_flops_and_bytes` of the family: the 192-wide
query/key head and the 128-wide value head, the bytes of the operands in
the form the kernel is handed), over the device time a step under the
`flash_latent` scope, read through the join table the program writes
(`benchmarks/step_parts.py`). Where the family has no such count or the
program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_latent"


def read(ctx):
    count = getattr(ctx["family"], "latent_flash_step_flops_and_bytes", None)
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
