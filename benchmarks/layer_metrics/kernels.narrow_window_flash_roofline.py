"""Share of its roofline that the flash kernels of the window layers
reach where the window is narrower than a K chunk: the least time for a
step's window attention cores, forward and backward, with their work
counted over the VISIBLE pairs alone
(`narrow_window_flash_step_flops_and_bytes` of the family: heads x (S W -
W (W - 1) / 2) pairs x 4 head_dim FLOPs forward and 8 head_dim backward),
over the device time a step under the `flash_window` scope, read through
the join table the program writes (`benchmarks/step_parts.py`). A key
that a tile visits and the mask hides costs time and adds no work: it
lowers the share and can never lift it over 100. Where the family has no
such count or the program no such scope the reader returns nothing."""

from benchmarks import step_parts

SCOPE = "flash_window"


def read(ctx):
    count = getattr(ctx["family"],
                    "narrow_window_flash_step_flops_and_bytes", None)
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
