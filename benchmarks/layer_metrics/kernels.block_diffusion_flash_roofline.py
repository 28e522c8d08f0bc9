"""Share of its roofline that the flash kernels under the block-diffusion
mask reach: the least time for a step's attention cores, forward and
backward, over the visible pairs counted exactly
(`block_diffusion_flash_step_flops_and_bytes` of the family), over the
device time a step under the `flash_block_diffusion` scope. A kernel that
visits tiles no query of which sees a key, or masks where it could skip,
spends that time here and reads lower."""

from benchmarks import scope_reduce


def read(ctx):
    count = getattr(ctx["family"],
                    "block_diffusion_flash_step_flops_and_bytes", None)
    if count is None:
        return None
    return scope_reduce.roofline_pct(ctx, "flash_block_diffusion",
                                     *count(ctx["counters"]["sizes"]))
