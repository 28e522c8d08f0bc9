"""Share of its roofline that the flash kernels of the sliding-window
layers reach: the least time for a step's window attention, forward and
backward, over the visible pairs counted exactly
(`window_flash_step_flops_and_bytes` of the family), over the device time
a step under the `flash_window` scope. A kernel that visits blocks no
query of which sees a key spends that time here and reads lower."""

from benchmarks import scope_reduce


def read(ctx):
    count = getattr(ctx["family"], "window_flash_step_flops_and_bytes", None)
    if count is None:
        return None
    return scope_reduce.roofline_pct(ctx, "flash_window",
                                     *count(ctx["counters"]["sizes"]))
