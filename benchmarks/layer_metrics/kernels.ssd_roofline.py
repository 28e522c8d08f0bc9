"""Share of its roofline that the chunked state-space scan reaches: the
least time for a step's scans, forward and backward
(`ssd_step_flops_and_bytes` of the family), over the device time a step
under the `ssd_scan` scope."""

from benchmarks import scope_reduce


def read(ctx):
    count = getattr(ctx["family"], "ssd_step_flops_and_bytes", None)
    if count is None:
        return None
    return scope_reduce.roofline_pct(ctx, "ssd_scan",
                                     *count(ctx["counters"]["sizes"]))
