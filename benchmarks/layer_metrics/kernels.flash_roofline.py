"""Share of its roofline that the flash attention forward and backward
reach: the least time the chip could take for a step's attention (the
larger of FLOPs over the bf16 peak and bytes over the HBM peak) over the
summed device time of the step's `tpu_custom_call*` events. Listed only
for cells in which every such event is a flash kernel."""

from benchmarks import trace_reduce as tr


def read(ctx):
    got = tr.mean_over_devices(ctx["devices"], tr.kernel_seconds)
    if got is None or not got[0] or not got[1]:
        return None
    seconds, steps = got
    c = ctx["counters"]
    flops, nbytes = ctx["family"].flash_step_flops_and_bytes(c["sizes"])
    least = max(flops / c["peaks"]["bf16_flops_per_s"],
                nbytes / c["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
