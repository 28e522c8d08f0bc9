"""Share of its roofline that the expert layers' grouped products reach:
the least time for a step's products, forward and backward, counted for
the (token, slot) pairs that landed on held experts in the run's last
epoch (`grouped_matmul_step_flops_and_bytes` of the family), over the
device time a step under the `moe_grouped_matmul` scope."""

from benchmarks import scope_reduce


def read(ctx):
    family, sizes = ctx["family"], ctx["counters"]["sizes"]
    count = getattr(family, "grouped_matmul_step_flops_and_bytes", None)
    if count is None:
        return None
    held = (family.observed.get("op_counters") or {}).get("moe/slots_held")
    layers = family.pattern_of(sizes).count("E")
    # the counter adds up the layers and the steps of one epoch
    slots = held / (layers * sizes["steps_per_epoch"]) if held else None
    return scope_reduce.roofline_pct(ctx, "moe_grouped_matmul",
                                     *count(sizes, slots))
