"""Model FLOP/s utilization: FLOPs the forward and backward of one sample
require, times the untraced part-A samples a second of this run, over
chips times the bf16 peak of the device kind."""


def read(ctx):
    c = ctx["counters"]
    if not c["peaks"]:
        return None
    return (100.0 * c["train_flops_per_sample"] * c["throughput"]
            / (c["chips"] * c["peaks"]["bf16_flops_per_s"]))
