"""Share of the traced train steps' device-busy time under the program's
`mamba_mixer` scope: the Mamba-1 mixers, forward and backward, with
their input projection (E -> 2 d_inner), the causal depthwise
convolution, the projections of dt, B and C, the selective scan
(`selective_scan`, which `kernels.selective_scan_roofline` reads alone,
with the passes that lay its operands out) and the output projection,
by the join table the program writes (`benchmarks/step_parts.py`). Where
the program has no such scope (no Mamba-1 op, an older program) the
table holds no such row and the reader returns nothing."""

from benchmarks import step_parts


def read(ctx):
    step_parts.reduced(ctx, __file__)   # leaves step_parts.json too
    return step_parts.scope_share_pct(ctx, __file__, "mamba_mixer") or None
