"""Device time under one of the program's named scopes.

`trace_reduce.load_xplane` keeps a device event's HLO instruction name and
drops its `op_name`. The family of a configuration whose per-layer metrics
read scopes keeps, from the compiled train step's HLO text, the `op_name` of
every instruction (`family.observed["scopes"]`, filled while the program is
still loaded); an event is under a scope if its instruction's `op_name`
holds the scope's name, forward (`jvp(...)`) and backward
(`transpose(jvp(...))`) alike. A fusion carries one `op_name`, its root's.
Where the family kept nothing (another family, a program without the
scopes, a failed lowering) the readers return nothing.
"""

from benchmarks import trace_reduce as tr


def scopes_of(ctx):
    return (getattr(ctx["family"], "observed", None) or {}).get("scopes")


def scope_seconds(dev, scopes, scope, module=tr.STEP_MODULE):
    """(device seconds of the events under `scope`, busy seconds, train
    steps) inside the device's window; enclosing ops left out."""
    w = tr.window(dev, module)
    if w is None:
        return None
    total = sum(d for n, s, d in dev.lines.get(tr.OPS, ())
                if w[0] <= s < w[1] and not n.startswith(tr.ENCLOSING)
                and scope in scopes.get(n, ""))
    busy = tr.length(tr.clip(tr.busy_intervals(dev), *w))
    return total, busy, len(tr.step_spans(dev, module))


def read(ctx, scope):
    """Mean over devices of `scope_seconds`, or None."""
    scopes = scopes_of(ctx)
    if not scopes:
        return None
    got = tr.mean_over_devices(
        ctx["devices"], lambda d: scope_seconds(d, scopes, scope))
    if got is None or not got[0] or not got[2]:
        return None
    return got


def share_pct(ctx, scope):
    got = read(ctx, scope)
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]


def roofline_pct(ctx, scope, flops, nbytes):
    """The least time the chip could take for `flops` and `nbytes` a step
    (the larger of FLOPs over the bf16 peak and bytes over the HBM peak)
    over the measured seconds a step under `scope`."""
    got = read(ctx, scope)
    peaks = ctx["counters"]["peaks"]
    if got is None or not peaks:
        return None
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (got[0] / got[2])
