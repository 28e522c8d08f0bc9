"""Per-layer roofline attribution for a compiled model.

The evidence channel behind conv-family optimization decisions (ISSUE 2):
each materialized op is slope-timed standalone on the live device (the
BENCH_NOTES methodology — two loop lengths cancel the per-call dispatch
and fetch cost, search/profile.measure_op), its analytic FLOPs and HBM
bytes give an arithmetic intensity, and comparing against the chip's
peaks names the op compute-bound or bandwidth-bound. The per-class
aggregates (conv family vs matmul family) are what
``MachineSpec.conv_efficiency`` / ``machine_to_json`` feed back into the
native cost model, so predicted conv times track measured ones.

Emitted as JSON rows (machine-readable, scripts/roofline.py commits them)
plus a markdown table for BENCH_NOTES.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from flexflow_tpu.ffconst import OperatorType

# op-class buckets for the per-class efficiency aggregates
CONV_FAMILY = {OperatorType.CONV2D, OperatorType.POOL2D,
               OperatorType.BATCHNORM, OperatorType.GROUPNORM}
MATMUL_FAMILY = {OperatorType.LINEAR, OperatorType.BATCHMATMUL,
                 OperatorType.MULTIHEAD_ATTENTION, OperatorType.EXPERTS,
                 OperatorType.EINSUM}


def _op_class(op) -> str:
    if op.op_type in CONV_FAMILY:
        return "conv"
    if op.op_type in MATMUL_FAMILY:
        return "matmul"
    return "other"


def roofline_report(nodes, machine_spec, repeats: int = 3, warmup: int = 1,
                    dtype_size: float = 4.0,
                    include_bwd: bool = True) -> Dict[str, Any]:
    """Time every op in an OpNode list and attribute it on the roofline.

    Returns ``{"rows": [...], "classes": {...}, "machine": {...}}``.
    Each row: op name/type/class, shapes, flops, bytes, intensity
    (flop/byte), measured fwd/bwd seconds, achieved FLOP/s and bytes/s,
    MFU (fraction of chip peak), and ``bound`` — which roofline wall the
    op sits under at the machine's ridge point. Ops whose standalone
    forward cannot run are reported with ``error`` instead of numbers.
    """
    from flexflow_tpu.search.profile import measure_op, op_io_bytes

    peak_flops = float(machine_spec.flops)
    hbm_bw = float(machine_spec.hbm_bw)
    ridge = peak_flops / hbm_bw  # flop/byte where the two walls meet
    rows: List[Dict[str, Any]] = []
    for node in nodes:
        op = node.op
        row: Dict[str, Any] = dict(
            name=op.name,
            type=op.op_type.name,
            op_class=_op_class(op),
            layout=getattr(op, "exec_layout", "NCHW"),
            input_shapes=[list(s) for s in op.input_shapes],
            output_shapes=[list(s) for s in op.output_shapes],
        )
        flops = float(op.flops())
        bytes_ = op_io_bytes(op, dtype_size)
        row["flops"] = flops
        row["bytes"] = bytes_
        row["intensity"] = flops / bytes_ if bytes_ else None
        # which wall the op sits under *analytically*, independent of how
        # well the kernel runs: under the ridge point it cannot beat HBM
        row["bound"] = ("compute" if bytes_ and flops / bytes_ >= ridge
                        else "bandwidth")
        try:
            fwd_s, bwd_s = measure_op(op, repeats=repeats, warmup=warmup,
                                      hbm_bw=hbm_bw,
                                      include_bwd=include_bwd)
        except Exception as e:  # standalone-unrunnable op: keep the row
            row["error"] = f"{type(e).__name__}: {e}"
            rows.append(row)
            continue
        row["fwd_s"] = fwd_s
        if include_bwd:
            row["bwd_s"] = bwd_s
        row["achieved_flops"] = flops / fwd_s if fwd_s else None
        row["achieved_bw"] = bytes_ / fwd_s if fwd_s else None
        row["mfu"] = flops / fwd_s / peak_flops if fwd_s else None
        row["hbm_frac"] = bytes_ / fwd_s / hbm_bw if fwd_s else None
        rows.append(row)
    return dict(rows=rows, classes=class_aggregates(rows),
                machine=dict(chip=machine_spec.chip, peak_flops=peak_flops,
                             hbm_bw=hbm_bw, ridge_intensity=ridge))


def class_aggregates(rows) -> Dict[str, Dict[str, float]]:
    """Per-op-class totals: the conv-vs-matmul efficiency evidence. The
    ``efficiency`` figure (class FLOPs / class measured time / peak) is
    the number to feed ``MachineSpec.conv_efficiency``."""
    agg: Dict[str, Dict[str, float]] = {}
    for r in rows:
        if "fwd_s" not in r:
            continue
        a = agg.setdefault(r["op_class"],
                           dict(ops=0, flops=0.0, bytes=0.0, fwd_s=0.0))
        a["ops"] += 1
        a["flops"] += r["flops"]
        a["bytes"] += r["bytes"]
        a["fwd_s"] += r["fwd_s"]
    return agg


def finish_aggregates(agg, peak_flops: float) -> None:
    """Attach achieved-FLOP/s and efficiency to class aggregates in
    place (separate from collection so callers can merge reports)."""
    for a in agg.values():
        t = a.get("fwd_s") or 0.0
        a["achieved_flops"] = a["flops"] / t if t else None
        a["efficiency"] = a["flops"] / t / peak_flops if t else None


def format_markdown(report, top: Optional[int] = 20) -> str:
    """Markdown roofline table, heaviest ops first (by measured fwd
    time), plus the per-class aggregate block."""
    rows = [r for r in report["rows"] if "fwd_s" in r]
    rows.sort(key=lambda r: -r["fwd_s"])
    skipped = len(report["rows"]) - len(rows)
    lines = [
        "| op | class | layout | fwd us | GFLOP/s | GB/s | MFU | bound |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows[:top]:
        lines.append(
            f"| {r['name']} | {r['op_class']} | {r['layout']} "
            f"| {r['fwd_s'] * 1e6:.1f} "
            f"| {(r['achieved_flops'] or 0) / 1e9:.1f} "
            f"| {(r['achieved_bw'] or 0) / 1e9:.1f} "
            f"| {(r['mfu'] or 0) * 100:.2f}% | {r['bound']} |")
    if top and len(rows) > top:
        lines.append(f"| ... ({len(rows) - top} more ops) | | | | | | | |")
    if skipped:
        lines.append(f"\n({skipped} ops unmeasurable standalone — see the "
                     f"JSON rows' `error` fields)")
    agg = dict(report["classes"])
    finish_aggregates(agg, report["machine"]["peak_flops"])
    lines.append("\nPer-class aggregates (feed `efficiency` of the conv "
                 "class to `MachineSpec.conv_efficiency`):\n")
    lines.append("| class | ops | total fwd ms | GFLOP/s | efficiency |")
    lines.append("|---|---|---|---|---|")
    for name, a in sorted(agg.items()):
        lines.append(
            f"| {name} | {a['ops']} | {a['fwd_s'] * 1e3:.2f} "
            f"| {(a['achieved_flops'] or 0) / 1e9:.1f} "
            f"| {(a['efficiency'] or 0) * 100:.2f}% |")
    bw_bound = sum(1 for r in rows if r["bound"] == "bandwidth")
    lines.append(f"\n{bw_bound}/{len(rows)} measured ops are "
                 f"bandwidth-bound at the machine ridge point "
                 f"({report['machine']['ridge_intensity']:.1f} flop/byte).")
    return "\n".join(lines)
