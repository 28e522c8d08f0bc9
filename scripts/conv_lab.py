#!/usr/bin/env python3
"""The hand look behind the form of the gated short convolution's pass
between its two products (PR 45): y = C * conv(B * x) ALONE, forward and
backward, at the lfm2 cell's shape (16,384 positions, 2048 lanes, 3 taps,
bfloat16), in the forms the op can take.

On the chip every piece is one jitted program, run five times under the
profiler; `<piece>_device_ms` is the median device time of its program
and `<piece>_device_ops` its ops by stem (`moe_combine_lab.device_ms`).
Each piece is the value and the `vjp` of it at a cotangent, what a train
step runs of it:

- `gated_conv.xla`: `ops.short_conv.gated_conv`, jax.numpy with its own
  backward (it keeps the projection alone): XLA writes B * x out in
  float32, reads it back a tap at a time and splits the backward into
  five fusions;
- `gated_conv.autodiff`: the same forward left to autodiff;
- `gated_conv.kernel`: `pallas_kernels.gated_conv_lanes`, one pass each
  way (what ships where `ShortConv.in_one_pass` says so);
- `roofline_ms`: 22 * T * E bytes at the HBM peak, the count
  `benchmarks/families/lfm2.py` `gated_conv_step_flops_and_bytes` makes
  for one op.

Prints one JSON line and writes it to `chiprun_out/conv_lab.json`.
`--deviceless` compiles the pieces for a described v5e and reports
`sync_gb`, the bytes (operands + result) of the compiled program's
synchronous top-level instructions (`gate_lab.synchronous_bytes`; the
kernel's projection counts twice there: its block and its halo are two
operands). `--tiny` runs a small shape wherever it is, the kernel
interpreted, and compares the forms (a rehearsal: its times mean
nothing). Nothing here is a benchmark metric.

    python scripts/conv_lab.py [--tiny | --deviceless] [--only kernel]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gate_lab import synchronous_bytes   # noqa: E402  (this directory's)
from moe_combine_lab import device_ms   # noqa: E402

HBM_BYTES_PER_S = 819e9     # benchmarks/peaks.json, TPU v5 lite


def pieces(seq, width, taps):
    """name -> (function of (proj, w, dy), shapes)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.short_conv import gated_conv
    from flexflow_tpu.ops.ssm import causal_depthwise_conv1d

    def autodiff(proj, w):
        b, c, x = (t.astype(jnp.float32) for t in jnp.split(proj, 3, -1))
        return (c * causal_depthwise_conv1d(b * x, w)).astype(proj.dtype)

    forms = {"gated_conv.xla": lambda p, w: gated_conv(p, w, True),
             "gated_conv.autodiff": autodiff,
             "gated_conv.kernel": lambda p, w: pk.gated_conv_lanes(p, w,
                                                                   True)}
    shapes = (jax.ShapeDtypeStruct((1, seq, 3 * width), jnp.bfloat16),
              jax.ShapeDtypeStruct((taps, width), jnp.float32),
              jax.ShapeDtypeStruct((1, seq, width), jnp.bfloat16))
    out = {}
    for name, form in forms.items():
        def both(proj, w, dy, form=form):
            y, vjp = jax.vjp(form, proj, w)
            return y, vjp(dy)

        both.__name__ = both.__qualname__ = name.replace(".", "_")
        out[name] = (both, shapes)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--deviceless", action="store_true")
    ap.add_argument("--only", default="", help="pieces whose name holds this")
    opts = ap.parse_args()
    if opts.deviceless:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if opts.tiny:
        os.environ.setdefault("FLEXFLOW_TPU_PALLAS", "interpret")
    import jax
    import numpy as np

    from flexflow_tpu.ops import pallas_kernels as pk

    if not (opts.tiny or opts.deviceless) and \
            jax.default_backend() != "tpu":
        sys.exit("conv_lab.py times the pieces on a TPU; --tiny rehearses, "
                 "--deviceless compiles and counts")
    seq, width, taps = (256, 256, 3) if opts.tiny else (16384, 2048, 3)
    line = dict(piece="gated_conv", seq=seq, width=width, taps=taps,
                roofline_ms=round(22 * seq * width / HBM_BYTES_PER_S * 1e3,
                                  4))
    chosen = {name: piece for name, piece in pieces(seq, width, taps).items()
              if opts.only in name}
    if opts.deviceless:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
        pk.pallas_mode = lambda: "tpu"      # the live backend is the CPU
        line["device"] = "deviceless v5e"
        for name, (fn, shapes) in chosen.items():
            hlo = jax.jit(fn).lower(*jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=chip),
                shapes)).compile().as_text()
            total, rows = synchronous_bytes(hlo)
            line[name] = dict(sync_gb=round(total / 1e9, 3),
                              instructions=len(rows))
        print(json.dumps(line), flush=True)
        return
    line["device"] = jax.devices()[0].device_kind
    rs = np.random.RandomState(0)
    jitted, outs, args = {}, {}, None
    for name, (fn, shapes) in chosen.items():
        # one set of operands for every form
        args = args or tuple(jax.numpy.asarray(rs.randn(*a.shape), a.dtype)
                             for a in shapes)
        jitted[name] = (jax.jit(fn), args)
        outs[name] = jax.block_until_ready(jitted[name][0](*args))
    if "gated_conv.kernel" in outs and "gated_conv.xla" in outs:
        # the two forms are one mathematics: every result's largest
        # difference over its largest entry
        line["kernel_vs_xla"] = [float(
            abs(a.astype("float32") - b.astype("float32")).max()
            / abs(b.astype("float32")).max())
            for a, b in zip(jax.tree.leaves(outs["gated_conv.kernel"]),
                            jax.tree.leaves(outs["gated_conv.xla"]))]
    for name, (ms, ops) in ({} if opts.tiny else device_ms(jitted)).items():
        line[name + "_device_ms"] = round(ms, 4)
        line[name + "_device_ops"] = ops
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "conv_lab.json"), "w") as f:
        json.dump(line, f, indent=1)


if __name__ == "__main__":
    main()
