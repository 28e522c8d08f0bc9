#!/usr/bin/env python3
"""The hand look behind the Mamba-2 scan's kernel pair (PR 62), at the
nemotron cell's shape: 8,192 positions, 8 heads of 64 on one group, a
state of 128, chunks of 128, bfloat16.

On the chip every piece is one jitted program, run five times under the
profiler; `<piece>_device_ms` is the median device time of its program
and `<piece>_device_ops` its ops by stem (`moe_combine_lab.device_ms`).
A `.grad` piece is the value and the gradients of a weighted sum of it,
what a train step runs; a `.fwd` piece the value alone.

- `scan.kernel.{fwd,grad}`: `pallas_kernels.ssd_scan`, ONE kernel each
  way from the convolution's [x ; B ; C], dt, A, D to y with D x (what
  ships where `SSMMixer.scans_by_kernel` says so);
- `scan.chunked.{fwd,grad}`: `ops.ssm.ssd_chunked` and D x over the
  `[b, s, h, p]` views of that array's slices, as the mixer ran it until
  PR 62 and runs it where Pallas is off: its ops by stem name what the
  kernels replace;
- `scan.rows<R>.{fwd,grad}`: the kernels at other blocks of rows a grid
  step (`pallas_kernels.SSD_ROWS`); `scan.unrolled.{fwd,grad}`: a
  block's chunks as straight-line code (the chunk loop's `unroll`:
  Mosaic takes 1 or all);
- `scan.sums_six_passes.{fwd,grad}`: a chunk's running sums of dt A and
  their transpose as float32 products at `Precision.HIGHEST` (six MXU
  passes each) where the shipped kernels send the three bfloat16 pieces
  of the float32 operand through ONE pass against the zeros and ones
  (`pallas_kernels._by_pieces`: the same float32 numbers);
- `scan.f32_cotangent.grad`: the backward's products with the float32
  cotangent as an operand (PR 58's form: the chunk's plain `jax.vjp`),
  not rounded to the operands' dtype first;
- `check`: both forms against `ssd_stepwise` at 2,048 positions (largest
  error over the largest value, bfloat16 operands), and the kernel's
  gradients against the chunked form's.

Prints one JSON line and writes it to `chiprun_out/ssd_lab.json`.
`--tiny` runs small shapes wherever it is, the kernels interpreted (a
rehearsal: its times mean nothing). Nothing here is a benchmark metric.

    python scripts/ssd_lab.py [--tiny] [--only kernel]
"""

import argparse
import json
import operator
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from delta_lab import traced_holding    # noqa: E402  (this directory's)
from moe_combine_lab import device_ms   # noqa: E402

ARGNUMS = tuple(range(4))
LEAVES = ("xbc", "dt", "a", "d")


def scan_inputs(batch, seq, heads, p, groups, n, dtype, seed=0):
    """(xbc, dt, a, d) as the mixer hands them to the scan, xbc = [x ; B ;
    C] along the lanes, and a weight for the output."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    xbc = jax.random.normal(
        ks[0], (batch, seq, heads * p + 2 * groups * n)).astype(dtype)
    # steps from 1e-3 to 1e-1 and A in [1, 16), as the op draws them:
    # heads that forget inside a chunk and heads that remember across it
    dt = jnp.exp(jax.random.uniform(ks[1], (batch, seq, heads),
                                    minval=-6.9, maxval=-2.3))
    a = -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0)
    d = 1.0 + 0.1 * jax.random.normal(ks[3], (heads,))
    wgt = jax.random.normal(ks[6], (batch, seq, heads * p))
    return (xbc, dt, a, d), wgt


def in_views(fn, groups, n, **kw):
    """``fn`` of `ops.ssm` (x [B, S, H, P], dt, a, bm, cm [B, S, G, N])
    with D x, over the kernel's operand forms."""
    def scan(xbc, dt, a, d):
        import jax.numpy as jnp
        b, s, lanes = xbc.shape
        width = lanes - 2 * groups * n
        xs = xbc[..., :width].reshape(b, s, dt.shape[-1], -1)
        y = fn(xs, dt, a,
               xbc[..., width:width + groups * n].reshape(b, s, groups, n),
               xbc[..., width + groups * n:].reshape(b, s, groups, n), **kw)
        y = y + d.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
        return y.reshape(b, s, width)

    return scan


def forms():
    """name -> what to hold in place while the kernel pair is traced."""
    import functools

    import jax

    from flexflow_tpu.ops import pallas_kernels as pk
    out = {"kernel": []}
    out.update(("rows%d" % rows, [(pk, "SSD_ROWS", rows)])
               for rows in (512, 2048))
    out["unrolled"] = [(jax.lax, "fori_loop", functools.partial(
        jax.lax.fori_loop, unroll=True))]
    out["f32_cotangent"] = [(pk, "_dot_rounding_back", pk._dot)]

    def ones(x, relation):
        return pk._ones(x.shape[0], relation).astype(jax.numpy.float32)

    out["sums_six_passes"] = [
        (pk, "_running_sums", lambda x: pk._dot32(
            ones(x, operator.ge), x, pk._NN)),
        (pk, "_rows_along_lanes", lambda x: pk._dot32(
            x, ones(x, operator.eq), pk._TN))]
    return out


def pieces(batch, seq, heads, p, groups, n, dtype, chunk):
    """name -> (jitted function, arguments)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.ssm import ssd_chunked

    ins, wgt = scan_inputs(batch, seq, heads, p, groups, n, dtype)
    out = {}

    def named(name, fn):
        fn.__name__ = name.replace(".", "_")
        return jax.jit(fn)

    def both(name, fn, held=()):
        def grads(wgt, *a):
            return jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * wgt), argnums=ARGNUMS)(*a)

        out[name + ".fwd"] = (named(name + ".fwd", traced_holding(held, fn)),
                              ins)
        out[name + ".grad"] = (named(name + ".grad", traced_holding(
            held, grads)), (wgt,) + ins)

    for form, held in forms().items():
        both("scan." + form, lambda *a: pk.ssd_scan(*a, groups, n, chunk), held)
    both("scan.chunked", in_views(ssd_chunked, groups, n, chunk=chunk,
                                  compute_dtype=dtype))
    return out


def check(batch, seq, heads, p, groups, n, dtype, chunk):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.ssm import ssd_chunked, ssd_stepwise
    ins, wgt = scan_inputs(batch, seq, heads, p, groups, n, dtype, seed=5)

    def of(fn):
        out, grads = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * wgt), argnums=ARGNUMS))(*ins)
        return [jax.jit(fn)(*ins)] + [g.astype(jnp.float32) for g in grads]

    def far(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    with jax.default_matmul_precision("highest"):
        want = of(in_views(ssd_stepwise, groups, n))
    got = {"kernel": of(lambda *a: pk.ssd_scan(*a, groups, n, chunk)),
           "chunked": of(in_views(ssd_chunked, groups, n, chunk=chunk,
                                  compute_dtype=dtype))}
    line = {name + "_vs_stepwise": dict(
        (leaf, far(a, b)) for leaf, a, b in zip(("y",) + LEAVES, o, want))
        for name, o in got.items()}
    line["kernel_vs_chunked"] = dict(
        (leaf, far(a, b)) for leaf, a, b in zip(
            ("y",) + LEAVES, got["kernel"], got["chunked"]))
    return line


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default="", help="pieces whose name holds this")
    opts = ap.parse_args()
    if opts.tiny:
        os.environ.setdefault("FLEXFLOW_TPU_PALLAS", "interpret")
    import jax
    import jax.numpy as jnp

    if not opts.tiny and jax.devices()[0].platform != "tpu":
        sys.exit("ssd_lab: no TPU here (try --tiny)")
    if opts.tiny:
        shape = dict(batch=1, seq=512, heads=2, p=64, groups=1, n=128,
                     dtype=jnp.float32, chunk=128)
        short = 256
    else:
        shape = dict(batch=1, seq=8192, heads=8, p=64, groups=1, n=128,
                     dtype=jnp.bfloat16, chunk=128)
        short = 2048
    line = dict(device=str(jax.devices()[0].device_kind), tiny=opts.tiny,
                seq=shape["seq"])
    jitted = {k: v for k, v in pieces(**shape).items() if opts.only in k}
    for name, (fn, args) in jitted.items():     # compile outside the trace
        jax.block_until_ready(fn(*args))
    if opts.tiny:   # the CPU's profile has no device lines
        line["ran"] = sorted(jitted)
    elif jitted:
        for name, (ms, ops) in device_ms(jitted, stems=12).items():
            line[name + "_device_ms"] = round(ms, 4)
            line[name + "_device_ops"] = ops
    if opts.only in "check":
        line["check"] = check(**dict(shape, seq=short))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_lab.json", "w") as f:
        json.dump(line, f)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
