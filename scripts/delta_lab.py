#!/usr/bin/env python3
"""The hand look behind the forms of the gated delta rule and of the
flash kernels at a head of 256 lanes (PR 58), at the qwen3_next cell's
shape: 16,384 positions, 16 key and 32 value heads of 128, bfloat16; 16
query heads on 2 key/value heads of 256.

On the chip every piece is one jitted program, run five times under the
profiler; `<piece>_device_ms` is the median device time of its program
and `<piece>_device_ops` its ops by stem (`moe_combine_lab.device_ms`).
A `.grad` piece is the value and the gradients of a weighted sum of it,
what a train step runs; a `.fwd` piece the value alone.

- `rule.kernel.{fwd,grad}`: `ops.delta_rule.delta_rule_core` (from the
  convolution's output to the output projection's input: the heads' L2
  norms, the rule, the gated head norm) as ONE kernel each way
  (`pallas_kernels.delta_rule_fused`: the inverse, W, U and the walk
  over the chunks in VMEM, the backward the chunk function's `jax.vjp`
  inside the kernel; what ships where `DeltaMixer.walks_by_kernel` says
  so);
- `rule.scan.{fwd,grad}`: the same in `jax.numpy`, the chunks' operands
  batched in XLA and the walk a `lax.scan` (where Pallas is off). (The
  RULE alone, q and k already normed and no head norm, was timed here
  in three forms before the norms joined the kernel: this one 25.8 /
  82.5 ms, its batched operands read by a kernel pair that only walked
  the chunks 32.8 / 87.6, one kernel each way 12.7 / 33.6.)
- `operands.{fwd,grad}`: the batched part alone (`_chunk_operands`: A,
  its inverse, W, U, the decayed q and k, the masked Q K^T);
- `inverse.doubling`, `inverse.doubling_default`, `inverse.solve`: the
  unit lower-triangular inverse of [1, 128, 32, 128, 128] float32 by six
  doublings at precision `highest` (what ships), the same at the
  default precision (one bfloat16 pass: a control of cost, not a
  candidate: float32 is stated), and `lax.linalg.triangular_solve`
  against the identity (what XLA serialises);
- `flash256.{fwd,grad}`: `pallas_kernels.flash_attention` at 16 : 2 heads
  of 256, causal; `flash128.{fwd,grad}`: the same pairs and lanes as 32 : 4
  heads of 128 through the chunk-loop kernels, for scale;
- `check`: the kernel form against `delta_rule_stepwise` at 2,048
  positions (max abs error over the largest value, bfloat16 operands).

Prints one JSON line and writes it to `chiprun_out/delta_lab.json`.
`--tiny` runs small shapes wherever it is, the kernels interpreted (a
rehearsal: its times mean nothing). Nothing here is a benchmark metric.

    python scripts/delta_lab.py [--tiny] [--only rule]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moe_combine_lab import device_ms   # noqa: E402  (this directory's)


def rule_inputs(seq, hk, hv, d, dtype, seed=0):
    """(qkv, z, g, beta, the norm's scale) as the op hands them to
    `delta_rule_core`, and a weight for the output."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkv = jax.random.normal(ks[0], (1, seq, (2 * hk + hv) * d))
    z = jax.random.normal(ks[1], (1, seq, hv * d))
    # decays from heads that forget inside a chunk to heads that remember
    g = -jnp.exp(jax.random.uniform(ks[3], (1, seq, hv), minval=-7.0,
                                    maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, hv)))
    wgt = jax.random.normal(ks[5], (1, seq, hv * d))
    return (qkv.astype(dtype), z.astype(dtype), g, beta,
            jnp.ones((d,), jnp.float32)), wgt


def normed_heads(qkv, hk, hv, d):
    """q, k (after the SiLU, L2-normed, q scaled) and v out of qkv,
    float32, as the stepwise form takes them."""
    import jax
    import jax.numpy as jnp
    b, s, _ = qkv.shape
    qkv = jax.nn.silu(qkv.astype(jnp.float32))
    q = qkv[..., :hk * d].reshape(b, s, hk, d)
    k = qkv[..., hk * d:2 * hk * d].reshape(b, s, hk, d)
    v = qkv[..., 2 * hk * d:].reshape(b, s, hv, d)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    return q, k, v


def pieces(seq, hk, hv, d, heads256, dtype, chunk):
    """name -> (jitted function, arguments)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import delta_rule as dr
    from flexflow_tpu.ops import pallas_kernels as pk

    ins, wgt = rule_inputs(seq, hk, hv, d, dtype)
    out = {}

    def named(name, fn):
        fn.__name__ = name.replace(".", "_")
        return jax.jit(fn)

    def both(name, fn, args, wgt):
        out[name + ".fwd"] = (named(name + ".fwd", fn), args)
        out[name + ".grad"] = (named(name + ".grad", jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * wgt),
            argnums=tuple(range(len(args))))), args)

    both("rule.kernel", lambda *a: dr.delta_rule_core(
        *a, hk, chunk, 1e-6, dtype, True), ins, wgt)
    both("rule.scan", lambda *a: dr.delta_rule_core(
        *a, hk, chunk, 1e-6, dtype, False), ins, wgt)
    n = seq // chunk
    q, k, v = (t.astype(dtype) for t in normed_heads(ins[0], hk, hv, d))
    shaped = tuple(t.reshape((1, n, chunk) + t.shape[2:])
                   for t in (q, k, v, ins[2], ins[3]))

    def operands(*a):   # every result read once
        return sum(jnp.sum(t.astype(jnp.float32) * 1e-3)
                   for t in dr._chunk_operands(*a, cd=dtype)).reshape(1)

    both("operands", operands, shaped, jnp.ones((1,), jnp.float32))
    a = jnp.tril(0.1 * jax.random.normal(
        jax.random.PRNGKey(3), (1, n, hv, chunk, chunk)), -1)
    eye = jnp.broadcast_to(jnp.eye(chunk), a.shape)
    out["inverse.doubling"] = (named("inverse.doubling",
                                     dr.unit_lower_inverse), (a,))

    def doubling_default(a):
        inv, power, reach = jnp.eye(chunk) - a, a, 2
        while reach < chunk:
            power = power @ power
            inv = inv + inv @ power
            reach *= 2
        return inv

    out["inverse.doubling_default"] = (named("inverse.doubling_default",
                                             doubling_default), (a,))
    out["inverse.solve"] = (named(
        "inverse.solve", lambda a: jax.lax.linalg.triangular_solve(
            eye + a, eye, left_side=True, lower=True, unit_diagonal=True)),
        (a,))
    for name, (h, hkv, hd) in (("flash256", heads256),
                               ("flash128", (2 * heads256[0],
                                             2 * heads256[1],
                                             heads256[2] // 2))):
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        q = jax.random.normal(ks[0], (1, seq, h * hd)).astype(dtype)
        k = jax.random.normal(ks[1], (1, seq, hkv * hd))
        v = jax.random.normal(ks[2], (1, seq, hkv * hd))
        both(name, lambda q, k, v, h=h, hkv=hkv: pk.flash_attention(
            q, k, v, h, causal=True, num_kv_heads=hkv), (q, k, v),
            jax.random.normal(ks[3], (1, seq, h * hd)))
    return out


def check(seq, hk, hv, d, dtype, chunk):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import delta_rule as dr
    (qkv, z, g, beta, scale), _ = rule_inputs(seq, hk, hv, d, dtype, seed=5)
    with jax.default_matmul_precision("highest"):
        o = jax.jit(dr.delta_rule_stepwise)(*normed_heads(qkv, hk, hv, d),
                                            g, beta)
        want = dr.heads_rms_norm_gated(
            o, z.reshape(o.shape), scale, 1e-6).reshape(z.shape)
    got = {name: jax.jit(lambda *a, kernel=kernel: dr.delta_rule_core(
        *a, hk, chunk, 1e-6, dtype, kernel))(
            qkv, z, g, beta, scale).astype(jnp.float32)
        for name, kernel in (("kernel", True), ("scan", False))}
    top = float(jnp.max(jnp.abs(want)))
    return {name + "_vs_stepwise": float(jnp.max(jnp.abs(o - want))) / top
            for name, o in got.items()}


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
        sys.exit("delta_lab: no TPU here (try --tiny)")
    if opts.tiny:
        shape = dict(seq=256, hk=1, hv=2, d=128, heads256=(2, 1, 256),
                     dtype=jnp.float32, chunk=128)
        short = 256
    else:
        shape = dict(seq=16384, hk=16, hv=32, d=128, heads256=(16, 2, 256),
                     dtype=jnp.bfloat16, chunk=128)
        short = 2048
    line = dict(device=str(jax.devices()[0].device_kind), tiny=opts.tiny,
                seq=shape["seq"])
    jitted = {k: v for k, v in pieces(**shape).items() if opts.only in k}
    for name, (fn, args) in jitted.items():     # compile outside the trace
        jax.block_until_ready(fn(*args))
    if opts.tiny:   # the CPU's profile has no device lines
        line["ran"] = sorted(jitted)
    else:
        for name, (ms, ops) in device_ms(jitted).items():
            line[name + "_device_ms"] = round(ms, 4)
            line[name + "_device_ops"] = ops
    if opts.only in "check":
        line["check"] = check(short, shape["hk"], shape["hv"], shape["d"],
                              shape["dtype"], shape["chunk"])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_lab.json", "w") as f:
        json.dump(line, f)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
