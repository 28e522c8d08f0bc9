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
  inside the kernel; a grid step a KEY head whose value heads walk
  together since PR 60; what ships where `DeltaMixer.walks_by_kernel`
  says so);
- `rule.value_head_step.{fwd,grad}` (PR 60): a grid step one VALUE
  head, as PR 58 shipped the pair: the kernels that ship with
  `pallas_kernels.MAX_DELTA_HEADS_A_STEP` held to 1, so a key head's q
  and k fetched, normed and multiplied once a value head, dq and dk
  written at value-head width and the pairs added by XLA;
  `rule.key_head_serial.{fwd,grad}`: the shipped key-head step
  with each head's whole inverse (and its backward's pair of products)
  written one head after the other, not a doubling of every head at a
  time: what the interleaving alone gives;
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
- `flash256.two_kernels.grad` (PR 59): the backward as PR 58 shipped it,
  kept HERE alone (`two_kernel_wide_flash_bwd`: dQ a Q block over its K
  blocks, dK and dV a K block over its Q blocks, each forming the tile's
  P^T and dS^T), beside the ONE kernel that ships;
  `flash256.q<Bq>_k<Bk>_run<n>.grad`: the shipped kernel at other
  backward blocks and K blocks a run; `flash256.no_sums_traffic.grad`:
  the shipped kernel with dQ's copies to and from HBM left out (a WRONG
  dQ: a control of what the copies cost, not a candidate);
- `check`: the kernel form against `delta_rule_stepwise` at 2,048
  positions (max abs error over the largest value, bfloat16 operands);
  `rule_forms_vs_kernel`: the output and every gradient of the other
  two forms of the pair against the shipped one's there (largest
  difference over the largest value);
  `flash256_vs_two_kernels`: dQ, dK, dV of the shipped backward, of
  every block tried and of the control against the two-kernel form's
  (largest difference over the largest value; the control's dQ is far).

Prints one JSON line and writes it to `chiprun_out/delta_lab.json`.
`--tiny` runs small shapes wherever it is, the kernels interpreted (a
rehearsal: its times mean nothing). Nothing here is a benchmark metric.

    python scripts/delta_lab.py [--tiny] [--only rule]
"""

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

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


def _two_kernel_kernels():
    """PR 58's dQ and dK / dV kernels at a head of 256, for the lab's row
    and the test of the sum's order."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from flexflow_tpu.ops import pallas_kernels as pk

    def dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  acc_scr, *, scale, causal, window, blk_q, blk_k, nk):
        iq, ik = pl.program_id(2), pl.program_id(3)
        q0, k0 = iq * blk_q, ik * blk_k

        @pl.when(ik == 0)
        def _():
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def tile(masked):
            _, dst = pk._wide_bwd_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                                       lse_ref[0, 0], delta_ref[0, 0], q0,
                                       k0, masked, scale, window)
            acc_scr[...] += pk._dot(dst, k_ref[0], pk._TN)

        pk._wide_visit(tile, ik, pk._wide_k_range(iq, blk_q, blk_k, nk,
                                                  causal, window),
                       q0, k0, blk_q, blk_k, causal, window)

        @pl.when(ik == nk - 1)
        def _():
            dq_ref[0] = (acc_scr[...] * scale).astype(dq_ref.dtype)

    def dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                   dv_ref, dk_scr, dv_scr, *, scale, causal, window, blk_q,
                   blk_k, nq, rep):
        ik, r, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        q0, k0 = iq * blk_q, ik * blk_k

        @pl.when(jnp.logical_and(r == 0, iq == 0))
        def _():
            dk_scr[...] = jnp.zeros_like(dk_scr)
            dv_scr[...] = jnp.zeros_like(dv_scr)

        def tile(masked):
            pt, dst = pk._wide_bwd_tile(q_ref[0], k_ref[0], v_ref[0],
                                        do_ref[0], lse_ref[0, 0],
                                        delta_ref[0, 0], q0, k0, masked,
                                        scale, window)
            dv_scr[...] += pk._dot(pt.astype(do_ref.dtype), do_ref[0], pk._NN)
            dk_scr[...] += pk._dot(dst, q_ref[0], pk._NN)

        pk._wide_visit(tile, iq, pk._wide_q_range(ik, blk_q, blk_k, nq,
                                                  causal, window),
                       q0, k0, blk_q, blk_k, causal, window)

        @pl.when(jnp.logical_and(r == rep - 1, iq == nq - 1))
        def _():
            dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    return dq_kernel, dkv_kernel


def two_kernel_wide_flash_bwd(q, k, v, o, lse, do, num_heads, causal,
                              interpret, window, num_kv_heads, glse=None):
    """`pallas_kernels._wide_flash_bwd` as PR 58 shipped it (its
    signature): two `pallas_call`s, both forming a visited tile's P^T and
    dS^T; dQ's sum a Q block's float32 scratch over ascending K blocks."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from flexflow_tpu.ops import pallas_kernels as pk

    dq_kernel, dkv_kernel = _two_kernel_kernels()
    b, s, hd = q.shape
    d = hd // num_heads
    hk = num_kv_heads or num_heads
    rep = num_heads // hk
    window = pk.normalized_window(s, causal, window)
    blk_q, blk_k = pk._wide_blocks(s)
    nq, nk = s // blk_q, s // blk_k
    do, delta = pk._wide_delta(q, o, do, num_heads, glse)
    params = dict(scale=1.0 / float(d) ** 0.5, causal=causal, window=window,
                  blk_q=blk_q, blk_k=blk_k)
    key_block = pk._wide_key_block(blk_q, blk_k, nk, causal, window)

    def query_block(ik, iq):
        first, last = pk._wide_q_range(ik, blk_q, blk_k, nq, causal, window)
        return jnp.clip(iq, first, last) if causal else iq

    def of_key(b, h, i, j):
        return b, key_block(i, j), h // rep

    rows = pl.BlockSpec((1, blk_q, d), lambda b, h, i, j: (b, i, h))
    stat = pl.BlockSpec((1, 1, 1, blk_q), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(dq_kernel, nk=nk, **params),
        name=pk.KERNEL_NAME_PREFIX + "flash_bwd_wide_dq",
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, num_heads, nq, nk),
        in_specs=[rows, pl.BlockSpec((1, blk_k, d), of_key),
                  pl.BlockSpec((1, blk_k, d), of_key), rows, stat, stat],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(q, k, v, do, lse, delta)

    def of_query(b, g, j, r, i):
        return b, query_block(j, i), g * rep + r

    def row_of_query(b, g, j, r, i):
        return b, g * rep + r, 0, query_block(j, i)

    keys = pl.BlockSpec((1, blk_k, d), lambda b, g, j, r, i: (b, j, g))
    kv_dtype = jnp.float32 if rep > 1 else k.dtype
    dk, dv = pl.pallas_call(
        functools.partial(dkv_kernel, nq=nq, rep=rep, **params),
        name=pk.KERNEL_NAME_PREFIX + "flash_bwd_wide_dkv",
        out_shape=(jax.ShapeDtypeStruct(k.shape, kv_dtype),
                   jax.ShapeDtypeStruct(v.shape, kv_dtype)),
        grid=(b, hk, nk, rep, nq),
        in_specs=[pl.BlockSpec((1, blk_q, d), of_query), keys, keys,
                  pl.BlockSpec((1, blk_q, d), of_query),
                  pl.BlockSpec((1, 1, 1, blk_q), row_of_query),
                  pl.BlockSpec((1, 1, 1, blk_q), row_of_query)],
        out_specs=(keys, keys),
        scratch_shapes=[pltpu.VMEM((blk_k, d), jnp.float32)] * 2,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 << 20),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def rule_forms():
    """name -> what to hold in place while the rule's kernel pair is
    traced, [(object, attribute, value), ...]: the pair that ships; a
    grid step one VALUE head, as PR 58 shipped it (the same kernels held
    to one head a step: a key head's q and k fetched, normed and
    multiplied once a value head, dq and dk written at value-head width
    and the pairs added by XLA; a copy of PR 58's own kernels, timed
    beside it once in PR 60, read 13.52 + 9.09 ms where this reads 13.66
    + 8.91); and the key-head step with the heads' inverses (and their
    backward's products) one head after the other."""
    from flexflow_tpu.ops import pallas_kernels as pk
    tiles, pullbacks = (pk._unit_lower_inverse_tiles,
                        pk._unit_lower_inverse_pullbacks)
    return {
        "kernel": [],
        "value_head_step": [(pk, "MAX_DELTA_HEADS_A_STEP", 1)],
        "key_head_serial": [
            (pk, "_unit_lower_inverse_tiles",
             lambda many: [tiles([a])[0] for a in many]),
            (pk, "_unit_lower_inverse_pullbacks",
             lambda invs, dinvs: [pullbacks([t], [d])[0]
                                  for t, d in zip(invs, dinvs)])]}


class _NoCopy:
    """A copy that is never made (`flash256.no_sums_traffic`)."""

    def start(self):
        pass

    wait = start


# the backward's (Q block, K block, K blocks a run) the lab tries beside
# the shipped ones
BWD_BLOCKS = ((512, 1024, 1), (1024, 1024, 1), (1024, 1024, 2),
              (1024, 1024, 8), (512, 1024, 4), (512, 2048, 2))


def wide_bwd_forms(seq):
    """name -> what to hold in place while the backward at a head of 256
    is traced, [(object, attribute, value)]: the two-kernel form, the
    control without dQ's copies, the other blocks that divide ``seq``."""
    from flexflow_tpu.ops import pallas_kernels as pk
    forms = {"two_kernels": [(pk, "_wide_flash_bwd",
                              two_kernel_wide_flash_bwd)],
             "no_sums_traffic": [(pk.pltpu, "make_async_copy",
                                  lambda *a: _NoCopy())]}
    forms.update(("q%d_k%d_run%d" % blk,
                  [(pk, "_wide_bwd_blocks", lambda s, blk=blk: blk)])
                 for blk in BWD_BLOCKS
                 if seq % blk[0] == 0 and seq % (blk[1] * blk[2]) == 0)
    return forms


def traced_holding(held, fn):
    """``fn``, traced with every (object, attribute, value) of ``held``
    in place; as it is where ``held`` is empty."""
    if not held:
        return fn

    def run(*a):
        with contextlib.ExitStack() as stack:
            for one in held:
                stack.enter_context(mock.patch.object(*one))
            return fn(*a)

    return run


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

    def grad_of(name, fn, args, wgt, held=()):
        """The value and gradients of a weighted sum of ``fn`` (the
        weight an argument: no constant of the program), traced with
        ``held`` in place."""
        def grads(wgt, *a):
            return jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * wgt),
                argnums=tuple(range(len(args))))(*a)

        out[name + ".grad"] = (named(name + ".grad", traced_holding(
            held, grads)), (wgt,) + args)

    def both(name, fn, args, wgt, held=()):
        out[name + ".fwd"] = (named(name + ".fwd", traced_holding(held, fn)),
                              args)
        grad_of(name, fn, args, wgt, held)

    for form, held in rule_forms().items():
        both("rule." + form, lambda *a: dr.delta_rule_core(
            *a, hk, chunk, 1e-6, dtype, True), ins, wgt, held)
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
        wgt = jax.random.normal(ks[3], (1, seq, h * hd))

        def flash(q, k, v, h=h, hkv=hkv):
            return pk.flash_attention(q, k, v, h, causal=True,
                                      num_kv_heads=hkv)

        both(name, flash, (q, k, v), wgt)
        if name == "flash128":
            continue
        for form, held in wide_bwd_forms(seq).items():
            grad_of(name + "." + form, flash, (q, k, v), wgt, held)
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


def rule_forms_check(seq, hk, hv, d, dtype, chunk):
    """The output and every gradient of `rule_forms`' other forms against
    the shipped pair's: largest difference over the largest value."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import delta_rule as dr
    ins, wgt = rule_inputs(seq, hk, hv, d, dtype, seed=5)

    def of(held):
        fn = jax.value_and_grad(lambda *a: jnp.sum(dr.delta_rule_core(
            *a, hk, chunk, 1e-6, dtype, True).astype(jnp.float32) * wgt),
            argnums=(0, 1, 2, 3, 4))
        out, grads = jax.jit(traced_holding(held, fn))(*ins)
        return [t.astype(jnp.float32) for t in (out, *grads)]

    forms = rule_forms()
    want = of(forms.pop("kernel"))
    return {form: {name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                   for name, a, b in zip(
                       ("out", "dqkv", "dz", "dg", "dbeta", "dscale"),
                       of(held), want)}
            for form, held in forms.items()}


def flash_check(seq, heads256, dtype):
    """The shipped one-kernel backward and the lab's controls against the
    two-kernel form at the lab's shape: largest difference of each
    gradient over the gradient's largest value."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk
    h, hkv, hd = heads256
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (1, seq, h * hd)).astype(dtype)
    k = jax.random.normal(ks[1], (1, seq, hkv * hd))
    v = jax.random.normal(ks[2], (1, seq, hkv * hd))
    wgt = jax.random.normal(ks[3], (1, seq, h * hd))

    def grads(held):
        fn = jax.grad(lambda q, k, v, wgt: jnp.sum(pk.flash_attention(
            q, k, v, h, causal=True, num_kv_heads=hkv).astype(jnp.float32)
            * wgt), argnums=(0, 1, 2))
        return [g.astype(jnp.float32)
                for g in jax.jit(traced_holding(held, fn))(q, k, v, wgt)]

    forms = dict(wide_bwd_forms(seq), shipped=None)
    want = grads(forms.pop("two_kernels"))
    return {form: {name: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                   for name, a, b in zip(("dq", "dk", "dv"), grads(held),
                                         want)}
            for form, held in forms.items()}


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
        for name, (ms, ops) in device_ms(jitted, stems=10).items():
            line[name + "_device_ms"] = round(ms, 4)
            line[name + "_device_ops"] = ops
    if opts.only in "check":
        line["check"] = check(short, shape["hk"], shape["hv"], shape["d"],
                              shape["dtype"], shape["chunk"])
    if opts.only in "rule.check":
        line["rule_forms_vs_kernel"] = rule_forms_check(
            short, shape["hk"], shape["hv"], shape["d"], shape["dtype"],
            shape["chunk"])
    if opts.only in "flash256.check":
        line["flash256_vs_two_kernels"] = flash_check(
            2048 if opts.tiny else shape["seq"], shape["heads256"],
            shape["dtype"])
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_lab.json", "w") as f:
        json.dump(line, f)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
