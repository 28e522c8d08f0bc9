#!/usr/bin/env python3
"""The kernels of learned sparse attention alone, on the chip (PR 54).

At the `keye_vl2_30b_a3b` cell's shapes (one sequence of 16,384
positions, an indexer of 16 heads of 64 with one key, 2,048 keys a
query, 8 query heads of 128 on one key/value head), each program alone,
REPS calls after a warm-up, wall milliseconds a call (the programs are
single kernels of milliseconds; a dispatch is 0.1 ms):

- `select.*`: the selection, the k-th largest of a row by bisection
  over the bits, in VMEM: `kernel.f32` (float32 index products at the
  compiler's `highest`, six MXU passes), `kernel.bf16_3x` (what ships:
  float32 operands split into bfloat16 high and low parts, three
  products in two passes since PR 55: a head's low part rides in the
  lanes a head of 64 leaves zeroed), `kernel.bf16` (bfloat16 operands,
  one pass),
  `top_k` (XLA: the scores of 2,048 query rows formed whole, then
  `lax.top_k`; eight such blocks make a layer, so the line is times 8),
  `approx_max_k` (the same with `lax.approx_max_k`, which keeps ANOTHER
  set: a control, not a candidate);
- `flash.*`: the chunk-loop flash kernels with the mask operand, forward
  and forward + backward, against the same kernels without it (plain
  causal), and the mask's transpose and summaries alone;
- `index_kl`: the indexer's loss and its gradients;
- `flips`: the pairs whose membership of a query's kept set differs
  between the float32 reference's selection (float32 layer input,
  products at `highest`) and the program's (the layer input rounded to
  bfloat16, as the program's norm leaves it), by the products'
  precision, over one layer's seeded weights at the cell's widths.

    python scripts/sparse_lab.py [--tiny] [--only select,flash,index_kl]

`--tiny` runs every program at 1,152 positions in interpret mode on the
CPU (a rehearsal: no time is a device's). PERF.md section 5 has the
table this printed on the v5e.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
REPS = 5


def wall_ms(fn, args):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / REPS


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", default="select,flash,index_kl,flips")
    args = ap.parse_args()
    if args.tiny:
        os.environ["FLEXFLOW_TPU_PALLAS"] = "interpret"
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk

    s, topk, hi, h, hk = (1152, 300, 4, 2, 1) if args.tiny else (
        16384, 2048, 16, 8, 1)
    block = 128 if args.tiny else 2048
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    f32, bf16 = jnp.float32, jnp.bfloat16
    qi = jax.random.normal(ks[0], (1, s, hi * 64), f32)
    ki = jax.random.normal(ks[1], (1, s, 64), f32)
    w = jax.random.normal(ks[2], (1, s, hi), f32) * 0.1
    q = jax.random.normal(ks[3], (1, s, h * 128), f32).astype(bf16)
    k = jax.random.normal(ks[4], (1, s, hk * 128), f32)
    v = jax.random.normal(ks[5], (1, s, hk * 128), f32)
    highest = jax.lax.Precision.HIGHEST
    only = args.only.split(",")

    def line(program, ms, **more):
        print(json.dumps(dict(program=program, seq=s, ms=ms, **more)),
              flush=True)

    mask, lse_i, _ = jax.jit(lambda q, k, w: pk.index_select(
        q, k, w, topk, pk.BF16_3X))(qi, ki, w)
    if "select" in only:
        for name, precision in (("f32", highest), ("bf16_3x", pk.BF16_3X)):
            line(f"select.kernel.{name}", wall_ms(jax.jit(
                lambda q, k, w: pk.index_select(q, k, w, topk, precision)),
                (qi, ki, w)))
        line("select.kernel.bf16", wall_ms(jax.jit(
            lambda q, k, w: pk.index_select(q, k, w, topk, None)),
            (qi.astype(bf16), ki.astype(bf16), w)))

        def xla(approximate):
            def fn(q, k, w):    # the last `block` queries against all keys
                dots = jnp.einsum("bthd,bsd->bhts",
                                  q.reshape(1, block, hi, 64), k,
                                  precision=highest)
                scores = jnp.einsum("bhts,bth->bts", jnp.maximum(dots, 0.0),
                                    w, precision=highest)
                t = s - block + jnp.arange(block)[:, None]
                scores = jnp.where(jnp.arange(s)[None, :] <= t, scores,
                                   -jnp.inf)
                return (jax.lax.approx_max_k if approximate
                        else jax.lax.top_k)(scores, topk)[1]
            return jax.jit(fn)

        for name, approximate in (("top_k", False), ("approx_max_k", True)):
            ms = wall_ms(xla(approximate), (qi[:, -block:], ki, w[:, -block:]))
            line(f"select.xla.{name}", ms * (s // block), rows=block,
                 ms_a_block=ms)
    if "flash" in only:
        def grads(core):
            return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                core(q, k, v).astype(f32)), argnums=(0, 1, 2)))

        masked = lambda q, k, v: pk.flash_attention_masked(  # noqa: E731
            q, k, v, mask, h, hk)[0]
        causal = lambda q, k, v: pk.flash_attention(  # noqa: E731
            q, k, v, h, True, num_kv_heads=hk)
        for name, core in (("masked", masked), ("causal", causal)):
            fwd = wall_ms(jax.jit(core), (q, k, v))
            both = wall_ms(grads(core), (q, k, v))
            line(f"flash.{name}", fwd + both, forward=fwd,
                 forward_and_backward=both)
        (rows, keys), _ = pk.masked_tiles(s)
        line("flash.mask_transpose_and_summaries", wall_ms(jax.jit(
            lambda m: (jnp.swapaxes(m, 1, 2),
                       pk.mask_tiles_any(m, rows, keys),
                       pk.mask_tiles_any(m, keys, keys))), (mask,)))
    if "index_kl" in only:
        _, lse = jax.jit(lambda q, k, v: pk.flash_attention_masked(
            q, k, v, mask, h, hk))(q, k, v)
        lse = lse[:, :, 0, :].transpose(0, 2, 1)
        for name, dt in (("bf16", bf16), ("f32", f32)):
            line(f"index_kl.{name}", wall_ms(jax.jit(
                lambda qi, ki, w, q, k: pk.index_kl(
                    qi, ki, w, lse_i, mask, q, k, lse, h, 1.0 / s)),
                (qi.astype(dt), ki.astype(dt), w, q, k)))


    if "flips" in only:
        e = 64 if args.tiny else 2048
        x = jax.random.normal(ks[6], (1, s, e), f32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        wk = jax.random.split(ks[7], 3)
        w_iq, w_ik, w_iw = (0.02 * jax.random.normal(key, (e, n), f32)
                            for key, n in zip(wk, (hi * 64, 64, hi)))

        def kept(x, precision, dtype=f32):
            def fn(x):
                q, k, w = (jnp.dot(x, m, precision=highest)
                           for m in (w_iq, w_ik, w_iw))
                mean = jnp.mean(k, -1, keepdims=True)
                k = (k - mean) * jax.lax.rsqrt(
                    jnp.mean(jnp.square(k - mean), -1, keepdims=True) + 1e-6)
                return pk.index_select(q.astype(dtype), k.astype(dtype),
                                       w * (hi * 64) ** -0.5, topk,
                                       precision)[0]
            return jax.jit(fn)(x)

        want = kept(x, highest)
        rounded = x.astype(bf16).astype(f32)
        total = int(jnp.sum(want, dtype=jnp.int32))
        for name, got in (
                ("f32_input.bf16_3x", kept(x, pk.BF16_3X)),
                ("bf16_input.f32", kept(rounded, highest)),
                ("bf16_input.bf16_3x", kept(rounded, pk.BF16_3X)),
                ("bf16_input.bf16", kept(rounded, None, bf16))):
            differ = int(jnp.sum(got != want, dtype=jnp.int32))
            line(f"flips.{name}", None, pairs_that_differ=differ,
                 kept_pairs=total, share=differ / total)


if __name__ == "__main__":
    main()
