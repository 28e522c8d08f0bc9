#!/usr/bin/env python
"""Measure per-leaf vs flat-buffer optimizer update on the bench model.

Diagnoses the r3 finding that the Adam update phase runs at ~340 GB/s
effective (per-leaf elementwise kernels) and quantifies what a flat
contiguous-buffer update + the unflatten/flatten boundary costs would be,
to decide the r4 fused-optimizer design. Run on the real TPU.
"""

import sys, os, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ffconst import LossType, MetricsType
from flexflow_tpu.models.transformer import TransformerConfig, create_transformer
from flexflow_tpu.optimizers import AdamOptimizer


def timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    cfg = TransformerConfig()
    ff = create_transformer(cfg, FFConfig(batch_size=cfg.batch_size))
    ff.compile(AdamOptimizer(alpha=1e-4), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [MetricsType.MEAN_SQUARED_ERROR])
    params, opt_state = ff.params, ff.opt_state
    opt = ff.optimizer

    leaves = jax.tree.leaves(params)
    nbytes = sum(l.size * l.dtype.itemsize for l in leaves)
    print(f"leaves={len(leaves)} total={nbytes/1e6:.1f} MB")

    # fake grads: same tree
    grads = jax.tree.map(lambda p: p * 1e-3, params)
    grads = jax.block_until_ready(grads)

    # 1. per-leaf Adam (current path), no donation (params reused)
    upd = jax.jit(lambda g, s, p: opt.update(g, s, p))
    t = timeit(upd, grads, opt_state, params)
    moved = 7 * nbytes  # p R+W, g R, m R+W, v R+W
    print(f"per-leaf adam: {t*1e3:.3f} ms  eff_bw={moved/t/1e9:.0f} GB/s")

    # 2. flat Adam: one buffer
    fp = jnp.concatenate([l.ravel() for l in leaves])
    fg = fp * 1e-3
    fm = jnp.zeros_like(fp); fv = jnp.zeros_like(fp)

    def flat_adam(g, m, v, p, t_):
        b1, b2, eps, lr = 0.9, 0.999, 1e-8, 1e-4
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        bc = jnp.sqrt(1 - b2 ** t_) / (1 - b1 ** t_)
        return p - lr * bc * m / (jnp.sqrt(v) + eps), m, v

    fupd = jax.jit(flat_adam)
    t = timeit(fupd, fg, fm, fv, fp, jnp.float32(3.0))
    print(f"flat adam:     {t*1e3:.3f} ms  eff_bw={moved/t/1e9:.0f} GB/s")

    # 2b. flat Adam with donation (in-place update like the real step)
    fupd_d = jax.jit(flat_adam, donate_argnums=(1, 2, 3))
    fm2 = jnp.zeros_like(fp); fv2 = jnp.zeros_like(fp); fp2 = fp + 0
    for _ in range(3):
        fp2, fm2, fv2 = fupd_d(fg, fm2, fv2, fp2, jnp.float32(3.0))
    jax.block_until_ready(fp2)
    t0 = time.perf_counter()
    for _ in range(20):
        fp2, fm2, fv2 = fupd_d(fg, fm2, fv2, fp2, jnp.float32(3.0))
    jax.block_until_ready(fp2)
    t = (time.perf_counter() - t0) / 20
    print(f"flat adam don: {t*1e3:.3f} ms  eff_bw={moved/t/1e9:.0f} GB/s")

    # 3. unflatten: flat -> leaves (slices + reshape)
    shapes = [l.shape for l in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    offs = np.cumsum([0] + sizes)

    def unflat(f):
        return [jax.lax.slice(f, (int(offs[i]),), (int(offs[i + 1]),))
                .reshape(shapes[i]) for i in range(len(shapes))]

    uf = jax.jit(unflat)
    t = timeit(uf, fp)
    print(f"unflatten:     {t*1e3:.3f} ms  eff_bw={2*nbytes/t/1e9:.0f} GB/s")

    # 4. flatten: leaves -> flat (concat)
    fl = jax.jit(lambda ls: jnp.concatenate([l.ravel() for l in ls]))
    t = timeit(fl, leaves)
    print(f"flatten:       {t*1e3:.3f} ms  eff_bw={2*nbytes/t/1e9:.0f} GB/s")

    # 5. matmul-from-slice vs matmul-from-leaf: does XLA materialize the
    # slice feeding a dot?
    x = jnp.ones((8 * 512, 1024), jnp.bfloat16)
    w_leaf = jnp.ones((1024, 4096), jnp.float32)

    def mm_leaf(x, w):
        return x @ w.astype(jnp.bfloat16)

    def mm_slice(x, f):
        w = jax.lax.slice(f, (0,), (1024 * 4096,)).reshape(1024, 4096)
        return x @ w.astype(jnp.bfloat16)

    t1 = timeit(jax.jit(mm_leaf), x, w_leaf)
    t2 = timeit(jax.jit(mm_slice), x, fp)
    print(f"mm from leaf:  {t1*1e6:.0f} us   mm from slice: {t2*1e6:.0f} us")

    # 6. full train step today (for the step-time breakdown)
    rs = np.random.RandomState(0)
    x_ = rs.randn(cfg.batch_size, cfg.seq_length, cfg.hidden_size).astype(np.float32)
    y_ = rs.randn(cfg.batch_size, cfg.seq_length, 1).astype(np.float32)
    inputs = ff._stage_inputs([x_]); labels = ff._shard_batch(y_)
    step = ff.executor.make_train_step()
    rng = jax.random.PRNGKey(0)
    p, s, st = ff.params, ff.opt_state, ff.state
    for _ in range(3):
        p, s, st, loss, _ = step(p, s, st, inputs, labels, rng)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(30):
        p, s, st, loss, _ = step(p, s, st, inputs, labels, rng)
    float(loss)
    t = (time.perf_counter() - t0) / 30
    print(f"train step:    {t*1e3:.3f} ms  ({cfg.batch_size/t:.1f} samples/s)")


if __name__ == "__main__":
    main()
