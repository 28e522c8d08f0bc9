"""Shared driver glue for the example programs.

Mirrors the reference examples' top_level_task pattern (e.g.
examples/cpp/Transformer/transformer.cc:105-211): parse FFConfig flags,
build the model, generate synthetic data, run the epochs/iterations loop,
print `ELAPSED TIME = .. THROUGHPUT = .. samples/s` (the metric the
osdi22ae scripts grep)."""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu import (AdamOptimizer, FFConfig, LossType, MetricsType,
                          SGDOptimizer)


def parse_config(argv=None) -> FFConfig:
    from flexflow_tpu.utils.compile_cache import configure_compile_cache
    configure_compile_cache()
    cfg = FFConfig()
    rest = cfg.parse_args(argv if argv is not None else sys.argv[1:])
    cfg._rest = rest
    return cfg


def train_synthetic(ff, cfg: FFConfig, input_specs, label_shape,
                    loss=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                    metrics=(MetricsType.ACCURACY,), classes=None,
                    optimizer=None, iterations=None):
    """input_specs: list of (shape_without_batch, dtype, high) tuples."""
    ff.compile(optimizer or SGDOptimizer(lr=cfg.learning_rate), loss,
               list(metrics))
    axes = dict(zip(ff.mesh.axis_names, ff.mesh.devices.shape))
    print(f"mesh: {axes}" + (
        f"  search: predicted {ff.search_info['predicted_time'] * 1e3:.3f} ms"
        if ff.search_info else "  (data-parallel default)"))
    bs = ff.input_tensors[0].shape[0]
    iters = iterations or max(cfg.iterations, 4)
    rs = np.random.RandomState(cfg.seed)
    xs = []
    for shape, dtype, high in input_specs:
        if np.issubdtype(np.dtype(dtype), np.integer):
            xs.append(rs.randint(0, high, (bs,) + tuple(shape)).astype(dtype))
        else:
            xs.append(rs.randn(bs, *shape).astype(dtype))
    if classes:
        y = rs.randint(0, classes, label_shape and (bs,) + tuple(label_shape)
                       or (bs, 1)).astype(np.int32)
    else:
        y = rs.randn(bs, *label_shape).astype(np.float32)

    ff.set_batch(xs if len(xs) > 1 else xs[0], y)
    ff.forward(); ff.backward(); ff.update()  # warmup / compile
    start = time.time()
    for _ in range(iters):
        ff.forward()
        ff.zero_gradients()
        ff.backward()
        ff.update()
    float(ff._last_loss)  # sync
    elapsed = time.time() - start
    thr = bs * iters / elapsed
    print(f"ELAPSED TIME = {elapsed:.4f}s, THROUGHPUT = {thr:.2f} samples/s")
    return thr
