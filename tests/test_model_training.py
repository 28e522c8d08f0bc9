"""End-to-end training tests: the minimum slice of SURVEY §7 stage 1.

Covers: FFModel layer API -> compile -> jitted fit loop; loss decreases;
metrics; evaluate; predict; reference-parity forward/backward/update
protocol; data-parallel strategy over the 8-device CPU mesh.
"""

import numpy as np
import pytest

import jax

from flexflow_tpu import (
    AdamOptimizer,
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.ffconst import ActiMode, DataType


def make_blobs(n=256, d=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.randn(classes, d) * 3
    y = rs.randint(0, classes, n)
    x = centers[y] + rs.randn(n, d)
    return x.astype(np.float32), y.astype(np.int32)


def test_mlp_learns():
    x, y = make_blobs()
    ff = FFModel(FFConfig(batch_size=32))
    t = ff.create_tensor((32, 8))
    t = ff.dense(t, 32, activation=ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    assert len(jax.devices()) == 8  # conftest forced the virtual mesh
    before = ff.evaluate(x, y)
    ff.fit(x, y, epochs=5, verbose=False)
    after = ff.evaluate(x, y)
    assert after["loss"] < before["loss"]
    assert after["accuracy"] > 0.8


def test_mlp_adam_and_mse():
    rs = np.random.RandomState(1)
    x = rs.randn(128, 4).astype(np.float32)
    w = rs.randn(4, 1).astype(np.float32)
    y = x @ w
    ff = FFModel(FFConfig(batch_size=32))
    t = ff.create_tensor((32, 4))
    t = ff.dense(t, 16, activation=ActiMode.AC_MODE_TANH)
    t = ff.dense(t, 1)
    ff.compile(AdamOptimizer(alpha=0.01),
               LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    ff.fit(x, y, epochs=20, verbose=False)
    assert ff.evaluate(x, y)["loss"] < 0.1


def test_adam_bf16_state():
    """Reduced-precision (bf16) m/v storage must converge like f32 state
    (the bench's TPU-native optimizer configuration, bench.py)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(1)
    x = rs.randn(128, 4).astype(np.float32)
    w = rs.randn(4, 1).astype(np.float32)
    y = x @ w

    def run(state_dtype):
        ff = FFModel(FFConfig(batch_size=32, seed=5))
        t = ff.create_tensor((32, 4))
        t = ff.dense(t, 16, activation=ActiMode.AC_MODE_TANH)
        t = ff.dense(t, 1)
        ff.compile(AdamOptimizer(alpha=0.01, state_dtype=state_dtype),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
        ff.fit(x, y, epochs=20, verbose=False)
        return ff.evaluate(x, y)["loss"]

    loss_bf16 = run(jnp.bfloat16)
    loss_f32 = run(None)
    assert loss_bf16 < 0.1
    assert abs(loss_bf16 - loss_f32) < 0.05


def test_forward_backward_update_protocol():
    """Reference iteration protocol (flexflow_cffi.py:2073-2086)."""
    x, y = make_blobs(64, 8, 4)
    ff = FFModel(FFConfig(batch_size=64))
    t = ff.create_tensor((64, 8))
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    loss0 = ff.evaluate(x, y)["loss"]
    for _ in range(5):
        ff.set_batch(x, y)
        ff.begin_trace(111)
        ff.forward()
        ff.zero_gradients()
        ff.backward()
        ff.update()
        ff.end_trace(111)
    assert ff.evaluate(x, y)["loss"] < loss0


def test_predict_shape():
    ff = FFModel()
    t = ff.create_tensor((16, 10))
    t = ff.dense(t, 3)
    ff.compile(loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
    out = ff.predict(np.zeros((16, 10), np.float32))
    assert out.shape == (16, 3)


def test_dp_matches_single_device():
    """DP over 8 virtual devices must match single-device numerics
    (SURVEY §7 stage 2 acceptance)."""
    from flexflow_tpu.machine import make_mesh

    x, y = make_blobs(64, 8, 4)

    def build(mesh):
        ff = FFModel(FFConfig(batch_size=64, seed=7))
        t = ff.create_tensor((64, 8))
        t = ff.dense(t, 16, activation=ActiMode.AC_MODE_RELU)
        t = ff.dense(t, 4)
        t = ff.softmax(t)
        ff.compile(SGDOptimizer(lr=0.1),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.ACCURACY], mesh=mesh)
        return ff

    ff8 = build(make_mesh(8, {"data": 8}))
    ff1 = build(make_mesh(1, {"data": 1}))
    for ff in (ff8, ff1):
        ff.fit(x, y, epochs=3, verbose=False)
    w8 = ff8.get_parameter(ff8.get_layer_names()[0])
    w1 = ff1.get_parameter(ff1.get_layer_names()[0])
    np.testing.assert_allclose(w8, w1, rtol=1e-4, atol=1e-5)


def test_cnn_forward_and_train():
    """Mini AlexNet-style CNN on random CIFAR-shaped data (stage-1 slice)."""
    rs = np.random.RandomState(0)
    x = rs.randn(32, 3, 16, 16).astype(np.float32)
    y = rs.randint(0, 10, 32).astype(np.int32)
    ff = FFModel(FFConfig(batch_size=32))
    t = ff.create_tensor((32, 3, 16, 16))
    t = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0)
    t = ff.conv2d(t, 16, 3, 3, 1, 1, 1, 1, activation=ActiMode.AC_MODE_RELU)
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0)
    t = ff.flat(t)
    t = ff.dense(t, 32, activation=ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 10)
    t = ff.softmax(t)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    l0 = ff.evaluate(x, y)["loss"]
    ff.fit(x, y, epochs=10, verbose=False)
    assert ff.evaluate(x, y)["loss"] < l0


def test_parameter_parallel_matches_dp():
    """--enable-parameter-parallel: model-axis sharded Linear must keep
    numerics (GSPMD inserts the Combine/Reduction collectives)."""
    from flexflow_tpu.machine import make_mesh

    x, y = make_blobs(64, 8, 4)

    def build(enable_pp):
        cfg = FFConfig(batch_size=64, seed=3)
        cfg.enable_parameter_parallel = enable_pp
        ff = FFModel(cfg)
        t = ff.create_tensor((64, 8))
        t = ff.dense(t, 16, activation=ActiMode.AC_MODE_RELU)
        t = ff.dense(t, 4)
        t = ff.softmax(t)
        ff.compile(SGDOptimizer(lr=0.1),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.ACCURACY])
        return ff

    ff_tp = build(True)
    assert "model" in ff_tp.mesh.axis_names
    ff_dp = build(False)
    for ff in (ff_tp, ff_dp):
        ff.fit(x, y, epochs=3, verbose=False)
    w_tp = ff_tp.get_parameter(ff_tp.get_layer_names()[0])
    w_dp = ff_dp.get_parameter(ff_dp.get_layer_names()[0])
    np.testing.assert_allclose(w_tp, w_dp, rtol=1e-4, atol=1e-5)


def test_moe_trains_with_lb_loss():
    x, y = make_blobs(64, 8, 4)
    ff = FFModel(FFConfig(batch_size=64))
    t = ff.create_tensor((64, 8))
    t = ff.moe(t, num_exp=4, num_select=2, expert_hidden_size=16,
               alpha=2.0, lambda_bal=0.04)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.ACCURACY])
    l0 = ff.evaluate(x, y)["loss"]
    ff.fit(x, y, epochs=10, verbose=False)
    assert ff.evaluate(x, y)["loss"] < l0


def test_fit_smaller_than_batch_raises():
    ff = FFModel()
    t = ff.create_tensor((32, 4))
    t = ff.dense(t, 2)
    ff.compile(loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
    with pytest.raises(ValueError, match="smaller than batch"):
        ff.fit(np.zeros((16, 4), np.float32), np.zeros((16, 2), np.float32))


def test_duplicate_layer_names_do_not_collide():
    ff = FFModel()
    t = ff.create_tensor((8, 4))
    t = ff.dense(t, 8, name="fc")
    t = ff.dense(t, 2, name="fc")
    ff.compile(loss_type=LossType.MEAN_SQUARED_ERROR_AVG_REDUCE)
    names = ff.get_layer_names()
    assert len(set(names)) == 2


@pytest.mark.parametrize("family", ["adam_mlp", "batchnorm_cnn"])
def test_train_step_compiles_once(family):
    """State that starts off the mesh (Adam's step count, BatchNorm's
    running statistics) comes back from the first step on it; jit took
    that for a new input type and compiled the whole step a second time."""
    rs = np.random.RandomState(0)
    ff = FFModel(FFConfig(batch_size=8))
    if family == "adam_mlp":
        x = rs.randn(8, 4).astype(np.float32)
        y = rs.randn(8, 1).astype(np.float32)
        t = ff.dense(ff.create_tensor((8, 4)), 1)
        ff.compile(AdamOptimizer(alpha=0.01),
                   LossType.MEAN_SQUARED_ERROR_AVG_REDUCE, [])
    else:
        x = rs.randn(8, 3, 8, 8).astype(np.float32)
        y = rs.randint(0, 4, 8).astype(np.int32)
        t = ff.conv2d(ff.create_tensor((8, 3, 8, 8)), 4, 3, 3, 1, 1, 1, 1)
        t = ff.dense(ff.flat(ff.batch_norm(t)), 4)
        ff.compile(SGDOptimizer(lr=0.05),
                   LossType.SPARSE_CATEGORICAL_CROSSENTROPY, [])
    for _ in range(3):
        ff.fit(x, y, epochs=1, verbose=False)
    assert ff.executor._jit_train._cache_size() == 1
