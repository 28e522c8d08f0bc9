"""Plain reference of the `inception_v3_ae` family.

Written from upstream flexflow/FlexFlow examples/cpp/InceptionV3/
inception.cc: a stem of five convolutions and two max pools, modules
A x3 (pool branch 32, 64, 64 channels), B, C x4 (128, 160, 160, 192),
D, E x2, a global average pool, `flat`, `dense(1000)`, `softmax`; loss
`SPARSE_CATEGORICAL_CROSSENTROPY`. Upstream has no batch norm. As
upstream, ReLU follows the stem's and module A's convolutions only.

Departure: average pooling divides by the window's area, padding
included (what the program computes; stated in the configuration file).

`network(ops, x)` walks the architecture once; `ops` is either the
float32 arithmetic below or `ShapeOps`, which only counts. Convolutions
take their weights in creation order from `w["convs"]` (OIHW kernel,
bias); the classifier is `w["fc"]`. Float32 under precision `highest`;
no code shared with `flexflow_tpu`. `operand` as in `bert_ae.py`.
"""

import jax
import jax.numpy as jnp

from benchmarks.references.bert_ae import HIGHEST, round_operand


# --------------------------------------------------------------------------
# the architecture, once


def module_a(ops, x, pool_features):
    t1 = ops.conv(x, 64, 1, 1, relu=True)
    t2 = ops.conv(x, 48, 1, 1, relu=True)
    t2 = ops.conv(t2, 64, 5, 5, pad=(2, 2), relu=True)
    t3 = ops.conv(x, 64, 1, 1, relu=True)
    t3 = ops.conv(t3, 96, 3, 3, pad=(1, 1), relu=True)
    t3 = ops.conv(t3, 96, 3, 3, pad=(1, 1), relu=True)
    t4 = ops.avg_pool(x, 3, 1, 1)
    t4 = ops.conv(t4, pool_features, 1, 1, relu=True)
    return ops.concat([t1, t2, t3, t4])


def module_b(ops, x):
    t1 = ops.conv(x, 384, 3, 3, stride=2)
    t2 = ops.conv(x, 64, 1, 1)
    t2 = ops.conv(t2, 96, 3, 3, pad=(1, 1))
    t2 = ops.conv(t2, 96, 3, 3, stride=2)
    t3 = ops.max_pool(x, 3, 2, 0)
    return ops.concat([t1, t2, t3])


def module_c(ops, x, channels):
    t1 = ops.conv(x, 192, 1, 1)
    t2 = ops.conv(x, channels, 1, 1)
    t2 = ops.conv(t2, channels, 1, 7, pad=(0, 3))
    t2 = ops.conv(t2, 192, 7, 1, pad=(3, 0))
    t3 = ops.conv(x, channels, 1, 1)
    t3 = ops.conv(t3, channels, 7, 1, pad=(3, 0))
    t3 = ops.conv(t3, channels, 1, 7, pad=(0, 3))
    t3 = ops.conv(t3, channels, 7, 1, pad=(3, 0))
    t3 = ops.conv(t3, 192, 1, 7, pad=(0, 3))
    t4 = ops.avg_pool(x, 3, 1, 1)
    t4 = ops.conv(t4, 192, 1, 1)
    return ops.concat([t1, t2, t3, t4])


def module_d(ops, x):
    t1 = ops.conv(x, 192, 1, 1)
    t1 = ops.conv(t1, 320, 3, 3, stride=2)
    t2 = ops.conv(x, 192, 1, 1)
    t2 = ops.conv(t2, 192, 1, 7, pad=(0, 3))
    t2 = ops.conv(t2, 192, 7, 1, pad=(3, 0))
    t2 = ops.conv(t2, 192, 3, 3, stride=2)
    t3 = ops.max_pool(x, 3, 2, 0)
    return ops.concat([t1, t2, t3])


def module_e(ops, x):
    t1 = ops.conv(x, 320, 1, 1)
    t2 = ops.conv(x, 384, 1, 1)
    t2a = ops.conv(t2, 384, 1, 3, pad=(0, 1))
    t2b = ops.conv(t2, 384, 3, 1, pad=(1, 0))
    t3 = ops.conv(x, 448, 1, 1)
    t3 = ops.conv(t3, 384, 3, 3, pad=(1, 1))
    t3a = ops.conv(t3, 384, 1, 3, pad=(0, 1))
    t3b = ops.conv(t3, 384, 3, 1, pad=(1, 0))
    t4 = ops.avg_pool(x, 3, 1, 1)
    t4 = ops.conv(t4, 192, 1, 1)
    return ops.concat([t1, t2a, t2b, t3a, t3b, t4])


def network(ops, x, num_classes):
    x = ops.conv(x, 32, 3, 3, stride=2, relu=True)
    x = ops.conv(x, 32, 3, 3, relu=True)
    x = ops.conv(x, 64, 3, 3, pad=(1, 1), relu=True)
    x = ops.max_pool(x, 3, 2, 0)
    x = ops.conv(x, 80, 1, 1, relu=True)
    x = ops.conv(x, 192, 3, 3, relu=True)
    x = ops.max_pool(x, 3, 2, 0)
    for pool_features in (32, 64, 64):
        x = module_a(ops, x, pool_features)
    x = module_b(ops, x)
    for channels in (128, 160, 160, 192):
        x = module_c(ops, x, channels)
    x = module_d(ops, x)
    x = module_e(ops, x)
    x = module_e(ops, x)
    x = ops.global_avg_pool(x)
    return ops.dense(x, num_classes)


# --------------------------------------------------------------------------
# counting shapes: what the weights and the FLOP function are made from


class ShapeOps:
    """Values are (channels, height, width). Records every convolution
    as (cout, cin, kh, kw, out_h, out_w, relu) and the classifier as
    (inputs, outputs)."""

    def __init__(self):
        self.convs = []
        self.fc = None

    def conv(self, x, cout, kh, kw, stride=1, pad=(0, 0), relu=False):
        cin, h, w = x
        oh = (h + 2 * pad[0] - kh) // stride + 1
        ow = (w + 2 * pad[1] - kw) // stride + 1
        self.convs.append((cout, cin, kh, kw, oh, ow, relu))
        return (cout, oh, ow)

    def _pool(self, x, k, stride, pad):
        c, h, w = x
        return (c, (h + 2 * pad - k) // stride + 1,
                (w + 2 * pad - k) // stride + 1)

    max_pool = avg_pool = _pool

    def concat(self, xs):
        return (sum(x[0] for x in xs),) + xs[0][1:]

    def global_avg_pool(self, x):
        return (x[0],)

    def dense(self, x, out):
        self.fc = (x[0], out)
        return (out,)


def shapes(image_size, num_classes):
    ops = ShapeOps()
    network(ops, (3, image_size, image_size), num_classes)
    return ops


# --------------------------------------------------------------------------
# float32 arithmetic


class ArrayOps:
    def __init__(self, w, operand):
        self.convs = iter(w["convs"])
        self.fc = w["fc"]
        self.operand = operand

    def conv(self, x, cout, kh, kw, stride=1, pad=(0, 0), relu=False):
        p = next(self.convs)
        y = jax.lax.conv_general_dilated(
            round_operand(x, self.operand),
            round_operand(p["kernel"], self.operand),
            window_strides=(stride, stride),
            padding=[(pad[0], pad[0]), (pad[1], pad[1])],
            dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
        y = y + p["bias"][None, :, None, None]
        return jax.nn.relu(y) if relu else y

    def _window(self, x, init, op, k, stride, pad):
        return jax.lax.reduce_window(
            x, init, op, (1, 1, k, k), (1, 1, stride, stride),
            ((0, 0), (0, 0), (pad, pad), (pad, pad)))

    def max_pool(self, x, k, stride, pad):
        return self._window(x, -jnp.inf, jax.lax.max, k, stride, pad)

    def avg_pool(self, x, k, stride, pad):
        return self._window(x, 0.0, jax.lax.add, k, stride, pad) / (k * k)

    def concat(self, xs):
        return jnp.concatenate(xs, axis=1)

    def global_avg_pool(self, x):
        return jnp.mean(x, axis=(2, 3))

    def dense(self, x, out):
        return jnp.einsum("bi,io->bo", round_operand(x, self.operand),
                          round_operand(self.fc["kernel"], self.operand),
                          precision=HIGHEST) + self.fc["bias"]


def forward(w, x, *, num_classes, operand="f32"):
    """x [b, 3, size, size] float32 -> class probabilities [b, classes]."""
    logits = network(ArrayOps(w, operand), x, num_classes)
    return jax.nn.softmax(logits, axis=-1)


def sample_losses(probs, y):
    """-log p[label] per sample (SPARSE_CATEGORICAL_CROSSENTROPY)."""
    lab = y.reshape(y.shape[0], -1)[:, :1].astype(jnp.int32)
    return -jnp.log(jnp.take_along_axis(probs, lab, axis=-1))[:, 0]


def loss_denominator(y):
    return y.shape[0]
