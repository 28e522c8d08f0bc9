"""Drives a family's plain reference over a batch in chunks of samples.

The loss of every family here is a mean over samples, so chunk sums and
accumulated gradients are exact. Runs on one device whatever the cell's
chips. The optimizer is Adam as upstream states it
(include/flexflow/optimizer.h, `AdamOptimizer::next`):
    alpha_t = alpha * sqrt(1 - beta2^t) / (1 - beta1^t)
    m = beta1 m + (1 - beta1) g;  v = beta2 v + (1 - beta2) g^2
    w = w - alpha_t * m / (sqrt(v) + epsilon)
in float32 (the configuration's bfloat16 moments are a departure the
check's band on the later losses has to hold).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _kw_items(kw):
    return tuple(sorted(kw.items()))


@functools.lru_cache(maxsize=None)
def compiled(ref, kw_items):
    """(forward, value-and-gradient of a chunk's summed loss), jitted once
    for a reference module and its keyword arguments."""
    kw = dict(kw_items)

    def chunk_sum(w, x, y):
        pred = ref.forward(w, x, **kw)
        return jnp.sum(ref.sample_losses(pred, y)), pred

    return (jax.jit(lambda w, x: ref.forward(w, x, **kw)),
            jax.jit(jax.value_and_grad(chunk_sum, has_aux=True)))


def predict(ref, w, x, chunk, **kw):
    fwd, _ = compiled(ref, _kw_items(kw))
    with jax.default_matmul_precision("highest"):
        outs = [np.asarray(fwd(w, jnp.asarray(x[i:i + chunk])))
                for i in range(0, x.shape[0], chunk)]
    return np.concatenate(outs, axis=0)


def loss_of(ref, preds, y):
    """The loss of predictions already made (no gradients: for sequences
    too long to hold a float32 backward pass)."""
    return float(jnp.sum(ref.sample_losses(jnp.asarray(preds),
                                           jnp.asarray(y)))
                 ) / ref.loss_denominator(y)


def loss_and_grads(ref, w, x, y, chunk, **kw):
    """Loss over the whole batch and its gradient, chunk by chunk."""
    _, vg = compiled(ref, _kw_items(kw))
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, x.shape[0], chunk):
            (s, _), g = vg(w, jnp.asarray(x[i:i + chunk]),
                           jnp.asarray(y[i:i + chunk]))
            total += float(s)
            grads = g if grads is None else _add(grads, g)
    n = ref.loss_denominator(y)
    return total / n, _scale(grads, 1.0 / n)


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


@jax.jit
def _scale(a, s):
    return jax.tree.map(lambda t: t * s, a)


@jax.jit
def _adam(w, g, m, v, t, alpha, beta1, beta2, eps, bias_correction):
    tf = t.astype(jnp.float32)
    alpha_t = jnp.where(bias_correction, alpha * jnp.sqrt(1.0 - beta2 ** tf)
                        / (1.0 - beta1 ** tf), alpha)
    m = jax.tree.map(lambda m_, g_: beta1 * m_ + (1 - beta1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: beta2 * v_ + (1 - beta2) * g_ * g_, v, g)
    w = jax.tree.map(
        lambda w_, m_, v_: w_ - alpha_t * m_ / (jnp.sqrt(v_) + eps), w, m, v)
    return w, m, v


def train_losses(ref, w, x, y, chunk, steps, adam, **kw):
    """Losses of `steps` successive steps on the one batch (x, y): each is
    taken before its update, as `fit` reports it. `bias_correction: false`
    in `adam` is the wrong update rule that the check's control uses."""
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses = []
    for t in range(1, steps + 1):
        loss, g = loss_and_grads(ref, w, x, y, chunk, **kw)
        losses.append(loss)
        if t < steps:
            w, m, v = _adam(w, g, m, v, jnp.int32(t),
                            jnp.float32(adam["alpha"]),
                            jnp.float32(adam["beta1"]),
                            jnp.float32(adam["beta2"]),
                            jnp.float32(adam["epsilon"]),
                            jnp.bool_(adam.get("bias_correction", True)))
    return losses
