"""Loss functions.

Analog of src/loss_functions/ (loss_functions.cc:41,71): categorical CE,
sparse categorical CE, MSE (avg/sum reduce), identity. The reference
launches LOSS_BWD_TASK_ID to seed gradients and scales by 1/num_replicas
when the final op is replicated; here the loss is part of the jitted
scalar objective and jax.grad seeds it — replica scaling is what
jnp.mean over the global (sharded) batch already does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import LossType


def categorical_crossentropy(logits, labels):
    """labels one-hot [B, C]; logits pre-softmax (the reference pairs this
    with a Softmax final op — we accept probabilities too)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.sum(labels * logp, axis=-1))


def sparse_categorical_crossentropy(logits, labels):
    """[B, C] logits with [B]/[B,1] labels (classification), or [B, S, V]
    logits with [B, S]/[B,S,1] labels (token-level LM objective)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    if logits.ndim == 3:
        lab = labels.reshape(labels.shape[0], labels.shape[1], -1)[..., :1]
        tok = jnp.take_along_axis(logp, lab.astype(jnp.int32), axis=-1)
        return -jnp.mean(tok)
    labels = labels.reshape(labels.shape[0], -1)[..., 0] if labels.ndim > 1 else labels
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=-1))


def weighted_sparse_categorical_crossentropy(logits, labels):
    """[B, S, V] logits with labels [B, S, 2] float32 that carry, a
    position, the target's id and its weight c: sum(c * ce) / (B * S), the
    mean over ALL positions of the weighted cross-entropy (a masked
    diffusion objective: c = 1/t where the token was masked, else 0). A
    position of weight 0 gets exactly zero gradient."""
    return -jnp.mean(labels[..., 1].astype(jnp.float32)
                     * _target_log_probs(logits, labels))


def _target_log_probs(logits, labels):
    """log softmax(logits)[target] a position, [B, S] float32, for labels
    [B, S, 2] whose [..., 0] is the target's id."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ids = labels[..., 0].astype(jnp.int32)
    return jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


def target_positions(labels):
    """Positions of weighted labels [B, S, 2] whose weight is not zero
    (the op counter `loss/target_positions`)."""
    return jnp.sum(labels[..., 1] > 0).astype(jnp.float32)


def part_nll_sums(logits, labels, parts):
    """name -> the sum of the UNWEIGHTED cross-entropies over the
    positions that carry a target, for each of the equal ``parts`` along
    the sequence that logits [B, S, V] and labels [B, S, 2] consist of
    (a main model's half and a multi-token-prediction module's: the op
    counters `loss/<part>_nll`). Beside the loss in one program the
    log-probabilities are one computation (the compiler merges the two)."""
    nll = jnp.where(labels[..., 1] > 0,
                    -_target_log_probs(logits, labels), 0.0)
    b, s = nll.shape
    sums = jnp.sum(nll.reshape(b, len(parts), s // len(parts)), axis=(0, 2))
    return {name: sums[i] for i, name in enumerate(parts)}


def mse_avg(preds, labels):
    return jnp.mean((preds.astype(jnp.float32) - labels.astype(jnp.float32)) ** 2)


def mse_sum(preds, labels):
    per_sample = jnp.sum(
        (preds.astype(jnp.float32) - labels.astype(jnp.float32)) ** 2,
        axis=tuple(range(1, preds.ndim)),
    )
    return jnp.mean(per_sample)


def identity(preds, labels):
    return jnp.mean(preds.astype(jnp.float32))


LOSS_FNS = {
    LossType.CATEGORICAL_CROSSENTROPY: categorical_crossentropy,
    LossType.SPARSE_CATEGORICAL_CROSSENTROPY: sparse_categorical_crossentropy,
    LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY:
        weighted_sparse_categorical_crossentropy,
    LossType.MEAN_SQUARED_ERROR_AVG_REDUCE: mse_avg,
    LossType.MEAN_SQUARED_ERROR_SUM_REDUCE: mse_sum,
    LossType.IDENTITY: identity,
}


def get_loss_fn(loss_type: LossType):
    return LOSS_FNS[loss_type]
