"""Loss functions.

Analog of src/loss_functions/ (loss_functions.cc:41,71): categorical CE,
sparse categorical CE, MSE (avg/sum reduce), identity. The reference
launches LOSS_BWD_TASK_ID to seed gradients and scales by 1/num_replicas
when the final op is replicated; here the loss is part of the jitted
scalar objective and jax.grad seeds it — replica scaling is what
jnp.mean over the global (sharded) batch already does.

The losses over class ids (sparse, weighted sparse, the part sums) reach
the logits through ONE function, `target_log_probs`, which has its own
backward (PR 40). Head + loss alone on the TPU v5e, `value_and_grad`
over the stream and the head's weights, device ms a call
(`scripts/loss_lab.py`, my chip runs, PR 40; the four decoder cells'
shapes as hidden x vocabulary rows x rows of a step):

                                   autodiff of    own     own backward  target by
                                   log_softmax  backward  in row blocks  a select
    2688 x 16384 x  8192              18.34      12.34       14.08        12.24
    2560 x 18992 x 16384              40.56      26.51       33.01        26.30
    2048 x 18992 x  8192 weighted     17.76      10.63       13.84        10.52
    2048 x 16160 x  8192 w., 2 parts  15.29       9.10       11.84         9.00

(the head's forward product alone 3.74 / 8.32 / 3.31 / 2.81, its two
transposed products alone 7.73 / 16.89 / 6.69 / 5.71: what is left for
the loss is 0.9 / 1.3 / 0.6 / 0.6 ms where autodiff's took 6.9 / 15.3 /
7.8 / 6.8). The compiler takes the row maximum as an epilogue of the
head's product, reads the bf16 logits once more for the sum of the
exponentials, and forms `softmax - onehot` INSIDE both transposed
products as their operand: no cotangent array is written at all, and
the float32 [rows, V] operand the head's backward used to read is gone
with it. In the whole step that is the compiler's choice and is left
to it: forcing the cotangent to be written once as bf16 (an
`optimization_barrier` in the backward, tried and taken out) read
`throughput` 10.39 against 10.13 (nemotron) and 9.33 against 9.28
(joyai) but 6.86 against 6.95 (smallthinker) and 7.39 against 7.56
(sdar), parents 9.81 / 8.77 / 6.37 / 7.06 (my chip runs, PR 40; PERF.md
section 6 says where each difference sits, section 7 what a rule from
the shapes would need). **Row blocks are not built**: `lax.map` over
blocks of 2048 rows is slower at all four shapes (it stops that fusion:
the cotangent is written once a block and copied into place, 1.7-6.5
ms), and the memory they would bound is already down to the bf16
logits: the allocator's peak fell 1.05 / 1.96 / 0.87 / 0.68 GB.
The target's logit as `sum(where(iota == id, l, 0))` in the pass of the
exponentials is 0.10-0.21 ms faster than the gather; the gather stays,
because an id outside the vocabulary then reads NaN instead of a loss
that looks sound.
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


def class_ids(logits, labels):
    """The class ids, int32 of the logits' leading shape, out of labels
    [B] / [B, 1] beside [B, C] logits, or [B, S] / [B, S, 1] / [B, S, 2]
    (id, weight) beside [B, S, V] ones."""
    if logits.ndim == 3:
        labels = labels.reshape(labels.shape[0], labels.shape[1], -1)
    elif labels.ndim > 1:
        labels = labels.reshape(labels.shape[0], -1)
    else:
        return labels.astype(jnp.int32)
    return labels[..., 0].astype(jnp.int32)


@jax.custom_vjp
def target_log_probs(logits, ids):
    """log softmax(logits)[ids] a row: float32 of ``ids``' shape, for
    logits [..., V] in whatever dtype they are stored in and int32 ids
    [...]. The ONE place the losses over class ids touch the logits.

    It has its own backward, because autodiff of
    `take_along_axis(log_softmax(logits.astype(float32)), ids)` keeps the
    float32 log-probabilities [..., V] as the residual and makes of the
    backward a scatter-add of one scalar a row into a zero-filled float32
    [..., V], `log_softmax`'s transpose over that array and a cast: about
    ten float32 passes for one float a row. Here:

    - forward: the row maximum, `lse = m + log(sum(exp(l - m)))` and the
      target's logit (a gather of one element a row) from the logits as
      stored, cast to float32 element by element inside the reductions;
      `l[target] - lse`. Residuals: the logits as stored, ids, lse.
    - backward: `g * (onehot(ids) - exp(l - lse))` formed in float32
      element by element and rounded ONCE to the logits' dtype, which is
      where autodiff rounded its cotangent (the transpose of the loss's
      `astype`). No scatter, no zero fill, no float32 array of the
      logits' shape. A row with g == 0 gets exactly zero.
    """
    return _target_log_probs_fwd(logits, ids)[0]


def _target_log_probs_fwd(logits, ids):
    m = jnp.max(logits, axis=-1, keepdims=True).astype(jnp.float32)
    lse = m[..., 0] + jnp.log(jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m), axis=-1))
    target = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
    return target.astype(jnp.float32) - lse, (logits, ids, lse)


def _target_log_probs_bwd(residuals, g):
    logits, ids, lse = residuals
    classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
    d = g[..., None] * (
        (classes == ids[..., None]).astype(jnp.float32)
        - jnp.exp(logits.astype(jnp.float32) - lse[..., None]))
    return d.astype(logits.dtype), None


target_log_probs.defvjp(_target_log_probs_fwd, _target_log_probs_bwd)


def sparse_categorical_crossentropy(logits, labels):
    """[B, C] logits with [B]/[B,1] labels (classification), or [B, S, V]
    logits with [B, S]/[B,S,1] labels (token-level LM objective)."""
    return -jnp.mean(target_log_probs(logits, class_ids(logits, labels)))


def weighted_sparse_categorical_crossentropy(logits, labels):
    """[B, S, V] logits with labels [B, S, 2] float32 that carry, a
    position, the target's id and its weight c: sum(c * ce) / (B * S), the
    mean over ALL positions of the weighted cross-entropy (a masked
    diffusion objective: c = 1/t where the token was masked, else 0). A
    position of weight 0 gets exactly zero gradient."""
    return weighted_nll_mean(
        target_log_probs(logits, class_ids(logits, labels)), labels)


def weighted_nll_mean(logp, labels):
    """The weighted loss from the targets' log-probabilities [B, S] (the
    train step holds them once, for this and for `part_nll_sums`)."""
    return -jnp.mean(labels[..., 1].astype(jnp.float32) * logp)


def target_positions(labels):
    """Positions of weighted labels [B, S, 2] whose weight is not zero
    (the op counter `loss/target_positions`)."""
    return jnp.sum(labels[..., 1] > 0).astype(jnp.float32)


def part_nll_sums(logp, labels, parts):
    """name -> the sum of the UNWEIGHTED cross-entropies over the
    positions that carry a target, for each of the equal ``parts`` along
    the sequence that the targets' log-probabilities [B, S] and labels
    [B, S, 2] consist of (a main model's half and a
    multi-token-prediction module's: the op counters `loss/<part>_nll`)."""
    nll = jnp.where(labels[..., 1] > 0, -logp, 0.0)
    b, s = nll.shape
    sums = jnp.sum(nll.reshape(b, len(parts), s // len(parts)), axis=(0, 2))
    return {name: sums[i] for i, name in enumerate(parts)}


def exit_log_probs(gate_logits):
    """log p_t [B, T, S] of the exit distribution a looped model's gate
    emits, from the gate's logits [B, T, S] (float32): with lambda_t =
    sigmoid(g_t), p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T and
    p_T = prod_{j<T} (1 - lambda_j): the last pass takes what is left,
    its own gate is not read (and gets no gradient). In logs, so that
    the entropy is finite where a pass's mass underflows."""
    g = gate_logits.astype(jnp.float32)
    stay = jax.nn.log_sigmoid(-g)               # log(1 - lambda_t)
    before = jnp.cumsum(stay, axis=1) - stay    # log prod_{j<t}
    return jnp.concatenate(
        [jax.nn.log_sigmoid(g[:, :-1]) + before[:, :-1], before[:, -1:]],
        axis=1)


def expected_exit_loss(output, labels, passes, beta=0.0, uniform=False):
    """(loss, counted) of a looped model from its output [B, T*S, V + 1]
    (the T passes' logits laid end to end, pass-major, the gate's logit
    the last column) and labels [B, S], which every pass predicts: the
    targets' log-probabilities through `target_log_probs` ONCE over all
    T * S rows, then, in float32 over [B, T, S], the mean over the B * S
    positions of sum_t p_t * ce_t - beta * H(p), H = -sum_t p_t log p_t.
    ``uniform`` puts p_t = 1 / T in the gate's place (a control).
    ``counted``: sums over the positions for the epoch's op counters:
    `loss/exit_nll` and `loss/exit_mass` [T] (the unweighted
    cross-entropies and the masses p_t, a pass), `loss/exit_entropy`,
    `loss/target_positions`."""
    b, rows, width = output.shape
    s = rows // passes
    ids = jnp.tile(class_ids(output[:, :s], labels), (1, passes))
    nll = -target_log_probs(output[..., :width - 1], ids).reshape(
        b, passes, s)
    log_p = exit_log_probs(output[..., width - 1].reshape(b, passes, s))
    if uniform:
        log_p = jnp.full_like(log_p, -jnp.log(jnp.float32(passes)))
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=1)
    loss = jnp.mean(jnp.sum(p * nll, axis=1) - beta * entropy)
    return loss, {"loss/exit_nll": jnp.sum(nll, axis=(0, 2)),
                  "loss/exit_mass": jnp.sum(p, axis=(0, 2)),
                  "loss/exit_entropy": jnp.sum(entropy),
                  "loss/target_positions": jnp.float32(b * s)}


def expected_exit_sparse_categorical_crossentropy(output, labels, passes=1,
                                                  beta=0.0):
    return expected_exit_loss(output, labels, passes, beta)[0]


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
    LossType.EXPECTED_EXIT_SPARSE_CATEGORICAL_CROSSENTROPY:
        expected_exit_sparse_categorical_crossentropy,
    LossType.MEAN_SQUARED_ERROR_AVG_REDUCE: mse_avg,
    LossType.MEAN_SQUARED_ERROR_SUM_REDUCE: mse_sum,
    LossType.IDENTITY: identity,
}


def get_loss_fn(loss_type: LossType):
    return LOSS_FNS[loss_type]
