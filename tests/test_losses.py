"""`losses.target_log_probs`: the log-probability of a row's target with
its own backward (PR 40), held to the formulation it replaces.

The yardstick is what `losses.py` did before:
`take_along_axis(log_softmax(logits.astype(float32)), ids)` with its
backward by autodiff. Same mathematics to the last rounding point, so the
tolerances are derived, not tuned:

- value, a row: the old body rounds `a = l_t - m` and `a - log s`, the new
  one `lse = m + log s` and `l_t - lse`; both use the same `m` and `s`.
  Four roundings of half a unit each, of numbers no larger than
  `|a|, |lse|, |result|`: within `EPS32 * (|a| + |lse| + 2 |result|) / 2`.
  The mean loss is held to one float32 unit of the largest row's bound.
- gradient, an element: both are `g * (onehot - p)` rounded once to the
  logits' dtype. `p` is `exp` of an argument the two bodies round
  differently, by at most `delta = EPS32 * (|l| + |m| + |lse|)`, and `exp`
  turns an absolute error of its argument into a relative one of its
  value: `|g| * p * delta` before the rounding, plus ONE unit of the
  logits' dtype (`finfo.eps * |d|`) where the two float32 values lie on
  either side of a rounding boundary.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          SGDOptimizer, losses)

EPS32 = float(jnp.finfo(jnp.float32).eps)      # 2**-23


def yardstick(logits, ids):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(logp, ids[..., None], axis=-1)[..., 0]


def without_the_row_maximum(logits, ids):
    """Control: `log(sum(exp(l)))` overflows where the logits are large."""
    l32 = logits.astype(jnp.float32)
    return (jnp.take_along_axis(l32, ids[..., None], axis=-1)[..., 0]
            - jnp.log(jnp.sum(jnp.exp(l32), axis=-1)))


@jax.custom_vjp
def without_the_onehot(logits, ids):
    """Control: the backward forgets the target's own term."""
    return losses.target_log_probs(logits, ids)


def _bwd(res, g):
    logits, _, lse = res
    d = -g[..., None] * jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    return d.astype(logits.dtype), None


without_the_onehot.defvjp(losses._target_log_probs_fwd, _bwd)


def case(shape, dtype, seed=0, scale=3.0):
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(*shape) * scale, dtype)
    ids = jnp.asarray(rs.randint(0, shape[-1], shape[:-1]), jnp.int32)
    weights = jnp.asarray(rs.rand(*shape[:-1]) * (rs.rand(*shape[:-1]) > 0.3),
                          jnp.float32)
    return logits, ids, weights


def check_value_and_gradient(fn, logits, ids, weights):
    """`fn` against the yardstick under the weighted mean, by the bounds
    of the module's docstring."""
    def loss_by(f):
        return jax.value_and_grad(
            lambda l: -jnp.mean(weights * f(l, ids)))(logits)

    (want, d_want), (got, d_got) = loss_by(yardstick), loss_by(fn)
    assert d_got.dtype == logits.dtype and d_got.shape == logits.shape
    l64 = np.asarray(logits.astype(jnp.float32), np.float64)
    m = l64.max(-1)
    lse = m + np.log(np.exp(l64 - m[..., None]).sum(-1))
    target = np.take_along_axis(l64, np.asarray(ids)[..., None], -1)[..., 0]
    result = target - lse
    row_bound = EPS32 * (np.abs(target - m) + np.abs(lse)
                         + 2 * np.abs(result)) / 2
    rows = np.abs(np.asarray(fn(logits, ids), np.float64)
                  - np.asarray(yardstick(logits, ids), np.float64))
    assert (rows <= row_bound).all(), (rows / row_bound).max()
    assert abs(float(got) - float(want)) <= max(
        row_bound.max(), EPS32 * abs(float(want)))
    g = np.abs(np.asarray(weights, np.float64))[..., None] / ids.size
    p = np.exp(l64 - lse[..., None])
    delta = EPS32 * (np.abs(l64) + np.abs(m)[..., None]
                     + np.abs(lse)[..., None])
    d64 = np.asarray(d_want.astype(jnp.float32), np.float64)
    bound = g * p * delta + float(jnp.finfo(logits.dtype).eps) * np.abs(d64)
    off = np.abs(np.asarray(d_got.astype(jnp.float32), np.float64) - d64)
    assert (off <= bound).all(), (off / np.maximum(bound, 1e-300)).max()
    # a position of weight 0 gets exactly zero gradient
    assert (np.asarray(d_got.astype(jnp.float32))[
        np.asarray(weights) == 0] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(256, 1000), (2, 64, 1000), (3, 5, 7, 33)],
                         ids=["rank2", "rank3", "rank4"])
def test_value_and_gradient_are_the_float32_formulations(shape, dtype):
    check_value_and_gradient(losses.target_log_probs, *case(shape, dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_backward_without_the_onehot_fails_the_same_check(dtype):
    with pytest.raises(AssertionError):
        check_value_and_gradient(without_the_onehot,
                                 *case((2, 64, 1000), dtype))


def check_large_logits(fn, dtype):
    """Logits of +-3e4: `exp` of them overflows float32, the shifted
    form does not."""
    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.choice([-3e4, 3e4, 0.0, 17.0], (4, 9, 50)), dtype)
    ids = jnp.asarray(rs.randint(0, 50, (4, 9)), jnp.int32)
    value, d = jax.value_and_grad(
        lambda l: -jnp.mean(fn(l, ids)))(logits)
    assert np.isfinite(float(value))
    assert np.isfinite(np.asarray(d.astype(jnp.float32))).all()
    np.testing.assert_allclose(float(value), -float(jnp.mean(
        yardstick(logits, ids))), rtol=4 * EPS32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_logits_of_3e4_do_not_overflow(dtype):
    check_large_logits(losses.target_log_probs, dtype)
    with pytest.raises(AssertionError):
        check_large_logits(without_the_row_maximum, dtype)


# labels as the front ends hand them over, beside logits of both ranks
LABELS = {
    "[B]": ((6, 11), lambda ids, w: ids),
    "[B,1]": ((6, 11), lambda ids, w: ids[:, None]),
    "[B,S]": ((2, 5, 11), lambda ids, w: ids),
    "[B,S,1]": ((2, 5, 11), lambda ids, w: ids[..., None]),
    "[B,S,2]": ((2, 5, 11), lambda ids, w: jnp.stack(
        [ids.astype(jnp.float32), w], axis=-1)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", list(LABELS))
def test_every_sparse_loss_goes_through_it(form, dtype):
    shape, labels_of = LABELS[form]
    logits, ids, weights = case(shape, dtype, seed=2)
    labels = labels_of(ids, weights)
    assert (np.asarray(losses.class_ids(logits, labels)) == np.asarray(ids)
            ).all()
    weighted = form == "[B,S,2]"
    fn = (losses.weighted_sparse_categorical_crossentropy if weighted
          else losses.sparse_categorical_crossentropy)
    scale = weights if weighted else 1.0
    want, d_want = jax.value_and_grad(
        lambda l: -jnp.mean(scale * yardstick(l, ids)))(logits)
    got, d_got = jax.value_and_grad(lambda l: fn(l, labels))(logits)
    # (the bounds themselves are checked above; here that each loss is
    # the function under its labels: a float32 unit or two of the value;
    # of the gradient's largest element one unit of the dtype and the
    # docstring's `delta`, 64 float32 units where |l| + |m| + |lse| < 64)
    assert float(got) == pytest.approx(float(want), rel=4 * EPS32)
    np.testing.assert_allclose(
        np.asarray(d_got.astype(jnp.float32)),
        np.asarray(d_want.astype(jnp.float32)), rtol=0,
        atol=(float(jnp.finfo(dtype).eps) + 64 * EPS32)
        * float(jnp.max(jnp.abs(d_want))))
    jaxpr = str(jax.make_jaxpr(lambda l: fn(l, labels))(logits))
    assert jaxpr.count("custom_vjp_call") == 1
    assert "log_softmax" not in jaxpr


def test_under_jit_scan_and_checkpoint():
    """The multi-step path runs the train step inside `lax.scan`, a
    searched `_r` op under `jax.checkpoint`: a `custom_vjp` has to
    differentiate inside both."""
    logits, ids, weights = case((3, 4, 16, 50), jnp.bfloat16, seed=3)

    def loss(l, i, w, f=losses.target_log_probs):
        return -jnp.mean(w * f(l, i))

    plain = [jax.value_and_grad(loss)(logits[k], ids[k], weights[k])
             for k in range(3)]
    jitted = jax.jit(jax.value_and_grad(loss))
    remat = jax.jit(jax.value_and_grad(jax.checkpoint(loss)))

    def body(carry, xs):
        value, d = jax.value_and_grad(loss)(*xs)
        return carry + value, d

    total, scanned = jax.jit(lambda *a: jax.lax.scan(body, 0.0, a))(
        logits, ids, weights)
    for k, (value, d) in enumerate(plain):
        for got_value, got_d in (jitted(logits[k], ids[k], weights[k]),
                                 remat(logits[k], ids[k], weights[k]),
                                 (value, scanned[k])):
            assert float(got_value) == pytest.approx(float(value),
                                                     rel=4 * EPS32)
            assert got_d.dtype == jnp.bfloat16
            np.testing.assert_allclose(
                np.asarray(got_d.astype(jnp.float32)),
                np.asarray(d.astype(jnp.float32)), rtol=2 ** -7, atol=0)
    assert float(total) == pytest.approx(
        sum(float(v) for v, _ in plain), rel=8 * EPS32)


def weighted_model(parts):
    ff = FFModel(FFConfig(batch_size=2))
    t = ff.create_tensor((2, 8, 16))
    t = ff.dense(t, 24, use_bias=False)
    if parts:
        ff.loss_parts = parts
    ff.compile(AdamOptimizer(alpha=1e-3),
               LossType.WEIGHTED_SPARSE_CATEGORICAL_CROSSENTROPY, [])
    rs = np.random.RandomState(4)
    labels = np.stack([rs.randint(0, 24, (2, 8)).astype(np.float32),
                       rs.rand(2, 8) * (rs.rand(2, 8) > 0.3)],
                      axis=-1).astype(np.float32)
    return ff, rs.randn(2, 8, 16).astype(np.float32), labels


def test_the_loss_and_its_part_sums_share_one_evaluation():
    ff, x, labels = weighted_model(("main", "mtp"))
    ex = ff.executor
    logits = jnp.asarray(np.random.RandomState(5).randn(2, 8, 24),
                         jnp.bfloat16)
    counted = {}

    def loss(l):
        return ex._loss_value(l, jnp.asarray(labels), counted)

    jaxpr = str(jax.make_jaxpr(loss)(logits))
    assert set(counted) == {"loss/target_positions", "loss/main_nll",
                            "loss/mtp_nll"}
    assert jaxpr.count("custom_vjp_call") == 1
    # and in the whole train step: one `exp` over the logits in the
    # forward, one in the backward
    step = jax.make_jaxpr(ex._train_step_fn())(
        ff.params, ff.opt_state, ff.state, ff._stage_inputs([x]),
        ff._shard_batch(labels), jax.random.PRNGKey(0))
    exps = [line for line in str(step).splitlines()
            if " exp " in line and "[2,8,24]" in line.split("=")[0]]
    assert len(exps) == 2, exps
    # what the counters read is what the loss is made of
    ff.fit([x], labels, epochs=1, verbose=False)
    assert ff.op_counters["loss/target_positions"] == float(
        (labels[..., 1] > 0).sum())
    assert ff.op_counters["executor.loss_own_vjp"] == 1.0


def test_losses_that_do_not_take_class_ids_are_left_alone():
    ff = FFModel(FFConfig(batch_size=4))
    t = ff.create_tensor((4, 16))
    t = ff.dense(t, 5)
    ff.compile(SGDOptimizer(lr=0.1), LossType.MEAN_SQUARED_ERROR_AVG_REDUCE,
               [])
    rs = np.random.RandomState(6)
    ff.fit([rs.randn(4, 16).astype(np.float32)],
           rs.randn(4, 5).astype(np.float32), epochs=1, verbose=False)
    assert ff.executor.traced_gauges()["executor.loss_own_vjp"] == 0
    logits = jnp.asarray(rs.randn(4, 5), jnp.float32)
    onehot = jax.nn.one_hot(jnp.asarray(rs.randint(0, 5, 4)), 5)
    assert "custom_vjp_call" not in str(jax.make_jaxpr(
        losses.categorical_crossentropy)(logits, onehot))


STEP_TEXT = """HloModule jit_train_step

%fused_computation.1 (p.1: bf16[4,8]) -> f32[4,8] {
  %p.1 = bf16[4,8]{1,0} parameter(0)
  ROOT %convert.1 = f32[4,8]{1,0:T(8,128)} convert(%p.1)
}

%fused_computation.2 (p.2: f32[4,8]) -> (f32[4], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) {
  %p.2 = f32[4,8]{1,0} parameter(0)
  %exp.2 = f32[4,8]{1,0} exponential(%p.2)
  ROOT %tuple.2 = (f32[4], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) tuple()
}

ENTRY %main.9 (a.1: bf16[4,8], b.1: f32[32]) -> (f32[4], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) {
  %a.1 = bf16[4,8]{1,0} parameter(0)
  %b.1 = f32[32]{0} parameter(1)
  %fusion.1 = f32[4,8]{1,0:T(8,128)} fusion(%a.1), kind=kLoop, calls=%fused_computation.1
  %scatter.3 = f32[32]{0:T(1024)} scatter(%b.1, %a.1, %a.1), to_apply=%region.1
  %reshape.4 = f32[1,4,8]{2,1,0} reshape(%scatter.3)
  ROOT %fusion.2 = (f32[4], f32[4], f32[4], f32[4], f32[4], /*index=5*/f32[4]) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
}
"""


@pytest.mark.parametrize("dtype,elements,want", [
    ("f32", 32, ["%fusion.1", "%scatter.3", "%reshape.4"]),
    ("bf16", 32, []),           # the parameter is not the program's array
    ("f32", 4, ["%fusion.2"]),  # a tuple of results counts by each
])
def test_the_check_of_a_compiled_step_reads_what_lies_between_fusions(
        dtype, elements, want):
    """A whole step's ENTRY header lists hundreds of results with
    `/*index=5*/` marks: its instructions must not read as the body of
    the fused computation above it (they did for a while in PR 40, and the
    check passed on the parent's step too)."""
    from flexflow_tpu.obs.inspect import arrays_between_fusions, scatters_in
    assert arrays_between_fusions(STEP_TEXT, dtype, elements) == want
    assert scatters_in(STEP_TEXT) == [("", 32)]
