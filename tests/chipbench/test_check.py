"""The comparison that decides `correct`, on arrays made by hand."""

import numpy as np
import pytest

from benchmarks import harness

TOL = {"pred_nrmse": 0.05, "loss0_rel": 0.05, "later_loss_rel": 0.05}


def _record(preds, losses):
    return dict(preds=np.asarray(preds, np.float32), losses=list(losses))


def test_an_offset_common_to_all_predictions_does_not_dilute_the_error():
    rng = np.random.default_rng(0)
    want = rng.standard_normal(10000)
    got = want + 0.1 * rng.standard_normal(10000)
    near = harness.prediction_errors(got, want, False)
    far = harness.prediction_errors(got + 50.0, want + 50.0, False)
    assert near["nrmse"] == pytest.approx(0.1, rel=0.05)
    assert far["nrmse"] == pytest.approx(near["nrmse"], rel=1e-3)
    assert far["rel_l2"] < near["rel_l2"] / 20      # what nrmse avoids


def test_probabilities_are_compared_as_log_probabilities():
    rng = np.random.default_rng(1)
    logits = rng.standard_normal((64, 100))
    noisy = logits + 0.05 * rng.standard_normal((64, 100))

    def softmax(z):
        e = np.exp(z - z.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)

    errs = harness.prediction_errors(softmax(noisy), softmax(logits), True)
    assert errs["log_nrmse"] == pytest.approx(0.05, rel=0.1)


@pytest.mark.parametrize("got_losses,ok", [
    ((2.0, 1.5, 1.2), True),
    ((2.2, 1.5, 1.2), False),      # the step-0 loss is off by 10%
    ((2.0, 1.5, 1.0), False),      # a later loss is off
    ((2.0, float("nan"), 1.2), False),
])
def test_every_number_stands_beside_its_limit(got_losses, ok):
    want = _record(np.linspace(-1, 1, 50), (2.0, 1.5, 1.2))
    got = _record(np.linspace(-1, 1, 50), got_losses)
    rows = harness.compare(got, want, TOL)
    assert [r["name"] for r in rows] == [
        "pred_nrmse", "loss0_rel", "later_loss_rel", "nonfinite_values"]
    assert all(set(r) == {"name", "value", "limit", "ok"} for r in rows)
    assert all(r["ok"] for r in rows) is ok


def test_p95_interpolates_between_order_statistics():
    assert harness.p95(list(range(1, 101))) == pytest.approx(95.05)
    assert harness.p95([5.0, 5.0]) == 5.0
