"""``ops.sweep.keep_scores``: a single-device launch hands whoever asked the
[F, C, n, k] score block its training program gave its metric pass — the
block the metrics were computed from, in the launch's candidate order — and
holds nothing where nobody asked."""
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.trees import OpRandomForestClassifier
from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
from transmogrifai_tpu.ops import sweep
from transmogrifai_tpu.ops.metrics import MULTICLASS_METRICS

FOLDS, K = 3, 4


@pytest.fixture(scope="module")
def launch():
    rng = np.random.default_rng(33)
    n, d = 300, 8
    X = np.round(rng.normal(size=(n, d)), 2).astype(np.float32)
    y = np.clip(np.round(X[:, 0] + X[:, 1] + 1.5), 0, K - 1).astype(np.float32)
    fold = rng.permutation(n) % FOLDS
    train_w = np.stack([fold != f for f in range(FOLDS)]).astype(np.float32)
    plan = build_sweep_plan(
        [(OpLogisticRegression(max_iter=20), [{"reg_param": 0.01}, {"reg_param": 0.1}]),
         (OpRandomForestClassifier(num_trees=5), [{"max_depth": 3}])],
        X, y, train_w, Evaluators.MultiClassification.error())
    assert plan is not None and plan.spec[0] == ("multiclass", K)
    return plan, y, train_w, 1.0 - train_w


def test_nothing_is_held_where_nobody_asked(launch):
    plan, _, train_w, val_w = launch
    sweep.reset_run_stats()
    plan.run(train_w, val_w)
    assert sweep.last_scores() is None
    assert sweep.run_stats()["launches"][-1]["split"] is False


def test_the_kept_block_is_what_the_metrics_were_computed_from(launch):
    plan, y, train_w, val_w = launch
    plain = plan.run(train_w, val_w)
    sweep.reset_run_stats()
    sweep.keep_scores(True)
    try:
        kept_run = plan.run(train_w, val_w)
        block = np.asarray(sweep.last_scores())
    finally:
        sweep.keep_scores(False)
    assert sweep.last_scores() is None
    # kept: the two-program path whatever the block's size, the same numbers
    assert sweep.run_stats()["launches"][-1]["split"] is True
    np.testing.assert_allclose(kept_run, plain, rtol=0, atol=1e-6)
    assert block.shape == (FOLDS, 3, len(y), K)
    np.testing.assert_array_equal(block, np.asarray(sweep._run_scores(
        plan.spec, plan.X, tuple(plan.xbs), plan.y, train_w, plan.blob)))
    np.testing.assert_allclose(block.sum(axis=-1), 1.0, rtol=0, atol=1e-5)
    errors = kept_run[..., MULTICLASS_METRICS.index("Error")]
    for f in range(FOLDS):
        val = val_w[f] > 0
        for c in range(3):
            wrong = np.argmax(block[f, c][val], axis=1) != y[val]
            assert errors[f, c] == pytest.approx(wrong.mean(), abs=1e-6)
