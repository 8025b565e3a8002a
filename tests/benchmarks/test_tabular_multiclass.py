"""The plain reference of the multiclass grid
(``references/tabular_multiclass.py``) against the program, through the fused
sweep and the winner's refit, on a seeded 2,400 x 28 table with 10 classes at
the configuration's shares: softmax pairs, one forest of each depth, and the
refit's holdout class probabilities (``holdout_prob_gap``)."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program  # noqa: E402
from benchmarks.references import tabular_automl as base  # noqa: E402
from benchmarks.references import tabular_multiclass as ref  # noqa: E402
from benchmarks.tables import wide_tabular_multiclass as table_maker  # noqa: E402

FOLDS, K = 3, 10
CELL = json.load(open(os.path.join(
    ROOT, "benchmarks", "workloads", "scale-500-multiclass.sweep.json")))
LIMITS = CELL["correct"]["limits"]
SHARES = json.load(open(os.path.join(
    ROOT, "benchmarks", "configs", "scale-500-multiclass.json")))["table"]["class_shares"]

CFG = {
    "folds": FOLDS, "classes": K,
    "assumed_numbers": {"sketch_edges": {"rows": 262144, "seed": 0},
                        "max_frontier": 8, "rf_reg_lambda": 1e-6},
    "grid": {
        "lr": {"estimator": "transmogrifai_tpu.impl.classification.logistic:OpLogisticRegression",
               "fixed": {"max_iter": 50}, "keys": ["reg_param", "elastic_net_param"],
               "points": [[0.001, 0.1], [0.01, 0.5]]},
        "rf": {"estimator": "transmogrifai_tpu.impl.classification.trees:OpRandomForestClassifier",
               "fixed": {"num_trees": 4, "max_bins": 32, "feature_subset_strategy": "auto",
                         "seed": 42, "max_frontier": 8},
               "keys": ["max_depth", "min_info_gain", "min_instances_per_node"],
               "points": [[3, 0.001, 10], [6, 0.001, 10], [12, 0.01, 100]]},
    },
}


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(33)
    n, d = 2400, 28
    X = np.round(rng.normal(size=(n, d)), 2).astype(np.float32)
    X[:, 5] = (rng.random(n) < 0.3)            # a one-hot-like column
    latent = 1.5 * X[:, 0] - 0.8 * X[:, 1] * (X[:, 2] > 0) + 0.5 * X[:, 5] \
        + rng.logistic(size=n)
    y = np.empty(n, np.float32)
    y[np.argsort(latent, kind="stable")] = table_maker.class_of_rank(n, SHARES)
    fold = rng.permutation(n) % FOLDS
    return X, y, fold


@pytest.fixture(scope="module")
def program_scores(table):
    """Every candidate's [n, k] score block per fold, from the fused sweep's
    training program, and the fused metric pass's Errors."""
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.ops import sweep
    from transmogrifai_tpu.ops.metrics import MULTICLASS_METRICS

    X, y, fold = table
    train_w = np.stack([fold != f for f in range(FOLDS)]).astype(np.float32)
    val_w = 1.0 - train_w
    plan = build_sweep_plan(program.candidates(CFG), X, y, train_w,
                            Evaluators.MultiClassification.error())
    assert plan is not None and plan.spec[0] == ("multiclass", K)
    scores = sweep._run_scores(plan.spec, plan.X, tuple(plan.xbs), plan.y,
                               train_w, plan.blob)
    metrics = np.asarray(sweep._run_metrics(plan.spec, plan.y, scores, val_w))
    levels = sweep._spec_tree_levels(plan.spec, FOLDS)
    return (np.asarray(scores), metrics[..., MULTICLASS_METRICS.index("Error")],
            levels)


def test_forests_grow_k_channels_through_the_beam(program_scores):
    scores, _, levels = program_scores
    assert scores.shape == (FOLDS, 5, 2400, K)
    assert levels["tree_level_builds"] == FOLDS * 4 * (3 + 6 + 12)
    # frontier 8: depth 6 ranks at levels 3-5, depth 12 at levels 3-11
    assert levels["tree_beam_levels"] == FOLDS * 4 * (3 + 9)


@pytest.mark.parametrize("family,limit", [("lr", "softmax_fold_gap"),
                                          ("rf", "rf_fold_gap")])
def test_reference_matches_the_fused_sweep(table, program_scores, family, limit):
    X, y, fold = table
    scores, errors, _ = program_scores
    fitter = ref.Fitter(X, y, CFG, low=False)
    gaps, prob_gaps = [], []
    for ci, (fam, hp) in enumerate(base.flat_candidates(CFG)):
        if fam != family:
            continue
        f = ci % FOLDS
        val = fold == f
        dist = fitter.score(fam, hp, (fold != f).astype(np.float32))
        gaps.append(abs(float(errors[f, ci]) - ref.error(y[val], dist[val])))
        # the device's float32 Error is the host's float64 one to rounding
        assert float(errors[f, ci]) == pytest.approx(
            ref.error(y[val], scores[f, ci][val]), abs=1e-6)
        prob_gaps.append(np.abs(scores[f, ci] - dist).max())
    assert len(gaps) >= 2
    assert max(gaps) <= LIMITS[limit], gaps
    # a forest's class counts are sums of integers: the two agree to rounding
    assert max(prob_gaps) <= LIMITS[limit.replace("fold", "prob")]
    assert family != "rf" or max(prob_gaps) <= 1e-6


@pytest.mark.parametrize("family,hp", [("lr", (0.01, 0.5)), ("rf", (6, 0.001, 10))])
def test_refit_holdout_probabilities_match(table, family, hp):
    """``holdout_prob_gap``: the estimator refitted on every row, scored on
    rows that never trained, against the reference doing the same."""
    X, y, _ = table
    Xtr, ytr, Xho = X[:2000], y[:2000], X[2000:]
    (est, _), = [c for c in program.candidates(CFG)
                 if program.family_of(CFG, type(c[0]).__name__) == family]
    refit = est.copy_with_params(dict(zip(CFG["grid"][family]["keys"], hp)))
    _, _, prob = refit.predict_arrays(refit.fit_arrays(Xtr, ytr), Xho)
    dist = ref.Fitter(Xtr, ytr, CFG, low=False).score(
        family, hp, np.ones(len(ytr), np.float32), Xho)[len(ytr):]
    want = ref.probabilities(family, dist)
    assert prob.shape == want.shape == (400, K)
    assert np.abs(prob - want).max() <= LIMITS["holdout_prob_gap"]


def test_control_in_bfloat16_reads_other_probabilities(table):
    """The control tells: a softmax fit wholly in bfloat16 moves a
    probability by more than three times the cell's limit; a forest's leaves
    kept to bfloat16 move one by more than its sums' rounding."""
    X, y, fold = table
    w = (fold != 0).astype(np.float32)
    for fam, hp, least in (("lr", (0.001, 0.1), 3 * LIMITS["softmax_prob_gap"]),
                           ("rf", (6, 0.001, 10), 3 * LIMITS["rf_prob_gap"])):
        exact = ref.Fitter(X, y, CFG, low=False).score(fam, hp, w)
        low = ref.Fitter(X, y, CFG, low=True).score(fam, hp, w)
        assert np.abs(exact - low).max() > least, fam


def test_metrics_on_a_hand_worked_case():
    y = np.array([0, 0, 1, 1, 2, 2])
    dist = np.eye(3)[[0, 1, 1, 1, 0, 2]] + 0.0
    assert ref.error(y, dist) == pytest.approx(2 / 6)
    # per class (p, r): 0 -> (1/2, 1/2), 1 -> (2/3, 1), 2 -> (1, 1/2)
    assert ref.weighted_f1(y, dist) == pytest.approx(
        (0.5 + 0.8 + 2 / 3) / 3)
    # ties go to the first class, as jnp.argmax's do
    assert ref.error(np.array([1]), np.array([[0.5, 0.5]])) == 1.0
