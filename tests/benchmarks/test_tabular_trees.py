"""The plain reference of the tree families (``references/tabular_trees.py``)
against the program, through the fused sweep, on a seeded 2,000 x 40 table;
the required-operation counts of ``trees_ops_count`` on a hand-worked shape.
CPU: the program is the one the chip runs (one grower since PR 31: whole
forests and boosted rounds a level at a time, the histograms a GEMM over row
blocks), the reference grows one tree and one level at a time from exact
one-hot products; the two share no code."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import program, trees_ops_count  # noqa: E402
from benchmarks.references import tabular_automl as base  # noqa: E402
from benchmarks.references import tabular_trees as ref  # noqa: E402

FOLDS = 3
#: the cell's limits (workloads/scale-500-trees.sweep.json)
LIMITS = __import__("json").load(open(os.path.join(
    ROOT, "benchmarks", "workloads", "scale-500-trees.sweep.json")))["correct"]["limits"]

CFG = {
    "folds": FOLDS,
    "assumed_numbers": {"sketch_edges": {"rows": 262144, "seed": 0},
                        "max_frontier": 8, "rf_reg_lambda": 1e-6,
                        "lr_min_iterations": 200},
    "grid": {
        "lr": {"estimator": "transmogrifai_tpu.impl.classification.logistic:OpLogisticRegression",
               "fixed": {"max_iter": 50}, "keys": ["reg_param", "elastic_net_param"],
               "points": [[0.01, 0.1], [0.1, 0.5]]},
        "rf": {"estimator": "transmogrifai_tpu.impl.classification.trees:OpRandomForestClassifier",
               "fixed": {"num_trees": 5, "max_bins": 32, "feature_subset_strategy": "auto",
                         "seed": 42, "max_frontier": 8},
               "keys": ["max_depth", "min_info_gain", "min_instances_per_node"],
               "points": [[3, 0.001, 10], [6, 0.001, 10], [6, 0.01, 100]]},
        "xgb": {"estimator": "transmogrifai_tpu.impl.classification.trees:OpXGBoostClassifier",
                "fixed": {"num_round": 12, "eta": 0.3, "max_depth": 5, "gamma": 0.8,
                          "max_bins": 32, "reg_lambda": 1.0, "seed": 42, "max_frontier": 8},
                "keys": ["min_child_weight"], "points": [[1.0], [10.0]]},
    },
}


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(29)
    n, d = 2000, 40
    X = np.round(rng.normal(size=(n, d)), 2).astype(np.float32)
    X[:, 5] = (rng.random(n) < 0.3)            # a one-hot-like column
    z = X[:, 0] - 0.8 * X[:, 1] * (X[:, 2] > 0) + 0.5 * X[:, 5]
    y = (z + 0.7 * rng.normal(size=n) > 0).astype(np.float32)
    fold = rng.permutation(n) % FOLDS
    return X, y, fold


@pytest.fixture(scope="module")
def program_scores(table):
    """Every candidate's class-1 score of every row, per fold, from the fused
    sweep's training program, and the beam levels its launch counted."""
    from transmogrifai_tpu.evaluators.classification import (
        OpBinaryClassificationEvaluator)
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.ops import sweep

    X, y, fold = table
    train_w = np.stack([fold != f for f in range(FOLDS)]).astype(np.float32)
    plan = build_sweep_plan(program.candidates(CFG), X, y, train_w,
                            OpBinaryClassificationEvaluator())
    assert plan is not None
    scores = np.asarray(sweep._run_scores(plan.spec, plan.X, tuple(plan.xbs),
                                          plan.y, train_w, plan.blob))
    return scores, sweep._spec_tree_levels(plan.spec, FOLDS)


def test_beam_is_forced_and_counted(program_scores):
    _, levels = program_scores
    # rf: 3 folds x 5 trees x (3 + 6 + 6 levels); xgb: 3 x 2 x 12 rounds x 5
    assert levels["tree_level_builds"] == 3 * 5 * 15 + 3 * 2 * 12 * 5
    # frontier 8: depth 6 ranks at levels 3-5, depth 5 at levels 3-4
    assert levels["tree_beam_levels"] == 3 * 5 * (3 + 3) + 3 * 2 * 12 * 2


@pytest.mark.parametrize("family,limit", [("lr", "lr_fold_gap"),
                                          ("rf", "rf_fold_gap"),
                                          ("xgb", "xgb_fold_gap")])
def test_reference_matches_the_fused_sweep(table, program_scores, family, limit):
    X, y, fold = table
    scores, _ = program_scores
    flat = base.flat_candidates(CFG)
    fitter = ref.Fitter(X, y, CFG, low=False)
    gaps = []
    for ci, (fam, hp) in enumerate(flat):
        if fam != family:
            continue
        f = ci % FOLDS
        val = fold == f
        want = base.fold_metric(fitter, fam, hp, fold, f, y)
        gaps.append(abs(base.aupr(y[val], scores[f, ci][val]) - want))
    assert gaps
    # forests: every histogram is a sum of integers, so the folds agree to
    # rounding; the others inside the cell's own limits
    assert max(gaps) <= (1e-6 if family == "rf" else LIMITS[limit]), gaps


def test_control_in_bfloat16_reads_other_trees(table):
    """The control (histogram sums kept in bfloat16) grows other boosted
    trees: the comparison can tell it from the reference.  (A forest's sums
    at this size are integers under 256 below the first levels, which
    bfloat16 holds exactly; at the cell's rows they are not.)"""
    X, y, fold = table
    w = (fold != 0).astype(np.float32)
    exact = ref.TreeFitter(X, y, CFG, low=False).score("xgb", (1.0,), w)
    low = ref.TreeFitter(X, y, CFG, low=True).score("xgb", (1.0,), w)
    assert np.abs(exact - low).max() > 0.05


def test_holdout_rows_walk_the_same_trees(table):
    """Rows that never train read the leaf a training row with the same
    features reads (``walk`` against the device's routing)."""
    X, y, fold = table
    w = np.ones(len(y), np.float32)
    fitter = ref.TreeFitter(X, y, CFG, low=False)
    for fam, hp in (("rf", (6, 0.001, 10)), ("xgb", (1.0,))):
        s = fitter.score(fam, hp, w, X_other=X[:300])
        np.testing.assert_allclose(s[len(y):], s[:300], rtol=0, atol=2e-6)


def test_required_operations_of_a_hand_worked_shape():
    """One forest candidate (2 trees, depth 3, frontier 4) and one boosted
    (3 rounds, depth 2) on 30 sweep rows x 16 features, 3 folds, 4 bins."""
    cfg = {"folds": 3, "assumed_numbers": {"max_frontier": 4},
           "grid": {"rf": {"fixed": {"num_trees": 2, "max_bins": 4},
                           "keys": ["max_depth"], "points": [[3]]},
                    "xgb": {"fixed": {"num_round": 3, "max_bins": 4},
                            "keys": ["max_depth"], "points": [[2]]}}}
    w = trees_ops_count.sweep_step(cfg, 30, 16, winner_family="xgb",
                                   holdout_rows=6)
    n_tr, kept = 20, 4                                   # sqrt(16) features
    rf_hist = 2 * 3 * 2 * n_tr * kept                    # trees x levels x (g, h)
    xgb_hist = 3 * 2 * 2 * n_tr * 16
    rf_cells = (1 + 2 + 4) * kept * 4                    # open nodes x kept x bins
    xgb_cells = (1 + 2) * 16 * 4
    refit_hist = 3 * 2 * 2 * 30 * 16
    assert w["hist_flops"] == 3 * (rf_hist + xgb_hist) + refit_hist
    assert w["split_flops"] == 12 * (3 * (2 * rf_cells + 3 * xgb_cells)
                                     + 3 * xgb_cells)
    assert w["flops"] == w["hist_flops"] + w["split_flops"]
    streams = (3 + 3 * 2) * 30 * 16 + 3 * 2 * (30 + 6) * 16
    writes = 8 * (3 * (2 * rf_cells + 3 * xgb_cells) + 3 * xgb_cells)
    assert w["hist_bytes"] == w["bytes"] == streams + writes
    assert w["cv_fits"] == 6
    assert trees_ops_count.open_nodes(12, 256) == 255 + 4 * 256
