"""Batched fold x grid sweeps for tree models must match the per-candidate
loop path exactly (SURVEY §2.7 axis 2 — the selector sweep as one launch)."""
import numpy as np
import pytest

from transmogrifai_tpu.impl.classification.trees import (OpRandomForestClassifier,
                                                         OpXGBoostClassifier)
from transmogrifai_tpu.impl.regression.trees import (OpRandomForestRegressor,
                                                     OpXGBoostRegressor)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    n, d = 200, 12
    X = rng.normal(0, 1, (n, d)).astype(np.float32)
    beta = rng.normal(0, 0.5, d)
    z = X @ beta
    y_bin = (1 / (1 + np.exp(-z)) > rng.random(n)).astype(np.float32)
    y_reg = (z + rng.normal(0, 0.3, n)).astype(np.float32)
    folds = (rng.random((2, n)) > 0.3).astype(np.float32)
    return X, y_bin, y_reg, folds


def _check_matches_loop(est, grids, X, y, folds, prob_check=False):
    batched = est.fit_grid_folds(X, y, folds, grids)
    for f in range(folds.shape[0]):
        for ci, grid in enumerate(grids):
            cand = est.copy_with_params(grid)
            params = cand.fit_arrays(X, y, w=folds[f])
            pred, raw, prob = cand.predict_arrays(params, X)
            pb, rb, probb = batched[f][ci]
            assert np.mean(pb == pred) > 0.97, (f, ci)
            if prob_check and prob is not None:
                assert np.corrcoef(probb[:, -1], prob[:, -1])[0, 1] > 0.99


def test_rf_classifier_batched_matches_loop(data):
    X, y, _, folds = data
    grids = [{"max_depth": 3, "min_instances_per_node": 1, "num_trees": 10},
             {"max_depth": 3, "min_instances_per_node": 20, "num_trees": 10},
             {"max_depth": 5, "min_instances_per_node": 1, "num_trees": 10}]
    _check_matches_loop(OpRandomForestClassifier(seed=5), grids, X, y, folds,
                        prob_check=True)


def test_xgb_classifier_batched_matches_loop(data):
    X, y, _, folds = data
    grids = [{"num_round": 15, "eta": 0.2, "max_depth": 3, "min_child_weight": 1.0},
             {"num_round": 15, "eta": 0.05, "max_depth": 3, "min_child_weight": 5.0}]
    _check_matches_loop(OpXGBoostClassifier(max_bins=16), grids, X, y, folds,
                        prob_check=True)


def test_rf_regressor_batched_matches_loop(data):
    X, _, y, folds = data
    grids = [{"max_depth": 4, "min_instances_per_node": 1, "num_trees": 8},
             {"max_depth": 4, "min_instances_per_node": 10, "num_trees": 8}]
    est = OpRandomForestRegressor(seed=5)
    batched = est.fit_grid_folds(X, y, folds, grids)
    for f in range(2):
        for ci, grid in enumerate(grids):
            cand = est.copy_with_params(grid)
            params = cand.fit_arrays(X, y, w=folds[f])
            pred, _, _ = cand.predict_arrays(params, X)
            np.testing.assert_allclose(batched[f][ci][0], pred, rtol=1e-4,
                                       atol=1e-4)


def test_xgb_regressor_batched_close_to_loop(data):
    X, _, y, folds = data
    grids = [{"num_round": 10, "eta": 0.3, "max_depth": 3}]
    est = OpXGBoostRegressor(max_bins=16)
    batched = est.fit_grid_folds(X, y, folds, grids)
    cand = est.copy_with_params(grids[0])
    params = cand.fit_arrays(X, y, w=folds[0])
    pred, _, _ = cand.predict_arrays(params, X)
    # fold base_score differs from full-data base_score by design; correlation
    # of fitted functions must still be essentially 1
    assert np.corrcoef(batched[0][0][0], pred)[0, 1] > 0.99


def test_non_batchable_grid_key_falls_back(data):
    X, y, _, folds = data
    with pytest.raises(NotImplementedError):
        OpRandomForestClassifier().fit_grid_folds(X, y, folds,
                                                  [{"bogus_param": 1}])


def test_frontier_bound_uses_actual_weight_sum():
    """DataBalancer-style up-weighted folds (sum(w) ~ n/(1-p) > 1.25n) must
    not be declared exact for a frontier sized from the 1.25n heuristic
    (round-4 ADVICE: exact_cap's count clamp silently kept first-come splits
    instead of the gain beam when the bound was violated)."""
    from transmogrifai_tpu.ops import trees as Tr

    n, depth, mcw = 1000, 10, 1.0
    # heuristic frontier sized for ~unit weights
    frontier = Tr.frontier_cap(n, depth, mcw, h_max=0.25, max_frontier=512)
    assert Tr.frontier_is_exact(n, depth, mcw, 0.25, frontier)
    # balancer weights sum to 4n: the same frontier is NOT provably exact...
    heavy = 4.0 * n
    assert not Tr.frontier_is_exact(n, depth, mcw, 0.25, frontier,
                                    total_weight=heavy)
    # ...and sizing from the actual sum restores exactness (or unrolls)
    f2 = Tr.frontier_cap(n, depth, mcw, h_max=0.25, max_frontier=4096,
                         total_weight=heavy)
    assert Tr.frontier_is_exact(n, depth, mcw, 0.25, f2, total_weight=heavy)


def test_zero_reg_lambda_leaves_finite(data):
    """reg_lambda=0 used to 0/0-NaN dead frontier slots and poison every
    child leaf through the packing matmul (round-4 ADVICE)."""
    import jax
    import jax.numpy as jnp

    from transmogrifai_tpu.ops import trees as Tr

    X, y, _, _ = data
    n, d = X.shape
    Xb, _ = Tr.quantize(X, 16)
    g = -np.asarray(y, np.float32)[:, None]
    tree = jax.jit(lambda xb, gg: Tr.grow_tree(
        xb, gg, jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
        jnp.ones(d, jnp.float32), max_depth=4, n_bins=16, frontier=16,
        reg_lambda=0.0))(jnp.asarray(Xb), jnp.asarray(g))
    assert bool(jnp.isfinite(tree.leaf_val).all())


@pytest.mark.parametrize("k", [2, 10], ids=["one-channel", "ten-classes"])
def test_fused_boosted_margins_are_the_estimators_walk(data, k):
    """A boosted group of the fused sweep (``_gbt_group_scores``: every
    round's leaves read by selection at ``row_node``, ``ops/trees.read_leaves``)
    against the estimator path, a fold's candidate fitted alone and its stacked
    trees walked over the training rows (``predict_gbt``): the same margins,
    logistic at one channel and softmax at ten; and a launch of the plan counts
    trees x rows x channels leaf reads."""
    from transmogrifai_tpu.evaluators import Evaluators
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.impl.trees_common import tree_from_params
    from transmogrifai_tpu.ops import sweep, trees as Tr

    X, y_bin, y_reg, folds = data
    n = len(y_bin)
    y = y_bin if k == 2 else (np.argsort(np.argsort(y_reg)) * k // n).astype(np.float32)
    c = 1 if k == 2 else k
    grids = [{"num_round": 6, "eta": 0.2, "max_depth": 3, "min_child_weight": 1.0},
             {"num_round": 6, "eta": 0.05, "max_depth": 3, "min_child_weight": 5.0}]
    est = OpXGBoostClassifier(max_bins=16)
    ev = (Evaluators.BinaryClassification.auPR() if k == 2
          else Evaluators.MultiClassification.error())
    plan = build_sweep_plan([(est, grids)], X, y, folds, ev)
    assert plan is not None
    (frag,) = plan.spec[1]
    assert frag[:3] == ("gbt", "logistic" if k == 2 else "softmax", c)
    (group,) = frag[3]
    fused = np.asarray(sweep._gbt_group_scores(
        group, tuple(plan.xbs), plan.y, folds, plan.blob, frag[1], c))
    assert fused.shape == (2, 2, n, c)
    for f in range(2):
        for ci, grid in enumerate(grids):
            params = est.copy_with_params(grid).fit_arrays(X, y, w=folds[f])
            walk = np.asarray(Tr.predict_gbt(
                Tr.bin_with_edges(X, params["edges"]), tree_from_params(params),
                3, float(params["eta"])))
            np.testing.assert_allclose(fused[f, ci], walk, rtol=0, atol=1e-5)
    sweep.reset_run_stats()
    sweep.run_sweep(plan.spec, plan.X, tuple(plan.xbs), plan.y, folds,
                    1.0 - folds, plan.blob)
    assert sweep.run_stats()["tree_leaf_reads"] == 2 * 2 * 6 * n * c
