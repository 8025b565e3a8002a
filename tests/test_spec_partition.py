"""Multi-chip fused sweep: cost-balanced spec partitioning + parity.

Acceptance contract of the partitioned path (parallel/spec_partition +
ops/sweep.run_sweep_partitioned):

- predicted max-shard cost <= 1.3x mean-shard cost on the default
  LR + RF + XGB grid at 2, 4 and 8 shards (static cost model,
  impl/sweep_fragments.spec_units),
- an 8-shard sweep over the virtual CPU devices (conftest forces
  ``--xla_force_host_platform_device_count=8``) returns metrics identical
  to the 1-shard fused launch to 1e-6 for every family and depth of the
  default grid (two candidates of each) — candidate-granular splits reuse the same device RNG draws
  (ops/trees.rng_keys is keyed by seed, not group width), so the split is
  numerically invisible,
- ``_fused_sweep`` no longer bails out when ``model_shards() > 1``: a
  multi-device mesh routes through the partitioned plan.
"""
import numpy as np
import pytest

import jax

from transmogrifai_tpu.evaluators.classification import \
    OpBinaryClassificationEvaluator
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.trees import (
    OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.impl.selector import defaults as D
from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
from transmogrifai_tpu.ops import sweep as sweep_ops
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.parallel.spec_partition import (partition_spec,
                                                       predicted_balance)


def _default_candidates():
    """The reference default sweep: LR 8 + RF 18 + XGB 2 = 28 candidates."""
    return [
        (OpLogisticRegression(max_iter=50), D.logistic_regression_grid()),
        (OpRandomForestClassifier(), D.random_forest_grid()),
        (OpXGBoostClassifier(), D.xgboost_grid()),
    ]


def _plan(candidates, n_candidates):
    rng = np.random.default_rng(0)
    n, d, F = 240, 12, 3
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    beta = rng.normal(size=d)
    y = (X @ beta + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=7, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    plan = build_sweep_plan(candidates, X, y, train_w, ev)
    assert plan is not None and len(plan.spec[2]) == n_candidates
    return plan, train_w, val_mask, F


@pytest.fixture(scope="module")
def default_plan():
    """The 28-candidate grid: what the static partition tests balance."""
    return _plan(_default_candidates(), 28)


@pytest.fixture(scope="module")
def parity_plan(cut_binary_space):
    """What runs: two candidates of each family and depth of the default
    grid (LR 4 + RF depth 3 / 6 / 12 x 2 + XGB 2 = 12, so the 8 shards
    split forest and boosted groups between them), fewer trees and rounds."""
    return _plan(cut_binary_space(lr=4, rf_every=3, rounds=40), 12)


def test_balance_bound_default_grid(default_plan):
    plan, _, _, F = default_plan
    for k in (2, 4, 8):
        shards = partition_spec(plan.spec, plan.blob, k, plan.n_rows,
                                plan.n_features, F)
        assert len(shards) == k
        mx, mean = predicted_balance(shards)
        assert mx <= 1.3 * mean, (k, mx, mean)
        # every global candidate lands in exactly one shard
        all_cis = sorted(ci for s in shards for ci in s.cis)
        assert all_cis == list(range(28))
        for s in shards:
            assert list(s.cis) == sorted(s.cis)  # ascending global order
            assert len(s.spec[2]) == len(s.cis)  # sub-spec C == shard size


def test_single_shard_shortcut(default_plan):
    plan, _, _, F = default_plan
    shards = partition_spec(plan.spec, plan.blob, 1, plan.n_rows,
                            plan.n_features, F)
    assert len(shards) == 1
    assert shards[0].spec is plan.spec
    assert shards[0].cis == tuple(range(28))


def test_tiny_grid_drops_empty_shards():
    rng = np.random.default_rng(3)
    n, d, F = 120, 6, 2
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    y = (X[:, 0] > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=1, mesh=None)
    train_w, _ = cv.make_folds(n, None)
    cands = [(OpLogisticRegression(max_iter=20),
              [{"reg_param": 0.01, "elastic_net_param": 0.1},
               {"reg_param": 0.1, "elastic_net_param": 0.5}])]
    plan = build_sweep_plan(cands, X, y, train_w, ev)
    shards = partition_spec(plan.spec, plan.blob, 8, plan.n_rows,
                            plan.n_features, F)
    assert 1 <= len(shards) <= 2  # 2 candidates cannot fill 8 shards
    assert sorted(ci for s in shards for ci in s.cis) == [0, 1]


def test_8_shard_parity_full_default_grid(parity_plan):
    """The acceptance bar: 8-shard partitioned == 1-shard fused to 1e-6."""
    plan, train_w, val_mask, _F = parity_plan
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must force 8 virtual CPU devices"
    m1 = plan.run(train_w, val_mask)
    sweep_ops.reset_run_stats()
    m8 = plan.run_sharded(train_w, val_mask, devs[:8])
    assert m8.shape == m1.shape
    assert np.max(np.abs(m8 - m1)) <= 1e-6
    stats = sweep_ops.run_stats()
    assert stats["sweep_shards"] == 8
    launch = stats["launches"][-1]
    assert len(launch["per_shard"]) == 8
    assert sum(s["candidates"] for s in launch["per_shard"]) == 12
    # steady state: every per-shard program must come from the AOT cache
    sweep_ops.reset_run_stats()
    m8b = plan.run_sharded(train_w, val_mask, devs[:8])
    assert np.max(np.abs(m8b - m1)) <= 1e-6
    launch = sweep_ops.run_stats()["launches"][-1]
    assert all(s["compile_s"] == 0.0 for s in launch["per_shard"])


def test_fused_sweep_runs_under_multidevice_mesh():
    """``_fused_sweep`` must NOT return False when ``model_shards() > 1``
    anymore — the validator routes through the partitioned plan and its
    metrics match the single-device fused run."""
    rng = np.random.default_rng(5)
    n, d = 200, 8
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, :3].sum(1) + 0.2 * rng.normal(size=n) > 0).astype(np.float32)
    cands = [
        (OpLogisticRegression(max_iter=30),
         [{"reg_param": 0.01, "elastic_net_param": 0.2},
          {"reg_param": 0.1, "elastic_net_param": 0.0}]),
        (OpRandomForestClassifier(num_trees=8),
         [{"max_depth": 3}, {"max_depth": 5}]),
    ]
    ev = OpBinaryClassificationEvaluator()
    n_dev = min(len(jax.devices()), 8)
    mesh = make_mesh(n_data=1, n_model=n_dev)

    sweep_ops.reset_run_stats()
    meshed = OpCrossValidation(ev, num_folds=2, seed=11,
                               mesh=mesh).validate(cands, X, y)
    stats = sweep_ops.run_stats()
    # the fused path ran AND partitioned (4 candidates -> 4 shards)
    assert stats["sweep_shards"] == min(n_dev, 4), stats
    single = OpCrossValidation(ev, num_folds=2, seed=11,
                               mesh=None).validate(cands, X, y)
    assert meshed.best.model_name == single.best.model_name
    assert meshed.best.grid == single.best.grid
    for rm, rs in zip(meshed.results, single.results):
        assert rm.grid == rs.grid
        assert rm.metric_value == pytest.approx(rs.metric_value, abs=1e-6)
        for a, b in zip(rm.fold_metrics, rs.fold_metrics):
            assert a == pytest.approx(b, abs=1e-6)
