"""Row-sharded fused sweep: (data x model) mesh parity + telemetry.

Acceptance contract of the row-sharded path (ops/sweep.run_sweep_rowsharded
+ parallel/mesh collectives + validator routing):

- row-sharded metrics match the single-device fused launch to <= 1e-6 on
  the default grid's families and depths (one candidate of each: the
  whole-grid walk is ``chip_smoke.py --chips 4``'s) at (2,1), (2,4) and
  (4,2) virtual-CPU meshes (conftest forces ``--xla_force_host_platform_device_count=8``) —
  on-device RNG draws happen at the ORIGINAL row count and are sliced per
  shard, so bootstrap/subsample streams match the replicated launch
  draw-for-draw.  Histogram subtraction (an orthogonal approximation) is
  pinned OFF for the module — see ``_direct_histograms`` below,
- zero-weight row padding (n_rows not divisible by the data-shard count) is
  numerically invisible for binary AND regression problems,
- the validator routes through the row-sharded path when the active mesh
  has ``data > 1`` and DEGRADES GRACEFULLY (recorded fallback reason,
  replicated run) on too-few rows or unfusable candidates,
- utils/flops grows a per-axis ``collectives`` bucket: psum/all_gather
  counts + bytes on the ``data`` axis ONLY — per-candidate state never
  crosses the model axis,
- peak per-device X/y bytes scale as 1/data_shards (``per_device_bytes``
  in the launch entry).
"""
import numpy as np
import pytest

import jax

from transmogrifai_tpu.evaluators.classification import \
    OpBinaryClassificationEvaluator
from transmogrifai_tpu.evaluators.regression import OpRegressionEvaluator
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.trees import (
    OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.impl.regression.linear import OpLinearRegression
from transmogrifai_tpu.impl.regression.trees import OpRandomForestRegressor
from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
from transmogrifai_tpu.ops import sweep as sweep_ops
from transmogrifai_tpu.parallel import mesh as mesh_mod
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.utils import flops


@pytest.fixture(scope="module", autouse=True)
def _direct_histograms():
    """Pin TMOG_HIST_SUBTRACT=0 for this module.

    These tests pin the row-sharding MACHINERY's 1e-6 parity contract
    (psum'd histograms, sliced RNG streams, zero-weight padding).
    Histogram subtraction is an orthogonal approximation: its
    ``parent - light`` cancellation amplifies psum-ordering noise across
    the boosting chain (~6e-4 at 4 data shards on the default grid), so
    its parity is pinned separately — with documented tolerance — in
    tests/test_hist_subtract_parity.py.  The flag is read at trace time,
    so both program caches are dropped around the module.
    """
    import os

    old = os.environ.get("TMOG_HIST_SUBTRACT")
    os.environ["TMOG_HIST_SUBTRACT"] = "0"
    sweep_ops._aot_cache.clear()
    jax.clear_caches()
    yield
    if old is None:
        os.environ.pop("TMOG_HIST_SUBTRACT", None)
    else:
        os.environ["TMOG_HIST_SUBTRACT"] = old
    sweep_ops._aot_cache.clear()
    jax.clear_caches()


N_CANDIDATES = 8


@pytest.fixture(scope="module")
def default_plan(cut_binary_space):
    """The reference default sweep cut to one candidate of each family and
    depth (LR 3 + RF depth 3 / 6 / 12 + XGB 2), with fewer trees and rounds:
    the same fragments, psums and RNG slices as the 28-candidate grid."""
    rng = np.random.default_rng(0)
    n, d, F = 240, 12, 3
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    beta = rng.normal(size=d)
    y = (X @ beta + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=7, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    candidates = cut_binary_space(rounds=40)
    assert sorted({g["max_depth"] for g in candidates[1][1]}) == [3, 6, 12]
    plan = build_sweep_plan(candidates, X, y, train_w, ev)
    assert plan is not None and len(plan.spec[2]) == N_CANDIDATES
    return plan, train_w, val_mask


@pytest.fixture(scope="module")
def single_ref(default_plan):
    plan, train_w, val_mask = default_plan
    return plan.run(train_w, val_mask)


@pytest.mark.parametrize("n_data,n_model", [(2, 1), (2, 4), (4, 2)],
                         ids=["2x1", "2x4", "4x2"])
def test_rowsharded_parity_full_default_grid(default_plan, single_ref,
                                             n_data, n_model):
    """The acceptance bar: row-sharded == single-device fused to 1e-6 on
    every family and depth of the default grid, with honest launch
    telemetry."""
    plan, train_w, val_mask = default_plan
    assert len(jax.devices()) >= n_data * n_model, \
        "conftest must force 8 virtual CPU devices"
    mesh = make_mesh(n_data=n_data, n_model=n_model)
    sweep_ops.reset_run_stats()
    mrs = plan.run_rowsharded(train_w, val_mask, mesh)
    assert mrs.shape == single_ref.shape
    assert np.max(np.abs(mrs - single_ref)) <= 1e-6
    stats = sweep_ops.run_stats()
    assert stats["data_shards"] == n_data
    launch = stats["launches"][-1]
    assert launch["rowsharded"] is True
    assert launch["shards"] == n_model
    assert sum(s["candidates"] for s in launch["per_shard"]) == N_CANDIDATES
    # one row shard per chip: every model column spans n_data devices
    for s in launch["per_shard"]:
        assert len(s["devices"]) == n_data
        assert s["rows_local"] == 240 // n_data
    # communication happens over the data axis ONLY (no cross-model traffic)
    assert set(launch["collectives"]) == {mesh_mod.DATA_AXIS}
    coll = launch["collectives"][mesh_mod.DATA_AXIS]
    assert coll["count"] > 0 and coll["bytes"] > 0
    # 1/data_shards peak bytes (240 divides evenly: no padding slack)
    pdb = launch["per_device_bytes"]
    assert pdb["X"] * n_data == pdb["X_replicated"] == 240 * 12 * 4
    assert pdb["y"] * n_data == pdb["y_replicated"] == 240 * 4


def test_rowsharded_steady_state_aot_cache(default_plan, single_ref):
    """Repeat launches must come from the AOT cache (compile_s == 0)."""
    plan, train_w, val_mask = default_plan
    mesh = make_mesh(n_data=4, n_model=2)
    plan.run_rowsharded(train_w, val_mask, mesh)  # warm (other test's mesh
    # object is equal, so this is already cached; asserted below either way)
    sweep_ops.reset_run_stats()
    mrs = plan.run_rowsharded(train_w, val_mask, mesh)
    assert np.max(np.abs(mrs - single_ref)) <= 1e-6
    launch = sweep_ops.run_stats()["launches"][-1]
    assert all(s["compile_s"] == 0.0 for s in launch["per_shard"])


def test_rowsharded_flops_collectives(default_plan):
    """satellite: the flops ``collectives`` bucket records psum + all_gather
    count/bytes per axis — the row-sharded sweep's communication claim."""
    plan, train_w, val_mask = default_plan
    mesh = make_mesh(n_data=4, n_model=2)
    plan.run_rowsharded(train_w, val_mask, mesh)  # warm outside accounting
    flops.enable()
    flops.reset()
    try:
        plan.run_rowsharded(train_w, val_mask, mesh)
        acct = flops.totals()
    finally:
        flops.disable()
        flops.reset()
    colls = acct["collectives"]
    assert set(colls) == {mesh_mod.DATA_AXIS}
    data = colls[mesh_mod.DATA_AXIS]
    assert data["count"] > 0 and data["bytes"] > 0
    # both reduction styles are exercised: psum'd normal equations /
    # histograms AND the all_gather reassembling rank-metric row order
    assert data["psum_count"] > 0
    assert data["all_gather_count"] > 0
    assert data["count"] == data["psum_count"] + data["all_gather_count"]
    # per-device attribution carries the same axis split
    dev_colls = [v.get("collectives") for v in acct["by_device"].values()]
    assert any(dc and mesh_mod.DATA_AXIS in dc for dc in dev_colls)


# ---------------------------------------------------------------------------
# Zero-weight row padding: n_rows not divisible by the data-shard count
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def pad_data():
    rng = np.random.default_rng(23)
    n, d = 237, 8  # 237 = 3 * 79: indivisible by 2 and 4
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    beta = rng.normal(size=d)
    z = X @ beta
    y_bin = (z + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    y_reg = (z + 0.3 * rng.normal(size=n)).astype(np.float32)
    return X, y_bin, y_reg


def _plan(cands, X, y, ev, F=2, seed=13):
    cv = OpCrossValidation(ev, num_folds=F, seed=seed, mesh=None)
    train_w, val_mask = cv.make_folds(len(y), None)
    plan = build_sweep_plan(cands, X, y, train_w, ev)
    assert plan is not None
    return plan, train_w, val_mask


def _binary_pad_plan(pad_data):
    X, y, _ = pad_data
    cands = [
        (OpLogisticRegression(max_iter=30),
         [{"reg_param": 0.01, "elastic_net_param": 0.2},
          {"reg_param": 0.1, "elastic_net_param": 0.0}]),
        (OpRandomForestClassifier(num_trees=6), [{"max_depth": 3}]),
        (OpXGBoostClassifier(num_round=5, max_depth=3), [{"eta": 0.3}]),
    ]
    return _plan(cands, X, y, OpBinaryClassificationEvaluator())


def _regression_pad_plan(pad_data):
    X, _, y = pad_data
    cands = [
        (OpLinearRegression(),
         [{"reg_param": 0.01, "elastic_net_param": 0.1},
          {"reg_param": 0.1, "elastic_net_param": 0.5}]),
        (OpRandomForestRegressor(num_trees=6), [{"max_depth": 3}]),
    ]
    return _plan(cands, X, y, OpRegressionEvaluator())


@pytest.mark.parametrize("build", [_binary_pad_plan, _regression_pad_plan],
                         ids=["binary", "regression"])
def test_rowsharded_zero_weight_padding(pad_data, build):
    """Padding rows (zero fold weight, zero val weight) are numerically
    invisible: 237 rows pad to 238 at 2 data shards and the metrics still
    match the unpadded single-device launch — including the rank-based
    AuROC/AuPR, whose kernels exclude vm=0 rows."""
    plan, train_w, val_mask = build(pad_data)
    single = plan.run(train_w, val_mask)
    mesh = make_mesh(n_data=2, n_model=2)
    sweep_ops.reset_run_stats()
    mrs = plan.run_rowsharded(train_w, val_mask, mesh)
    assert np.max(np.abs(mrs - single)) <= 1e-6
    launch = sweep_ops.run_stats()["launches"][-1]
    # 237 -> 238 padded rows, 119 per shard
    assert all(s["rows_local"] == 119 for s in launch["per_shard"])
    assert launch["per_device_bytes"]["X"] == 119 * 8 * 4


# ---------------------------------------------------------------------------
# Validator routing + graceful fallback
# ---------------------------------------------------------------------------
def test_validator_routes_rowsharded(pad_data):
    """A (data > 1) mesh routes ``_fused_sweep`` through the row-sharded
    launcher; metrics match the single-device validator run."""
    X, y, _ = pad_data
    cands = [
        (OpLogisticRegression(max_iter=30),
         [{"reg_param": 0.01, "elastic_net_param": 0.2},
          {"reg_param": 0.1, "elastic_net_param": 0.0}]),
        (OpRandomForestClassifier(num_trees=6), [{"max_depth": 3}]),
        (OpXGBoostClassifier(num_round=5, max_depth=3), [{"eta": 0.3}]),
    ]
    ev = OpBinaryClassificationEvaluator()
    mesh = make_mesh(n_data=2, n_model=2)
    meshed = OpCrossValidation(ev, num_folds=2, seed=13,
                               mesh=mesh).validate(cands, X, y)
    stats = sweep_ops.run_stats()
    assert stats["data_shards"] == 2
    assert stats["launches"][-1]["rowsharded"] is True
    assert stats["fallbacks"] == []
    single = OpCrossValidation(ev, num_folds=2, seed=13,
                               mesh=None).validate(cands, X, y)
    assert meshed.best.model_name == single.best.model_name
    assert meshed.best.grid == single.best.grid
    for rm, rs in zip(meshed.results, single.results):
        assert rm.metric_value == pytest.approx(rs.metric_value, abs=1e-6)


def test_validator_fallback_too_few_rows():
    """Below data_shards * min_rows_per_shard the validator records the
    reason and runs the REPLICATED path — never errors."""
    rng = np.random.default_rng(31)
    n, d = 40, 4  # 40 < 4 * 32 rows: the 4-wide data axis is not viable
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    cands = [(OpLogisticRegression(max_iter=20),
              [{"reg_param": 0.01, "elastic_net_param": 0.1},
               {"reg_param": 0.1, "elastic_net_param": 0.5}])]
    ev = OpBinaryClassificationEvaluator()
    mesh = make_mesh(n_data=4, n_model=2)
    meshed = OpCrossValidation(ev, num_folds=2, seed=3,
                               mesh=mesh).validate(cands, X, y)
    stats = sweep_ops.run_stats()
    fb = stats["fallbacks"]
    assert len(fb) == 1
    assert fb[0]["reason"] == "too_few_rows_for_data_axis"
    assert fb[0]["rows"] == n and fb[0]["data_shards"] == 4
    # every launch ran replicated (model-sharded at most)
    assert all(not e.get("rowsharded") for e in stats["launches"])
    single = OpCrossValidation(ev, num_folds=2, seed=3,
                               mesh=None).validate(cands, X, y)
    for rm, rs in zip(meshed.results, single.results):
        assert rm.metric_value == pytest.approx(rs.metric_value, abs=1e-6)


def test_validator_fallback_custom_estimator():
    """An estimator SUBCLASS blocks fusion (it may override fit semantics);
    under a data mesh the validator records that the data axis sat idle and
    the per-family path still produces a summary."""

    class TunedLogisticRegression(OpLogisticRegression):
        pass

    rng = np.random.default_rng(37)
    n, d = 200, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, :2].sum(1) + 0.2 * rng.normal(size=n) > 0).astype(np.float32)
    cands = [(TunedLogisticRegression(max_iter=20),
              [{"reg_param": 0.01, "elastic_net_param": 0.1},
               {"reg_param": 0.1, "elastic_net_param": 0.0}])]
    ev = OpBinaryClassificationEvaluator()
    mesh = make_mesh(n_data=2, n_model=2)
    summary = OpCrossValidation(ev, num_folds=2, seed=5,
                                mesh=mesh).validate(cands, X, y)
    assert len(summary.results) == 2
    assert summary.best.metric_value == summary.best.metric_value  # finite path ran
    fb = sweep_ops.run_stats()["fallbacks"]
    assert any(e["reason"] == "unfusable_candidates_block_data_axis"
               for e in fb)


def test_env_mesh_resolution(monkeypatch):
    """TMOG_MESH drives ``mesh='auto'`` resolution; unsatisfiable or unset
    requests degrade to the all-model-axis auto mesh."""
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=2, mesh="auto")
    monkeypatch.setenv("TMOG_MESH", "2x4")
    m = cv._resolve_mesh()
    assert m is not None
    assert int(m.shape[mesh_mod.DATA_AXIS]) == 2
    assert int(m.shape[mesh_mod.MODEL_AXIS]) == 4
    monkeypatch.setenv("TMOG_MESH", "64x64")  # cannot be satisfied: auto
    m = cv._resolve_mesh()
    assert m is None or mesh_mod.DATA_AXIS in m.shape  # auto_mesh fallback
    if m is not None:
        assert int(m.shape[mesh_mod.DATA_AXIS]) == 1
    monkeypatch.setenv("TMOG_MESH", "not-a-mesh")
    assert mesh_mod.env_mesh() is None
    monkeypatch.delenv("TMOG_MESH")
    assert mesh_mod.env_mesh() is None


def test_shard_rows_pads_and_places():
    """parallel.mesh.shard_rows: rows pad to a multiple of the data-shard
    count with the fill value and land row-sharded over DATA_AXIS."""
    mesh = make_mesh(n_data=4, n_model=1)
    x = np.arange(30, dtype=np.float32).reshape(10, 3)
    arr, n = mesh_mod.shard_rows(x, mesh)
    assert n == 10
    assert arr.shape == (12, 3)  # padded to a multiple of 4
    host = np.asarray(arr)
    assert np.array_equal(host[:10], x)
    assert np.all(host[10:] == 0.0)
    # fold-weight style: pad along axis 1
    w = np.ones((2, 10), np.float32)
    arr2, n2 = mesh_mod.shard_rows(w, mesh, axis=1)
    assert n2 == 10 and arr2.shape == (2, 12)
    assert np.all(np.asarray(arr2)[:, 10:] == 0.0)


def test_rowshard_viability_policy(monkeypatch):
    assert not mesh_mod.rowshard_viable(100, 1)  # no data axis: never
    assert mesh_mod.rowshard_viable(64, 2)       # 64 >= 2 * 32
    assert not mesh_mod.rowshard_viable(63, 2)
    monkeypatch.setenv("TMOG_MIN_ROWS_PER_SHARD", "8")
    assert mesh_mod.rowshard_viable(16, 2)
    monkeypatch.delenv("TMOG_MIN_ROWS_PER_SHARD")
