"""MFU-gap levers: candidate-packed launches + cross-device GBT pipelining
(TMOG_SWEEP_PACK / TMOG_GBT_PIPELINE).

Acceptance contract:

- ``launch_packs`` at the default budgets returns the SAME partition
  ``partition_spec`` builds (byte-identical programs — packing off vs on
  must be bit-exact f32), and splits queues only when the HBM or the
  learned-cost budget says so;
- the packed metric map (``_metric_pack_size`` candidates per ``lax.map``
  step on the row-sharded path) is bit-exact vs the historical
  one-candidate map;
- pipelined partitioned dispatch is bit-exact vs sequential dispatch, and
  a WARM pipelined launch reports ``gbt_chain_eff`` with strictly fewer
  effective sequential levels than the full dependency chain (floored at
  ``ceil(levels / n_shards)``);
- launch-count telemetry is honest: ``sweep_pack_count`` equals the
  launches the FLOP ledger saw dispatched, ``launches_avoided`` counts
  against the one-launch-per-candidate baseline;
- the hedge deadline clock starts AFTER the pipelined prologue: a cold
  pipelined run whose compile prologue dwarfs the armed deadlines must
  fire zero hedges.

Env-flip convention (tests/test_hist_subtract_parity.py): compiled
programs bake the trace knobs in at lowering.  The AOT cache keys carry
them (``_trace_knobs``) but jit's traced-program cache does not, so every
configuration flip clears ``jax.clear_caches()`` AND
``sweep_ops._aot_cache``.
"""
import os

import numpy as np
import pytest

import jax

from transmogrifai_tpu.costmodel.features import FEATURE_NAMES
from transmogrifai_tpu.evaluators.classification import \
    OpBinaryClassificationEvaluator
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.trees import (
    OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
from transmogrifai_tpu.obs.regress import POLICIES
from transmogrifai_tpu.ops import sweep as sweep_ops
from transmogrifai_tpu.parallel.mesh import make_mesh
from transmogrifai_tpu.parallel.spec_partition import (launch_packs,
                                                       partition_spec,
                                                       set_cost_provider)
from transmogrifai_tpu.utils import flops

KNOBS = ("TMOG_SWEEP_PACK", "TMOG_GBT_PIPELINE",
         "TMOG_PACK_HBM_MB", "TMOG_PACK_COST_BUDGET")


def _clear():
    """Fresh compile state + stats: flag flips must re-lower everything."""
    sweep_ops._aot_cache.clear()
    jax.clear_caches()
    sweep_ops.reset_run_stats()


@pytest.fixture(scope="module", autouse=True)
def knobs_off_baseline_env():
    """This module's baselines are knobs-OFF even when the CI matrix arms
    the knobs suite-wide (tier1 tmog_pack entry); per-test monkeypatch
    re-arms them on top."""
    mp = pytest.MonkeyPatch()
    for k in KNOBS:
        mp.delenv(k, raising=False)
    yield
    mp.undo()
    _clear()


def _candidates():
    """4 LR + 2 RF + 2 XGB: every fragment family the packers must handle,
    small enough that each cold configuration compiles in seconds."""
    return [
        (OpLogisticRegression(max_iter=30),
         [{"reg_param": 0.01}, {"reg_param": 0.1},
          {"reg_param": 0.2}, {"reg_param": 0.001}]),
        (OpRandomForestClassifier(),
         [{"num_trees": 6, "max_depth": 4}, {"num_trees": 6, "max_depth": 3}]),
        (OpXGBoostClassifier(),
         [{"num_round": 8, "max_depth": 3, "eta": 0.3},
          {"num_round": 8, "max_depth": 2, "eta": 0.3}]),
    ]


@pytest.fixture(scope="module")
def small_plan():
    rng = np.random.default_rng(0)
    n, d, F = 200, 8, 3
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    y = (X @ rng.normal(size=d) + 0.3 * rng.normal(size=n) > 0
         ).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=7, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    plan = build_sweep_plan(_candidates(), X, y, train_w, ev)
    assert plan is not None and len(plan.spec[2]) == 8
    return plan, train_w, val_mask, F


@pytest.fixture(scope="module")
def base_partitioned(small_plan):
    """Knobs-off 8-device partitioned metrics + run stats (the parity and
    back-compat reference every knob-on run is judged against)."""
    plan, tw, vm, _ = small_plan
    devs = jax.devices()[:8]
    assert len(devs) == 8, "conftest forces 8 virtual devices"
    _clear()
    out = np.asarray(plan.run_sharded(tw, vm, devs))
    return out, sweep_ops.run_stats()


# ---------------------------------------------------------------------------
# launch_packs sizing (host-only)
# ---------------------------------------------------------------------------
def test_launch_packs_default_matches_partition(small_plan):
    plan, _, _, F = small_plan
    shards = partition_spec(plan.spec, plan.blob, 4, plan.n_rows,
                            plan.n_features, F)
    packs = launch_packs(plan.spec, plan.blob, 4, plan.n_rows,
                         plan.n_features, F)
    # default budgets: the packs ARE the LPT shards (same specs, same
    # candidate sets, positional slots made explicit)
    assert len(packs) == len(shards)
    for i, (p, s) in enumerate(zip(packs, shards)):
        assert p.cis == s.cis and p.spec == s.spec
        assert p.slot == (s.slot if s.slot is not None else i)


def test_launch_packs_hbm_budget_splits(small_plan):
    plan, tw, _, F = small_plan
    C = len(plan.spec[2])
    # budget of exactly one candidate's score block -> one pack per cand
    one_cand = float(plan.n_rows) * F * 4.0
    packs = launch_packs(plan.spec, plan.blob, 4, plan.n_rows,
                         plan.n_features, F, budget_bytes=one_cand)
    assert len(packs) == C
    assert all(p.n_candidates == 1 for p in packs)
    # every global candidate lands in exactly one pack, slots stay in range
    assert sorted(ci for p in packs for ci in p.cis) == list(range(C))
    assert all(p.slot is not None and 0 <= p.slot < 4 for p in packs)
    assert all(p.cost > 0.0 for p in packs)


def test_launch_packs_learned_cost_budget(small_plan):
    plan, _, _, F = small_plan
    prev = set_cost_provider(lambda u: 100.0)   # flat 100 units/candidate
    try:
        shards = partition_spec(plan.spec, plan.blob, 2, plan.n_rows,
                                plan.n_features, F)
        # per-queue predicted cost is 100 x n_candidates; a 150-unit wall
        # budget must split every multi-candidate queue
        packs = launch_packs(plan.spec, plan.blob, 2, plan.n_rows,
                             plan.n_features, F, cost_budget=150.0)
    finally:
        set_cost_provider(prev)
    assert len(packs) > len(shards)
    assert sorted(ci for p in packs for ci in p.cis) == \
        list(range(len(plan.spec[2])))
    by_slot = {p.slot for p in packs}
    assert by_slot <= {s.slot if s.slot is not None else i
                       for i, s in enumerate(shards)} | {0, 1}


def test_metric_pack_size(monkeypatch):
    monkeypatch.delenv("TMOG_SWEEP_PACK", raising=False)
    assert sweep_ops._metric_pack_size(28, 3, 1024) == 1   # knob off
    monkeypatch.setenv("TMOG_SWEEP_PACK", "1")
    assert sweep_ops._metric_pack_size(1, 3, 1024) == 1    # nothing to pack
    # default 2048 MB budget >> 28 x [3, 1024] transients: pack them all
    assert sweep_ops._metric_pack_size(28, 3, 1024) == 28
    # budget of exactly two transients -> P = 2; k scales the transient
    two = 2 * 3 * 1024 * 4 / 1e6
    monkeypatch.setenv("TMOG_PACK_HBM_MB", str(two))
    assert sweep_ops._metric_pack_size(28, 3, 1024) == 2
    assert sweep_ops._metric_pack_size(28, 3, 1024, k=2) == 1


# ---------------------------------------------------------------------------
# satellite wiring: cost-model features + perfgate policy
# ---------------------------------------------------------------------------
def test_feature_names_appended():
    # append-only contract: new launch-shape features extend the tail so
    # historical training rows (zero-filled) stay loadable
    assert FEATURE_NAMES[-4:] == ("pack_size", "pipeline_depth",
                                  "host_count", "host_index")


def test_perfgate_gates_sequential_launches():
    pol = POLICIES["selector_sweep_models_per_sec"]
    assert pol["gbt_sequential_launches"] == -1   # lower is better
    assert pol["warmup_compile_s"] == -1


# ---------------------------------------------------------------------------
# partitioned path: pack + pipeline parity and telemetry
# ---------------------------------------------------------------------------
def test_pack_partitioned_bit_exact(base_partitioned, small_plan,
                                    monkeypatch):
    base, base_stats = base_partitioned
    plan, tw, vm, _ = small_plan
    assert base_stats["sweep_pack_count"] == 0    # knob off: no packing
    monkeypatch.setenv("TMOG_SWEEP_PACK", "1")
    _clear()
    packed = np.asarray(plan.run_sharded(tw, vm, jax.devices()[:8]))
    np.testing.assert_array_equal(packed, base)   # byte-identical programs
    st = sweep_ops.run_stats()
    entry = st["launches"][-1]
    # telemetry honesty: every pack is one dispatched launch; 8 candidates
    # over 8 devices packs 1:1, so nothing is avoided — and says so
    assert st["sweep_pack_count"] == len(entry["per_shard"]) == 8
    assert st["launches_avoided"] == 0
    feats = [s["feat"] for s in entry["per_shard"] if s.get("feat")]
    assert feats and all(f["pack_size"] >= 1.0 for f in feats)
    assert all(f["pipeline_depth"] == 0.0 for f in feats)


def test_pack_hbm_split_telemetry_matches_flops(base_partitioned,
                                                small_plan, monkeypatch):
    """Tiny HBM budget: several packs per device queue, launch counts
    cross-checked against the FLOP ledger's per-program call counts."""
    base, _ = base_partitioned
    plan, tw, vm, F = small_plan
    monkeypatch.setenv("TMOG_SWEEP_PACK", "1")
    # two candidates' score blocks per launch
    monkeypatch.setenv("TMOG_PACK_HBM_MB",
                       str(2 * plan.n_rows * F * 4 / 1e6))
    _clear()
    flops.enable()
    flops.reset()
    try:
        packed = np.asarray(plan.run_sharded(tw, vm, jax.devices()[:2]))
        st = sweep_ops.run_stats()
        dispatched = sum(
            v["calls"] for k, v in flops.totals()["by_fn"].items()
            if k in ("sweep.run", "sweep.run_scores"))
    finally:
        flops.disable()
    np.testing.assert_array_equal(packed, base)
    assert st["sweep_pack_count"] > 2            # split past the 2 slots
    assert st["sweep_pack_count"] == dispatched  # ledger agrees
    assert st["launches_avoided"] == \
        len(plan.spec[2]) - st["sweep_pack_count"]
    assert st["launches_avoided"] >= 1


def test_pipeline_partitioned_parity_and_chain_eff(base_partitioned,
                                                   small_plan, monkeypatch):
    base, base_stats = base_partitioned
    plan, tw, vm, _ = small_plan
    levels = base_stats["gbt_chain_levels"]
    assert levels > 0
    # back-compat: knobs off, the sequential-launch headline IS the chain
    assert base_stats["gbt_sequential_launches"] == levels
    monkeypatch.setenv("TMOG_SWEEP_PACK", "1")
    monkeypatch.setenv("TMOG_GBT_PIPELINE", "1")
    _clear()
    devs = jax.devices()[:8]
    cold = np.asarray(plan.run_sharded(tw, vm, devs))
    np.testing.assert_array_equal(cold, base)    # overlap, same math
    # the overlap claim is asserted on the WARM run: AOT caches hot, every
    # shard's dispatch window starts near-simultaneously (a cold run's
    # chain shard can finish compiling after its neighbors already ran)
    sweep_ops.reset_run_stats()
    warm = np.asarray(plan.run_sharded(tw, vm, devs))
    np.testing.assert_array_equal(warm, base)
    st = sweep_ops.run_stats()
    entry = st["launches"][-1]
    assert entry.get("pipelined") is True and entry["pipeline_depth"] == 2
    eff = entry["gbt_chain_eff"]
    assert 0.0 <= eff["overlap_fraction"] <= 1.0
    # strictly fewer effective sequential levels, floored at levels/shards
    assert eff["levels"] < levels
    assert eff["levels"] >= -(-levels // len(entry["per_shard"]))
    assert st["gbt_sequential_launches"] == eff["levels"]
    assert entry["gbt_chain"]["levels"] == levels   # the raw chain stays
    feats = [s["feat"] for s in entry["per_shard"] if s.get("feat")]
    assert feats and all(f["pipeline_depth"] == 2.0 for f in feats)
    # the measured windows are internal scaffolding, not telemetry
    assert not any("_win" in s for s in entry["per_shard"])


# ---------------------------------------------------------------------------
# row-sharded path: packed metric map parity
# ---------------------------------------------------------------------------
def test_rowsharded_pack_bit_exact(small_plan, monkeypatch):
    plan, tw, vm, _ = small_plan
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 on CPU)")
    mesh = make_mesh(n_data=2, n_model=2)
    _clear()
    base = np.asarray(plan.run_rowsharded(tw, vm, mesh))
    monkeypatch.setenv("TMOG_SWEEP_PACK", "1")
    _clear()
    packed = np.asarray(plan.run_rowsharded(tw, vm, mesh))
    # lax.map over vmap-packed candidate groups: same per-candidate math,
    # same reduction order -> bit-exact
    np.testing.assert_array_equal(packed, base)
    st = sweep_ops.run_stats()
    entry = st["launches"][-1]
    mp = [s.get("metric_pack") for s in entry["per_shard"]]
    assert any(p and p > 1 for p in mp), mp   # some column actually packed
    assert st["sweep_pack_count"] >= 1
    assert st["launches_avoided"] >= 1        # P>1 map beats one-per-cand
    feats = [s["feat"] for s in entry["per_shard"] if s.get("feat")]
    assert feats and any(f["pack_size"] > 1.0 for f in feats)


# ---------------------------------------------------------------------------
# hedge integration: the deadline clock starts after the pipelined prologue
# ---------------------------------------------------------------------------
def test_hedge_clock_starts_after_pipelined_prologue(small_plan,
                                                     monkeypatch):
    """Cold pipelined dispatch with armed sub-second deadlines: the compile
    prologue takes many times the deadline, so a clock that started at
    worker entry (the pre-pipelining placement) would hedge every shard.
    Post-prologue, the measured dispatch windows sit far inside their
    deadlines -> zero hedges, parity intact."""
    from transmogrifai_tpu.resilience import health

    plan, tw, vm, _ = small_plan
    devs = jax.devices()[:8]
    monkeypatch.setenv("TMOG_HEDGE", "1")
    monkeypatch.setenv("TMOG_HEDGE_FLOOR_S", "0.5")
    monkeypatch.setenv("TMOG_HEDGE_FACTOR", "2.0")
    health.reset()
    try:
        _clear()
        clean = np.asarray(plan.run_sharded(tw, vm, devs))   # calibrates
        assert sweep_ops.run_stats()["hedges_fired"] == 0
        monkeypatch.setenv("TMOG_GBT_PIPELINE", "1")
        _clear()   # cold again: the compile prologue is the point
        piped = np.asarray(plan.run_sharded(tw, vm, devs))
        st = sweep_ops.run_stats()
    finally:
        health.reset()
    np.testing.assert_array_equal(piped, clean)
    assert st["launches"][-1].get("pipelined") is True
    assert st["hedges_fired"] == 0, \
        "prologue (compiles + handshake) must not count against deadlines"
