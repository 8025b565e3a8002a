"""Straggler defense: delay faults, hedged dispatch, device health.

Tentpole contract (resilience/hedge.py + resilience/health.py wired into
ops/sweep.py and parallel/spec_partition.py):

- ``delay`` fault rules are deterministic stragglers: they sleep at the
  hook site and let the call proceed, with the same prob/seed/after/fires
  bookkeeping as the other kinds;
- ``with_retry`` clamps its wall deadline to a hedged shard's remaining
  hedge budget, so a retrying loser cannot outlive the winner;
- the health tracker turns measured-vs-predicted shard walls into
  per-device slowdown EWMAs that weight (and past the evict ratio,
  filter) LPT partitioning — but can never evict ALL devices;
- ``run_hedged`` re-dispatches a deadline-blowing or failing attempt to
  an idle slot, first completion wins, losers are never returned;
- the integration bar: with an injected dispatch delay many times the
  shard wall pinned to 1 of 8 devices, the full 28-candidate partitioned
  sweep finishes well under the injected stall, returns metrics
  bit-identical to the no-fault run, merges exactly one result per
  shard, and reports ``hedges_fired`` / ``hedge_wasted_s``.
"""
import time

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
from transmogrifai_tpu.obs import registry as obs_registry
from transmogrifai_tpu.ops import sweep as sweep_ops
from transmogrifai_tpu.parallel.spec_partition import partition_spec
from transmogrifai_tpu.resilience import health, hedge, inject, retry
from transmogrifai_tpu.resilience.inject import parse_rules


# ---------------------------------------------------------------------------
# delay fault kind


def test_delay_rule_parsing():
    r, = parse_rules("sweep.dispatch#TFRT_CPU_0:delay:2.5:0.5:7:1:2")
    assert (r.site, r.key, r.kind) == ("sweep.dispatch", "TFRT_CPU_0",
                                       "delay")
    assert r.seconds == 2.5
    assert (r.prob, r.seed, r.after, r.fires) == (0.5, 7, 1, 2)
    # the tail is optional: bare seconds defaults to prob=1 always-on
    r, = parse_rules("stream.upload:delay:0.25")
    assert (r.seconds, r.prob, r.seed, r.after, r.fires) == \
        (0.25, 1.0, 0, 0, 0)


def test_delay_rule_rejects_bad_seconds():
    with pytest.raises(ValueError):
        parse_rules("sweep.dispatch:delay")          # missing seconds
    with pytest.raises(ValueError):
        parse_rules("sweep.dispatch:delay:0")        # non-positive
    with pytest.raises(ValueError):
        parse_rules("sweep.dispatch:delay:-1:1")


def test_delay_fires_deterministically():
    # after=1, fires=2: invocation 1 passes, 2 and 3 stall, 4 passes
    inject.configure("unit.site:delay:0.08:1:0:1:2")
    try:
        walls = []
        for _ in range(4):
            t0 = time.monotonic()
            inject.maybe_fail("unit.site")   # must proceed, never raise
            walls.append(time.monotonic() - t0)
        assert walls[0] < 0.05 and walls[3] < 0.05
        assert walls[1] >= 0.08 and walls[2] >= 0.08
        faults = obs_registry.scope("resilience").list("faults")
        mine = [f for f in faults if f.get("site") == "unit.site"]
        assert len(mine) == 2
        assert all(f["kind"] == "delay" and f["seconds"] == 0.08
                   for f in mine)
    finally:
        inject.configure("")


# ---------------------------------------------------------------------------
# retry deadline clamp


def test_retry_deadline_clamps_policy():
    calls = []

    def boom():
        calls.append(1)
        raise ConnectionError("transient")

    pol = retry.RetryPolicy(attempts=5, base_s=0.0, max_s=0.0,
                            deadline_s=60.0)
    # a zero remaining hedge budget means: one attempt, then give up
    with pytest.raises(ConnectionError):
        retry.with_retry("unit.clamp", boom, policy=pol, deadline_s=0.0)
    assert len(calls) == 1
    # without the clamp the policy budget applies
    calls.clear()
    with pytest.raises(ConnectionError):
        retry.with_retry("unit.clamp", boom, policy=pol)
    assert len(calls) == 5


# ---------------------------------------------------------------------------
# device health scoring


def test_health_slowdown_weights_and_deadband():
    tr = health.HealthTracker(alpha=0.5)
    # uniform walls: everyone healthy, weights stay on the unweighted path
    tr.observe_launch([("a", 1.0, 1.0), ("b", 1.0, 1.0), ("c", 1.0, 1.0)])
    assert tr.slowdown("a") == pytest.approx(1.0)
    assert tr.partition_weights(["a", "b", "c"]) == [1.0, 1.0, 1.0]
    # device b persistently 2x slow: weight == its slowdown EWMA; jitter
    # under the deadband never flips the partitioner off the exact path
    for _ in range(4):
        tr.observe_launch([("a", 1.0, 1.0), ("b", 1.0, 2.0),
                           ("c", 1.0, 1.0)])
    assert tr.slowdown("b") > health.WEIGHT_DEADBAND
    wa, wb, wc = tr.partition_weights(["a", "b", "c"])
    assert wa == 1.0 and wc == 1.0 and wb == pytest.approx(tr.slowdown("b"))
    assert tr.usable("b")   # slow, but under the evict ratio
    assert tr.predict_wall(2.0) == pytest.approx(2.0 * tr._spu)


def test_health_eviction_and_never_evict_all(monkeypatch):
    monkeypatch.setenv("TMOG_DEVICE_EVICT_RATIO", "4.0")
    tr = health.HealthTracker()
    devs = [f"d{i}" for i in range(8)]
    # one chip 10x slow in an otherwise healthy launch crosses the ratio
    tr.observe_launch([(d, 1.0, 10.0 if d == "d0" else 1.0) for d in devs])
    assert tr.slowdown("d0") > health.evict_ratio()
    kept, evicted = tr.filter_devices(devs)
    assert evicted == ["d0"] and len(kept) == 7
    # a wrong health signal must not be able to kill the sweep
    sick = health.HealthTracker()
    sick.observe_launch([("x", 1.0, 1.0), ("y", 1.0, 1.0)])
    sick.record_straggler("x", 1.0, 50.0)
    sick.record_straggler("y", 1.0, 50.0)
    kept, evicted = sick.filter_devices(["x", "y"])
    assert kept == ["x", "y"] and evicted == []


def test_health_breaker_evicts_failing_device():
    tr = health.HealthTracker()
    for _ in range(3):   # TMOG_CIRCUIT_THRESHOLD consecutive failures
        tr.record_error("bad", "InjectedFault()")
    assert not tr.usable("bad")
    kept, evicted = tr.filter_devices(["good", "bad"])
    assert kept == ["good"] and evicted == ["bad"]
    snap = tr.snapshot()
    assert snap["devices"]["bad"]["breaker"]["state"] != "closed"


def test_record_straggler_rates_against_global_rate():
    tr = health.HealthTracker()
    tr.observe_launch([("a", 1.0, 1.0), ("b", 1.0, 1.0)])  # spu == 1.0
    # first evidence about c is a hedged-out straggler: predicted 2s at
    # the global rate, measured 12s -> slowdown 6x, past the evict ratio
    tr.record_straggler("c", 2.0, 12.0)
    assert tr.slowdown("c") == pytest.approx(6.0)
    assert not tr.usable("c")


# ---------------------------------------------------------------------------
# weighted LPT partitioning


def _plan(models, n_candidates):
    rng = np.random.default_rng(0)
    n, d, F = 240, 12, 3
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    beta = rng.normal(size=d)
    y = (X @ beta + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=7, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    plan = build_sweep_plan(models, X, y, train_w, ev)
    assert plan is not None and len(plan.spec[2]) == n_candidates
    return plan, train_w, val_mask, F


@pytest.fixture(scope="module")
def default_plan():
    return _plan([
        (OpLogisticRegression(max_iter=50), D.logistic_regression_grid()),
        (OpRandomForestClassifier(), D.random_forest_grid()),
        (OpXGBoostClassifier(), D.xgboost_grid()),
    ], 28)


@pytest.fixture(scope="module")
def straggler_plan(cut_binary_space):
    """One candidate a shard on 8 devices, every family and every depth of
    the default grid among them (a forest of each depth, the depth-10
    boosted chain): what the hedge layer does with a stalled shard does not
    depend on how long a healthy one runs."""
    return _plan(cut_binary_space(), 8)


def test_weighted_partition_none_and_uniform_identical(default_plan):
    plan, _, _, F = default_plan
    base = partition_spec(plan.spec, plan.blob, 4, plan.n_rows,
                          plan.n_features, F)
    uni = partition_spec(plan.spec, plan.blob, 4, plan.n_rows,
                         plan.n_features, F, device_weights=[1.0] * 4)
    assert [s.cis for s in base] == [s.cis for s in uni]
    assert all(s.slot is None for s in base)
    assert all(s.slot is None for s in uni)   # uniform == unweighted path


def test_weighted_partition_shifts_load_off_slow_device(default_plan):
    plan, _, _, F = default_plan
    base = partition_spec(plan.spec, plan.blob, 4, plan.n_rows,
                          plan.n_features, F)
    skew = partition_spec(plan.spec, plan.blob, 4, plan.n_rows,
                          plan.n_features, F,
                          device_weights=[4.0, 1.0, 1.0, 1.0])
    # weighted shards carry their slot so empty shards can drop without
    # scrambling the shard -> device mapping
    slots = [s.slot for s in skew]
    assert slots == sorted(slots) and set(slots) <= {0, 1, 2, 3}
    # the 4x-slow slot must get strictly less predicted cost than any
    # healthy slot (or nothing at all), and every candidate still lands
    # exactly once
    loads = {s.slot: s.cost for s in skew}
    slow = loads.get(0, 0.0)
    assert slow < min(v for k, v in loads.items() if k != 0)
    assert slow < max(s.cost for s in base)
    assert sorted(ci for s in skew for ci in s.cis) == list(range(28))


# ---------------------------------------------------------------------------
# run_hedged coordinator


def test_run_hedged_deadline_triggers_hedge():
    wasted = []

    def attempt(task, slot, ctl):
        ctl.mark_dispatch()
        if ctl.attempt == 0:
            time.sleep(3.0)    # the straggler
            return ("slow", slot)
        return ("fast", slot)

    t0 = time.monotonic()
    winners, stats = hedge.run_hedged(
        1, 2, attempt, [0.25],
        on_waste=lambda t, s, w, r: wasted.append((t, s, round(w, 1))))
    dt = time.monotonic() - t0
    assert stats["hedges_fired"] == 1
    (out, slot, attempt_no, _wall), = winners
    assert out == ("fast", 1) and slot == 1 and attempt_no == 1
    assert dt < 2.0, "the winner must not wait for the straggler"
    deadline = time.monotonic() + 5.0
    while not wasted and time.monotonic() < deadline:
        time.sleep(0.05)     # the loser reports from its own thread
    assert wasted == [(0, 0, 3.0)]


def test_run_hedged_error_triggers_immediate_hedge():
    reasons = []

    def attempt(task, slot, ctl):
        ctl.mark_dispatch()
        if ctl.attempt == 0:
            raise ValueError("dead chip")
        return slot

    winners, stats = hedge.run_hedged(
        1, 2, attempt, [30.0],
        on_hedge=lambda t, s, a, reason: reasons.append(reason),
        slot_ok=lambda s: s != 0)   # production: the breaker marks it dead
    assert stats["hedges_fired"] == 1 and reasons == ["error"]
    (out, slot, attempt_no, _wall), = winners
    assert out == 1 and slot == 1 and attempt_no == 1


def test_run_hedged_reraises_when_all_attempts_fail():
    def attempt(task, slot, ctl):
        raise ValueError("boom")

    with pytest.raises(ValueError, match="boom"):
        hedge.run_hedged(1, 2, attempt, [0.1])


def test_run_hedged_clock_starts_at_dispatch():
    def attempt(task, slot, ctl):
        time.sleep(0.5)        # "compile": must not count against the
        ctl.mark_dispatch()    # 0.2 s deadline
        return slot

    winners, stats = hedge.run_hedged(1, 2, attempt, [0.2])
    assert stats["hedges_fired"] == 0
    assert winners[0][2] == 0   # the primary attempt won


def test_shard_deadline_floor_and_factor(monkeypatch):
    monkeypatch.setenv("TMOG_HEDGE", "1")   # conftest disarms suite-wide
    monkeypatch.setenv("TMOG_HEDGE_FLOOR_S", "2.0")
    monkeypatch.setenv("TMOG_HEDGE_FACTOR", "3.0")
    health.reset()
    try:
        # uncalibrated: no prediction means no deadline — an absolute
        # guess about an unknown machine would hedge healthy shards
        assert hedge.shard_deadline(5.0) is None
        # with a live calibration the factored prediction dominates...
        health.tracker().observe_launch([("a", 1.0, 4.0)])   # spu = 4
        assert hedge.shard_deadline(5.0) == pytest.approx(3.0 * 20.0)
        # ...and the floor clamps tiny predicted deadlines from below
        assert hedge.shard_deadline(0.01) == 2.0
        monkeypatch.setenv("TMOG_HEDGE", "0")
        assert hedge.shard_deadline(5.0) is None
    finally:
        health.reset()


# ---------------------------------------------------------------------------
# integration: a partitioned sweep, a shard a device, under an injected
# straggler


def test_partitioned_sweep_hedges_and_recovers(straggler_plan, monkeypatch):
    plan, train_w, val_mask, _F = straggler_plan
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must force 8 virtual CPU devices"
    devs = devs[:8]
    DELAY = 8.0

    def _clear_ratios():
        # keep the seconds-per-unit calibration but drop per-device
        # ratios, so every run below takes the identical unweighted
        # split (bit-equality and AOT-cache hits are meaningful)
        tr = health.tracker()
        with tr._lock:
            tr._ratio.clear()
            tr._seen.clear()

    monkeypatch.setenv("TMOG_HEDGE", "1")   # conftest disarms suite-wide
    health.reset()   # uncalibrated: the cold run arms no deadlines
    sweep_ops.reset_run_stats()
    m_clean = plan.run_sharded(train_w, val_mask, devs)
    assert sweep_ops.run_stats()["hedges_fired"] == 0, \
        "an uncalibrated cold run must never hedge"
    # second (cached) run on the kill-switch path: measures the steady-
    # state makespan for the recovery bound without the hedge layer in
    # the way (contended CI hosts can legitimately blow CI-scale
    # deadlines, which is waste, not a correctness failure)
    monkeypatch.setenv("TMOG_HEDGE", "0")
    _clear_ratios()
    sweep_ops.reset_run_stats()
    t0 = time.monotonic()
    plan.run_sharded(train_w, val_mask, devs)
    clean_dt = time.monotonic() - t0
    assert sweep_ops.run_stats()["hedges_fired"] == 0, \
        "TMOG_HEDGE=0 must fully disarm the hedge layer"

    try:
        # pin a deterministic stall, many times the shard wall, to chip
        # 0, with the floor/factor dropped to CI scale so the deadline
        # logic engages on second-long shards
        monkeypatch.setenv("TMOG_HEDGE", "1")
        monkeypatch.setenv("TMOG_HEDGE_FLOOR_S", "0.5")
        monkeypatch.setenv("TMOG_HEDGE_FACTOR", "2.0")
        _clear_ratios()
        inject.configure(f"sweep.dispatch#{devs[0]}:delay:{DELAY}:1")
        sweep_ops.reset_run_stats()
        t0 = time.monotonic()
        m_fault = plan.run_sharded(train_w, val_mask, devs)
        fault_dt = time.monotonic() - t0
    finally:
        inject.configure("")
        health.reset()

    # bit-identical recovery: the loser was discarded, never merged
    assert m_fault.shape == m_clean.shape
    assert np.array_equal(np.asarray(m_fault), np.asarray(m_clean))

    stats = sweep_ops.run_stats()
    assert stats["hedges_fired"] >= 1, "the stalled shard must hedge"
    launch = stats["launches"][-1]
    assert launch["hedges_fired"] >= 1
    # exactly one winning result per shard, full grid covered once
    assert len(launch["per_shard"]) == 8
    assert sum(s["candidates"] for s in launch["per_shard"]) == 8
    # recovery, asserted via EVENTS rather than wall-clock bounds (a
    # loaded CI host can stretch any wall arbitrarily without anything
    # being wrong): the deadline blow re-dispatched (hedges_fired above),
    # exactly one attempt per shard was merged (coverage above, metrics
    # bit-identical), and whichever attempt lost the race reports its
    # wall as hedge_wasted_s below.  Which attempt WINS is host luck —
    # under heavy oversubscription the re-dispatch can queue behind busy
    # cores and the stalled original finishes first; that is waste, not a
    # correctness failure — so no assert demands a hedged winner.  When
    # the takeover does win it must have run off the stalled chip.
    hedged = [s for s in launch["per_shard"] if s.get("hedged")]
    assert all(s["device"] != str(devs[0]) for s in hedged)
    # clean_dt / fault_dt stay measured above for the diagnosis trail
    assert clean_dt > 0.0 and fault_dt > 0.0

    # the hedge counters ride the obs registry into every JSONL record
    snap = obs_registry.snapshot()
    assert snap["sweep"]["hedges_fired"] >= 1
    # the loser reports its wasted wall from its own thread once its
    # injected stall elapses — bounded by DELAY, so poll for it
    deadline = time.monotonic() + DELAY + 10.0
    while (sweep_ops.run_stats()["hedge_wasted_s"] == 0.0
           and time.monotonic() < deadline):
        time.sleep(0.25)
    stats = sweep_ops.run_stats()
    assert stats["hedge_wasted_s"] > 0.0
    launch = stats["launches"][-1]
    assert any(ev.get("wasted") for ev in launch.get("hedges", []))
    sweep_ops.reset_run_stats()


def test_partitioned_sweep_evicts_sick_device(monkeypatch):
    rng = np.random.default_rng(3)
    n, d, F = 120, 6, 2
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    y = (X[:, 0] > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=1, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    plan = build_sweep_plan(
        [(OpLogisticRegression(max_iter=20),
          [{"reg_param": 0.01, "elastic_net_param": 0.1},
           {"reg_param": 0.1, "elastic_net_param": 0.5}])],
        X, y, train_w, ev)
    devs = jax.devices()[:8]
    m_ref = plan.run(train_w, val_mask)

    monkeypatch.setenv("TMOG_HEDGE", "1")   # conftest disarms suite-wide
    health.reset()
    try:
        tr = health.tracker()
        # one chip 10x slow in an otherwise healthy launch: past the ratio
        tr.observe_launch([(str(dv), 1.0, 10.0 if i == 0 else 1.0)
                           for i, dv in enumerate(devs)])
        assert not tr.usable(devs[0])
        sweep_ops.reset_run_stats()
        m = plan.run_sharded(train_w, val_mask, devs)
        assert np.max(np.abs(np.asarray(m) - np.asarray(m_ref))) <= 1e-6
        stats = sweep_ops.run_stats()
        # the sick chip never ran a shard; the eviction left an audit row
        launch = stats["launches"][-1]
        assert all(s["device"] != str(devs[0])
                   for s in launch["per_shard"])
        assert any(f.get("reason") == "device_evicted"
                   for f in stats["fallbacks"])
    finally:
        health.reset()
        sweep_ops.reset_run_stats()
