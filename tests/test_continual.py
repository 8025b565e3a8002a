"""Continual-learning subsystem: golden drift JS values, controller policy
(hysteresis / cooldown / evidence floors), warm-start grid pruning parity,
the champion-challenger promotion gate, post-swap rollback, and the full
closed loop (drift -> warm retrain -> gate -> rolling swap -> rollback)."""
import json
import math

import numpy as np
import pytest

import transmogrifai_tpu.types as T
from transmogrifai_tpu import OpWorkflow
from transmogrifai_tpu.continual import (ContinualLoop, ControllerConfig,
                                         GateConfig, RetrainController,
                                         ServeSketch, baselines_from_model,
                                         decide, incumbent_summary,
                                         merged_distributions,
                                         rollback_if_regressed, scope)
from transmogrifai_tpu.evaluators import Evaluators
from transmogrifai_tpu.impl.feature.vectorizers import (OneHotVectorizer,
                                                        RealVectorizer,
                                                        VectorsCombiner)
from transmogrifai_tpu.impl.filters.distribution import FeatureDistribution
from transmogrifai_tpu.impl.selector.factories import (
    BinaryClassificationModelSelector)
from transmogrifai_tpu.serve import MicroBatcher, ModelRegistry, ServeMetrics
from transmogrifai_tpu.testkit import TestFeatureBuilder

N = 96


def _era(n, shift):
    """One era's (x, cat, y): the label flips at the era's own center, so a
    model fit on era A is genuinely wrong about era B."""
    xs = list(np.linspace(-2.0, 2.0, n) + shift)
    cats = (["a", "b", "c", "d"] * ((n + 3) // 4))[:n]
    ys = [1.0 if x > shift else 0.0 for x in xs]
    return xs, cats, ys


def _build(n, shift):
    xs, cats, ys = _era(n, shift)
    return TestFeatureBuilder.of(("x", T.Real, xs), ("cat", T.PickList, cats),
                                 ("y", T.RealNN, ys), response="y")


def _workflow(ds, features, grid):
    x, cat, y = features
    feats = VectorsCombiner().set_input(
        RealVectorizer().set_input(x).get_output(),
        OneHotVectorizer(top_k=5, min_support=1).set_input(cat).get_output(),
    ).get_output()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        num_folds=2, splitter=None, models_and_parameters=grid)
    pred = sel.set_input(y, feats).get_output()
    return OpWorkflow().set_input_dataset(ds).set_result_features(pred)


@pytest.fixture(scope="module")
def grid(cut_binary_space):
    """The default binary space cut where pruning does not look: every
    family and every axis of its grid (LR 8, RF 12, XGB 2), with fewer
    trees and rounds and without the depth-12 forests."""
    return cut_binary_space(lr=8, rf_every=1, max_depth=6)


@pytest.fixture(scope="module")
def champion(grid):
    """(model, full_grid_size): one cold full-sweep champion on era A,
    shared by the pruning / rollback / closed-loop tests."""
    ds, feats = _build(N, 0.0)
    wf = _workflow(ds, feats, grid)
    sel = next(s for s in wf.stages if getattr(s, "is_model_selector", False))
    full = sum(len(g) for _, g in sel.models)
    return wf.train(), full


# ---------------------------------------------------------------------------
# drift: golden JS values on hand-made distributions
# ---------------------------------------------------------------------------
def _baseline_x(counts):
    """Numeric training baseline over edges [0,1,2,3,4] (4 bins + the
    trailing invalid bucket; len(dist) == len(edges) marks it numeric)."""
    dist = np.asarray(counts, float)
    return FeatureDistribution("x", None, int(dist.sum()), 0, dist,
                               np.asarray([0.0, 1.0, 2.0, 3.0, 4.0]),
                               "training")


def test_drift_js_golden():
    # training uniform over 4 bins; serving concentrated in bin 0.
    sketch = ServeSketch({("x", None): _baseline_x([10, 10, 10, 10, 0])})
    sketch.observe([{"x": 0.5}] * 40)
    row = sketch.scores()["x"]
    # Analytic JS(p, q) in bits for p = [1/4]*4, q = [1, 0, 0, 0]:
    # m = [5/8, 1/8, 1/8, 1/8]
    # KL(p||m) = 1/4*log2(2/5) + 3/4*log2(2);  KL(q||m) = log2(8/5)
    expected = 0.5 * (0.25 * math.log2(0.4) + 0.75) + 0.5 * math.log2(1.6)
    assert row["js"] == pytest.approx(expected, abs=1e-9)
    assert row["count"] == 40.0
    assert row["fill_rate"] == 1.0
    assert row["fill_rate_diff"] == pytest.approx(0.0)


def test_drift_js_zero_when_distributions_match():
    sketch = ServeSketch({("x", None): _baseline_x([10, 10, 10, 10, 0])})
    sketch.observe([{"x": v} for v in (0.5, 1.5, 2.5, 3.5)
                    for _ in range(10)])
    assert sketch.scores()["x"]["js"] == pytest.approx(0.0, abs=1e-12)


def test_drift_out_of_range_and_nulls():
    sketch = ServeSketch({("x", None): _baseline_x([10, 10, 10, 10, 0])})
    sketch.observe([{"x": 99.0}] * 10 + [{}] * 10)
    d = sketch.distributions()[("x", None)]
    assert d.distribution[-1] == 10.0  # outside training range -> invalid bin
    assert d.nulls == 10
    row = sketch.scores()["x"]
    assert row["fill_rate"] == pytest.approx(0.5)
    assert row["fill_rate_diff"] == pytest.approx(0.5)
    assert row["js"] > 0.5  # invalid-bucket mass registers as drift


def test_drift_sketch_merge_is_the_reduce_monoid():
    base = _baseline_x([10, 10, 10, 10, 0])
    a = ServeSketch({("x", None): base})
    b = ServeSketch({("x", None): base})
    a.observe([{"x": 0.5}] * 20)
    b.observe([{"x": 1.5}] * 20)
    both = ServeSketch({("x", None): base})
    both.observe([{"x": 0.5}] * 20 + [{"x": 1.5}] * 20)
    merged = merged_distributions([a, b])[("x", None)]
    want = both.distributions()[("x", None)]
    assert merged.count == want.count == 40
    np.testing.assert_allclose(merged.distribution, want.distribution)
    assert base.js_divergence(merged) == pytest.approx(
        base.js_divergence(want))


def test_prediction_sketch_reports_without_baseline():
    sketch = ServeSketch({})  # no feature baselines at all
    sketch.observe([{"x": 1.0}] * 4,
                   outputs=[{"p": {"prediction": 0.9}}] * 3 + [RuntimeError()])
    scores = sketch.scores()
    row = scores["__prediction__"]
    assert row["count"] == 3.0  # exceptions skipped, no js without baseline
    assert "js" not in row


# ---------------------------------------------------------------------------
# controller policy: hysteresis, cooldown, evidence floors
# ---------------------------------------------------------------------------
def _scores(js=0.5, count=100.0, fill_diff=0.0):
    return {"x": {"count": count, "fill_rate": 1.0, "js": js,
                  "fill_rate_diff": fill_diff}}


def test_controller_hysteresis_then_cooldown():
    now = [0.0]
    ctl = RetrainController(
        ControllerConfig(threshold=0.3, hysteresis=2, cooldown_s=100.0,
                         min_count=10), clock=lambda: now[0])
    d1 = ctl.evaluate(_scores())
    assert (d1.action, d1.reason) == ("skip", "hysteresis")
    d2 = ctl.evaluate(_scores())
    assert d2.triggered and d2.reason == "drift"
    assert d2.breached == {"x": 0.5}
    now[0] = 50.0  # still inside the cooldown window: breaches suppressed
    assert ctl.evaluate(_scores()).reason == "cooldown"
    assert ctl.evaluate(_scores()).reason == "cooldown"
    now[0] = 151.0  # past cooldown, streak already >= hysteresis
    assert ctl.evaluate(_scores()).triggered


def test_controller_no_drift_resets_the_streak():
    ctl = RetrainController(
        ControllerConfig(threshold=0.3, hysteresis=2, cooldown_s=0.0,
                         min_count=10), clock=lambda: 0.0)
    assert ctl.evaluate(_scores()).reason == "hysteresis"
    assert ctl.evaluate(_scores(js=0.1)).reason == "no_drift"
    assert ctl.evaluate(_scores()).reason == "hysteresis"  # streak restarted


def test_controller_evidence_floor_and_per_feature_threshold():
    ctl = RetrainController(
        ControllerConfig(threshold=0.3, hysteresis=1, cooldown_s=0.0,
                         min_count=64, per_feature={"x": 0.9}),
        clock=lambda: 0.0)
    # a 10-record burst is noise, not drift
    assert ctl.evaluate(_scores(js=0.99, count=10.0)).reason == "no_drift"
    # per-feature override raises x's bar above the global threshold
    assert ctl.evaluate(_scores(js=0.5)).reason == "no_drift"
    assert ctl.evaluate(_scores(js=0.95)).triggered


def test_controller_fill_rate_breach_path():
    ctl = RetrainController(
        ControllerConfig(threshold=0.3, fill_rate_diff=0.5, hysteresis=1,
                         cooldown_s=0.0, min_count=10), clock=lambda: 0.0)
    # js absent (e.g. text feature without matching bins): fill delta gates
    d = ctl.evaluate({"x": {"count": 100.0, "fill_rate": 0.4,
                            "fill_rate_diff": 0.6}})
    assert d.triggered and d.breached["x"] == pytest.approx(0.6)


# ---------------------------------------------------------------------------
# promotion gate
# ---------------------------------------------------------------------------
def test_gate_decide_both_directions():
    cfg = GateConfig(epsilon=0.01)
    assert decide(0.80, 0.795, True, "auPR", cfg).promote  # within epsilon
    worse = decide(0.80, 0.70, True, "auPR", cfg)
    assert not worse.promote and worse.reason == "challenger_worse"
    assert decide(0.20, 0.205, False, "rmse", cfg).promote  # smaller-better
    assert not decide(0.20, 0.40, False, "rmse", cfg).promote


def test_gate_counts_land_in_the_continual_scope():
    before = scope.snapshot()
    decide(1.0, 1.0, True, "auPR", GateConfig())
    decide(1.0, 0.0, True, "auPR", GateConfig())
    after = scope.snapshot()
    assert after["promotions"] == before["promotions"] + 1
    assert after["rejections"] == before["rejections"] + 1


# ---------------------------------------------------------------------------
# warm-start pruning parity
# ---------------------------------------------------------------------------
def test_warm_start_pruning_parity(champion, grid):
    model, full = champion
    summary = incumbent_summary(model)
    assert summary is not None and summary.best_model_type
    ds, feats = _build(N, 0.0)
    wf = _workflow(ds, feats, grid)
    sel = next(s for s in wf.stages if getattr(s, "is_model_selector", False))
    sel.warm_start(summary, explore=1)
    pruned, full2 = sel.validator.warm_start_counts
    assert full2 == full
    assert pruned < full / 2  # the warm grid is a fraction of the cold sweep
    # the incumbent's winning spec survives pruning...
    kept = next(g for est, g in sel.models
                if type(est).__name__ == summary.best_model_type)
    assert any(all(grid.get(k) == v for k, v in summary.best_grid.items())
               for grid in kept)
    # ...and the pruned sweep on the SAME data re-elects the same family
    challenger = wf.train()
    assert incumbent_summary(challenger).best_model_type == \
        summary.best_model_type


# ---------------------------------------------------------------------------
# rollback policy thresholds
# ---------------------------------------------------------------------------
def test_rollback_policy_thresholds(champion):
    model, _ = champion
    registry = ModelRegistry(max_batch=16)
    registry.deploy(model, version="v1")
    cfg = GateConfig(rollback_error_rate=0.10, rollback_min_responses=8)
    zero = {"responses": 0, "errors": 0}
    # too little post-swap evidence either way
    assert rollback_if_regressed(registry, zero,
                                 {"responses": 3, "errors": 2},
                                 model, "v1", cfg) is None
    # healthy error rate: the promotion holds
    assert rollback_if_regressed(registry, zero,
                                 {"responses": 100, "errors": 1},
                                 model, "v1", cfg) is None
    # regression: champion redeployed under a fresh -rbN tag
    before_rb = scope.snapshot()["rollbacks"]
    entry = rollback_if_regressed(registry, zero,
                                  {"responses": 2, "errors": 10},
                                  model, "v1", cfg)
    assert entry is not None and entry.version.startswith("v1-rb")
    assert registry.active().version == entry.version
    assert scope.snapshot()["rollbacks"] == before_rb + 1


# ---------------------------------------------------------------------------
# the closed loop, end to end
# ---------------------------------------------------------------------------
def test_e2e_closed_loop(champion, grid, tmp_path, monkeypatch):
    model, full = champion
    tele = tmp_path / "telemetry.jsonl"
    monkeypatch.setenv("TMOG_TELEMETRY", str(tele))
    base_counts = scope.snapshot()

    metrics = ServeMetrics()
    registry = ModelRegistry(max_batch=16, metrics=metrics)
    registry.deploy(model, version="champion")
    metrics.attach_sketch(ServeSketch(baselines_from_model(model)))

    def capacity():
        return sum(1 for i in range(registry.n_replicas)
                   if registry.replica(i) is not None)

    # era-B traffic through the batcher fills the serve-path drift sketch
    shift = 3.0
    xs, cats, _ = _era(N, shift)
    batcher = MicroBatcher(registry, max_batch=16, metrics=metrics)
    batcher.start()
    for f in [batcher.submit({"x": float(x), "cat": c})
              for x, c in zip(xs, cats)]:
        f.result(60.0)
    samples = [capacity()]
    drift = metrics.snapshot()["drift"]
    assert drift["x"]["js"] >= 0.25  # the shifted era breaches the gauge

    ds_b, feats_b = _build(N, shift)
    loop = ContinualLoop(
        registry, metrics,
        workflow_factory=lambda ds: _workflow(ds, feats_b, grid),
        window_provider=lambda: ds_b,
        evaluator=Evaluators.BinaryClassification.auPR(),
        controller=RetrainController(ControllerConfig(
            threshold=0.25, hysteresis=1, cooldown_s=0.0, min_count=16)),
        gate=GateConfig(epsilon=0.05), holdout_fraction=0.25)
    out = loop.run_once(scores=drift, version="challenger")
    samples.append(capacity())

    assert out["outcome"] == "promote"
    assert registry.active().version == "challenger"
    retrain = out["retrain"]
    assert retrain["warm_start"] is True
    assert retrain["full_candidates"] == full
    assert retrain["pruned_candidates"] < full / 2
    assert out["gate"]["promote"] is True

    # sabotage the promoted challenger: every score path raises, post-swap
    # traffic regresses, and the watch rolls back to the champion
    entry = registry.active()

    def _boom(*a, **k):
        raise RuntimeError("injected post-swap regression")

    entry.batch = _boom
    entry.row = _boom
    for x, c in zip(xs, cats):
        try:
            batcher.submit({"x": float(x), "cat": c}).result(60.0)
        except Exception:
            pass
    rb = loop.check_rollback()
    samples.append(capacity())
    batcher.stop()
    assert rb is not None and rb.startswith("champion-rb")
    assert registry.active().version == rb
    assert min(samples) > 0  # rolling swaps: capacity never hit zero

    counts = scope.snapshot()
    for key in ("triggers", "retrains", "promotions", "rollbacks"):
        assert counts[key] >= base_counts[key] + 1, key

    # every loop iteration landed a schema-versioned JSONL run record
    rows = [json.loads(line) for line in tele.read_text().splitlines()]
    promo = next(r for r in rows if r["kind"] == "continual"
                 and r.get("outcome") == "promote")
    assert promo["retrain"]["pruned_candidates"] == \
        retrain["pruned_candidates"]
    assert promo["decision"]["action"] == "trigger"
    assert any(r["kind"] == "continual" and r.get("outcome") == "rollback"
               for r in rows)
