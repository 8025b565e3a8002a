"""Fault-tolerance layer: deterministic injection grammar, retry/backoff,
circuit breakers, content-keyed checkpoints, kill-and-resume (real SIGKILL
in a subprocess), stream chunk resume, sweep shard resume, self-healing
serve replicas, crash-safe model saves, and the continual loop's
iteration-failure backoff.

The contract under test is the ISSUE's acceptance bar: with ``TMOG_FAULTS``
and ``TMOG_CHECKPOINT_DIR`` unset every path is bit-identical to the
pre-resilience code; with them set, a preempted fit resumes bit-identically
redoing only unfinished work, and a crashed replica recovers without a
process restart.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from transmogrifai_tpu.obs import registry as obs_registry
from transmogrifai_tpu.resilience import (CheckpointStore, CircuitBreaker,
                                          InjectedFatal, InjectedFault,
                                          RetryPolicy, content_key, inject,
                                          maybe_fail, with_retry)
from transmogrifai_tpu.resilience.inject import parse_rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_scope = obs_registry.scope("resilience")


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends with no armed fault rules."""
    inject.clear_rules()
    yield
    inject.clear_rules()


# ---------------------------------------------------------------------------
# injection grammar
# ---------------------------------------------------------------------------
def test_parse_rules_full_grammar():
    rules = parse_rules("serve.score#1:fatal:0.5:7:2:3, stream.upload:error")
    assert len(rules) == 2
    r = rules[0]
    assert (r.site, r.key, r.kind) == ("serve.score", "1", "fatal")
    assert (r.prob, r.seed, r.after, r.fires) == (0.5, 7, 2, 3)
    d = rules[1]
    assert (d.site, d.key, d.kind) == ("stream.upload", None, "error")
    assert (d.prob, d.seed, d.after, d.fires) == (1.0, 0, 0, 0)


def test_parse_rules_rejects_bad_specs():
    with pytest.raises(ValueError):
        parse_rules("no-kind-at-all")
    with pytest.raises(ValueError):
        parse_rules("site:explode")


def test_unset_is_inert():
    """TMOG_FAULTS unset: one boolean test, no counters, no exceptions."""
    assert not inject.active()
    before = _scope.get("faults_injected")
    for _ in range(100):
        maybe_fail("sweep.compile")
        maybe_fail("serve.score", key=3)
    assert _scope.get("faults_injected") == before


def test_after_pins_the_fault_deterministically():
    inject.add_rule("unit.site:error:1:0:2")  # skip 2, fail from the 3rd on
    maybe_fail("unit.site")
    maybe_fail("unit.site")
    with pytest.raises(InjectedFault) as ei:
        maybe_fail("unit.site")
    assert ei.value.transient is True
    assert "invocation 3" in str(ei.value)


def test_fires_caps_injections():
    """error:1:0:0:1 — the canonical one-shot transient — fires exactly once."""
    inject.add_rule("unit.once:error:1:0:0:1")
    with pytest.raises(InjectedFault):
        maybe_fail("unit.once")
    for _ in range(5):
        maybe_fail("unit.once")  # spent: never fires again


def test_key_narrows_the_rule():
    inject.add_rule("unit.keyed#1:fatal")
    maybe_fail("unit.keyed", key=0)
    maybe_fail("unit.keyed", key=2)
    with pytest.raises(InjectedFatal) as ei:
        maybe_fail("unit.keyed", key=1)
    assert ei.value.transient is False


def test_seeded_probability_is_reproducible():
    a = parse_rules("s:error:0.4:123")[0]
    b = parse_rules("s:error:0.4:123")[0]
    seq_a = [a.rng.random() for _ in range(20)]
    seq_b = [b.rng.random() for _ in range(20)]
    assert seq_a == seq_b


# ---------------------------------------------------------------------------
# retry wrapper
# ---------------------------------------------------------------------------
def _fail_n_times(n, exc_factory):
    calls = [0]

    def fn():
        calls[0] += 1
        if calls[0] <= n:
            raise exc_factory()
        return "ok"

    return fn, calls


def test_retry_absorbs_transient_and_counts_recovery():
    fn, calls = _fail_n_times(2, lambda: ConnectionError("flaky"))
    before = {k: _scope.get(k) for k in ("retries", "recoveries")}
    pol = RetryPolicy(attempts=3, base_s=0.0, max_s=0.0)
    assert with_retry("unit.retry", fn, policy=pol) == "ok"
    assert calls[0] == 3
    assert _scope.get("retries") == before["retries"] + 2
    assert _scope.get("recoveries") == before["recoveries"] + 1


def test_retry_fatal_propagates_on_first_attempt():
    fn, calls = _fail_n_times(5, lambda: ValueError("shape bug"))
    with pytest.raises(ValueError):
        with_retry("unit.retry", fn, policy=RetryPolicy(attempts=5, base_s=0.0))
    assert calls[0] == 1  # never retried


def test_retry_exhaustion_gives_up():
    fn, calls = _fail_n_times(99, lambda: InjectedFault("always"))
    before = _scope.get("gave_up")
    with pytest.raises(InjectedFault):
        with_retry("unit.retry", fn, policy=RetryPolicy(attempts=3, base_s=0.0))
    assert calls[0] == 3
    assert _scope.get("gave_up") == before + 1


def test_transient_classification():
    from transmogrifai_tpu.resilience import is_transient

    assert is_transient(ConnectionError())
    assert is_transient(TimeoutError())
    assert not is_transient(ValueError())
    assert is_transient(InjectedFault("x"))
    assert not is_transient(InjectedFatal("x"))
    e = RuntimeError("tagged")
    e.transient = True
    assert is_transient(e)


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------
def test_circuit_open_halfopen_close_cycle():
    t = [0.0]
    brk = CircuitBreaker("unit", threshold=2, cooldown_s=5.0,
                         clock=lambda: t[0])
    assert brk.available
    assert not brk.record_failure("one")
    assert brk.record_failure("two")       # threshold -> OPEN
    assert brk.state == "open" and not brk.available
    assert not brk.probe_ready()           # cooldown not yet elapsed
    assert not brk.try_trial()
    t[0] = 6.0
    assert brk.probe_ready()
    assert brk.try_trial()                 # HALF_OPEN, one in-flight trial
    assert not brk.try_trial()             # second trial refused
    assert brk.record_success()            # trial ok -> CLOSED
    assert brk.available and brk.closes == 1
    assert brk.last_outage_s == pytest.approx(6.0)


def test_circuit_failed_trial_keeps_outage_clock():
    t = [0.0]
    brk = CircuitBreaker("unit", threshold=1, cooldown_s=1.0,
                         clock=lambda: t[0])
    brk.record_failure("down")
    t[0] = 2.0
    assert brk.try_trial()
    brk.record_failure("still down")       # re-opens, same outage
    assert brk.state == "open" and brk.opens == 1
    t[0] = 4.0
    assert brk.try_trial()
    brk.record_success()
    assert brk.last_outage_s == pytest.approx(4.0)  # from the FIRST open


# ---------------------------------------------------------------------------
# checkpoint store
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_and_corrupt_handling(tmp_path):
    st = CheckpointStore(str(tmp_path))
    arrays = {"m": np.arange(6, dtype=np.float32).reshape(2, 3)}
    path = st.save("unit", "k1", arrays, meta={"rounds": 4})
    assert path and os.path.exists(path)
    got, meta = st.load("unit", "k1")
    np.testing.assert_array_equal(got["m"], arrays["m"])
    assert meta == {"rounds": 4}
    assert st.load("unit", "absent") is None
    # a torn/corrupt file is counted, deleted, and treated as absent
    with open(path, "wb") as fh:
        fh.write(b"not an npz")
    before = _scope.get("checkpoint_corrupt")
    assert st.load("unit", "k1") is None
    assert _scope.get("checkpoint_corrupt") == before + 1
    assert not os.path.exists(path)


def test_checkpoint_disabled_without_dir():
    st = CheckpointStore("")
    assert not st.enabled
    assert st.save("unit", "k", {"a": np.zeros(1)}) is None
    assert st.load("unit", "k") is None


def test_content_key_tracks_values():
    a = np.arange(10, dtype=np.float32)
    b = a.copy()
    b[3] = -1.0
    assert content_key("unit", a) == content_key("unit", a.copy())
    assert content_key("unit", a) != content_key("unit", b)
    assert content_key("unit", a) != content_key("other", a)


# ---------------------------------------------------------------------------
# kill-and-resume: a real SIGKILL mid-fit, then a bit-identical resume
# ---------------------------------------------------------------------------
_GBT_CHILD = """
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from transmogrifai_tpu.ops import trees as Tr
from transmogrifai_tpu.resilience import checkpointed_gbt_fit
from transmogrifai_tpu.obs import registry as obs

rng = np.random.default_rng(3)
n, d, B, R = 96, 6, 16, 6
Xb = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
y = jnp.asarray(rng.normal(size=n), jnp.float32)
w = jnp.ones((n,), jnp.float32)
rw = jnp.asarray(rng.uniform(0.5, 1.5, (R, n)), jnp.float32)
fms = jnp.ones((R, d), jnp.float32)
trees, F = checkpointed_gbt_fit(
    Tr.fit_gbt, Xb, y, w, rw, fms, loss="squared", n_rounds=R,
    max_depth=3, n_bins=B, frontier=Tr.frontier_cap(n, 3), eta=0.3,
    trees_per_round=1)
leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(trees)]
np.savez(sys.argv[1], F=np.asarray(F),
         **{f"t{i}": a for i, a in enumerate(leaves)})
print(json.dumps({
    "skipped": obs.scope("resilience").get("gbt_rounds_skipped"),
    "saves": obs.scope("resilience").get("checkpoint_saves")}))
"""


def _run_gbt_child(out_npz, ckpt_dir, faults=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               TMOG_CHECKPOINT_DIR=str(ckpt_dir), TMOG_CHECKPOINT_ROUNDS="2",
               TMOG_FAULTS=faults)
    return subprocess.run([sys.executable, "-c", _GBT_CHILD, str(out_npz)],
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_gbt_kill_and_resume_bit_identical(tmp_path):
    """SIGKILL after the first checkpointed segment; the resumed fit redoes
    only the unfinished rounds and bit-matches an uninterrupted run."""
    dir_kill = tmp_path / "ck_kill"
    dir_clean = tmp_path / "ck_clean"
    # 1. the preemption: kill on the 2nd segment (after segment 1 is saved)
    r = _run_gbt_child(tmp_path / "dead.npz", dir_kill,
                       faults="trees.gbt_segment:kill:1:0:1")
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    assert list(dir_kill.glob("gbt-*.npz")), "segment 1 checkpoint must exist"
    # 2. resume in the same checkpoint dir: only rounds 3..6 are refit
    r2 = _run_gbt_child(tmp_path / "resumed.npz", dir_kill)
    assert r2.returncode == 0, r2.stderr[-2000:]
    stats = json.loads(r2.stdout.strip().splitlines()[-1])
    assert stats["skipped"] == 2, stats   # rounds 1-2 came from the checkpoint
    # 3. the uninterrupted reference (fresh dir, identical segmentation)
    r3 = _run_gbt_child(tmp_path / "reference.npz", dir_clean)
    assert r3.returncode == 0, r3.stderr[-2000:]
    assert json.loads(r3.stdout.strip().splitlines()[-1])["skipped"] == 0
    resumed = np.load(tmp_path / "resumed.npz")
    ref = np.load(tmp_path / "reference.npz")
    assert set(resumed.files) == set(ref.files)
    for k in ref.files:
        np.testing.assert_array_equal(resumed[k], ref[k], err_msg=k)


# ---------------------------------------------------------------------------
# sweep resume: second run skips the completed work, metrics identical
# ---------------------------------------------------------------------------
def _tiny_sweep_plan(cut_binary_space):
    from transmogrifai_tpu.evaluators.classification import \
        OpBinaryClassificationEvaluator
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation

    rng = np.random.default_rng(0)
    n, d, F = 240, 12, 3
    X = np.ascontiguousarray(rng.normal(size=(n, d)).astype(np.float32))
    beta = rng.normal(size=d)
    y = (X @ beta + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    ev = OpBinaryClassificationEvaluator()
    cv = OpCrossValidation(ev, num_folds=F, seed=7, mesh=None)
    train_w, val_mask = cv.make_folds(n, None)
    # one candidate of each family and depth of the default grid, fewer
    # trees and rounds: a checkpointed launch skips whole, whatever its size
    plan = build_sweep_plan(cut_binary_space(lr=2, xgb=1), X, y, train_w, ev)
    assert plan is not None and len(plan.spec[2]) == 6
    return plan, train_w, val_mask


def test_sweep_checkpoint_resume_identical_metrics(tmp_path, monkeypatch,
                                                   cut_binary_space):
    from transmogrifai_tpu.ops import sweep as sweep_ops

    monkeypatch.setenv("TMOG_CHECKPOINT_DIR", str(tmp_path))
    plan, train_w, val_mask = _tiny_sweep_plan(cut_binary_space)
    sweep_ops.reset_run_stats()
    m1 = np.asarray(plan.run(train_w, val_mask))
    st1 = sweep_ops.run_stats()
    assert st1["checkpoint_skips"] == 0
    sweep_ops.reset_run_stats()
    m2 = np.asarray(plan.run(train_w, val_mask))
    st2 = sweep_ops.run_stats()
    assert st2["checkpoint_skips"] >= 1, st2
    np.testing.assert_array_equal(m1, m2)
    # the resume shows up in the run record's "resume" block
    from transmogrifai_tpu.runner import _resume_stats

    resume = _resume_stats()
    assert resume is not None and resume["sweep_shard_skips"] >= 1


# ---------------------------------------------------------------------------
# streaming transforms: chunk checkpoints + transient upload faults
# ---------------------------------------------------------------------------
def _stream_setup():
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import Dataset, FeatureBuilder
    from transmogrifai_tpu.columns import NumericColumn
    from transmogrifai_tpu.impl.feature.transformers import FillMissingWithMean
    from transmogrifai_tpu.impl.feature.vectorizers import (
        RealVectorizer, StandardScalerVectorizer, VectorsCombiner)

    rng = np.random.default_rng(7)
    n = 237
    cols = {}
    for j in range(6):
        v = rng.normal(size=n)
        m = rng.random(n) > 0.1
        cols[f"x{j}"] = NumericColumn(T.Real, np.where(m, v, 0.0), m)
    cols["label"] = NumericColumn(T.RealNN, (rng.random(n) > 0.5).astype(float),
                                  np.ones(n, bool))
    ds = Dataset(cols)
    xs = [FeatureBuilder(f"x{j}", T.Real).extract(field=f"x{j}").as_predictor()
          for j in range(6)]
    fm = FillMissingWithMean().set_input(xs[0]).fit(ds)
    m1 = RealVectorizer().set_input(*xs[:3]).fit(ds)
    m2 = RealVectorizer(fill_with_mean=False,
                        fill_value=-1.0).set_input(*xs[3:]).fit(ds)
    comb = VectorsCombiner().set_input(m1.get_output(), m2.get_output())
    ref = ds
    for t in (fm, m1, m2, comb):
        ref = ref.with_column(t.get_output().name, t.transform_dataset(ref))
    sm = StandardScalerVectorizer().set_input(comb.get_output()).fit(ref)
    return ds, [[fm, m1, m2], [comb], [sm]]


def _assert_datasets_equal(a, b):
    assert set(a.columns) == set(b.columns)
    for nm in a.columns:
        np.testing.assert_array_equal(np.asarray(a[nm].values),
                                      np.asarray(b[nm].values), err_msg=nm)
        ma, mb = getattr(a[nm], "mask", None), getattr(b[nm], "mask", None)
        if ma is not None and mb is not None:
            np.testing.assert_array_equal(np.asarray(ma), np.asarray(mb))


def test_stream_chunk_checkpoint_resume(tmp_path, monkeypatch):
    from transmogrifai_tpu.workflow import stream

    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    ds, layers = _stream_setup()
    out0 = stream.apply_streamed(ds, layers)      # baseline, no checkpoints
    monkeypatch.setenv("TMOG_CHECKPOINT_DIR", str(tmp_path))
    stream.reset_stream_stats()
    out1 = stream.apply_streamed(ds, layers)
    s1 = stream.stream_stats()
    assert s1["chunks"] == 4 and s1["checkpoint_skips"] == 0, s1
    stream.reset_stream_stats()
    out2 = stream.apply_streamed(ds, layers)      # every chunk restored
    s2 = stream.stream_stats()
    assert s2["chunks"] == 0 and s2["checkpoint_skips"] == 4, s2
    _assert_datasets_equal(out1, out0)
    _assert_datasets_equal(out2, out0)


def test_stream_transient_upload_fault_recovers(monkeypatch):
    from transmogrifai_tpu.workflow import stream

    monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "64")
    monkeypatch.setenv("TMOG_RETRY_BASE_S", "0.001")
    ds, layers = _stream_setup()
    out0 = stream.apply_streamed(ds, layers)
    before = {k: _scope.get(k) for k in ("retries", "recoveries")}
    inject.add_rule("stream.upload#64:error:1:0:0:1")  # one-shot transient
    out1 = stream.apply_streamed(ds, layers)
    inject.clear_rules()
    assert _scope.get("retries") >= before["retries"] + 1
    assert _scope.get("recoveries") >= before["recoveries"] + 1
    _assert_datasets_equal(out1, out0)


# ---------------------------------------------------------------------------
# serve: replica crash -> circuit open -> supervisor rebuild -> recovery
# ---------------------------------------------------------------------------
def test_replica_crash_self_heals(monkeypatch):
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.impl.classification.logistic import \
        OpLogisticRegression
    from transmogrifai_tpu.impl.feature.vectorizers import (
        OneHotVectorizer, RealVectorizer, VectorsCombiner)
    from transmogrifai_tpu.serve import MicroBatcher, ModelRegistry, ServeMetrics
    from transmogrifai_tpu.testkit import TestFeatureBuilder

    monkeypatch.setenv("TMOG_CIRCUIT_THRESHOLD", "2")
    monkeypatch.setenv("TMOG_CIRCUIT_COOLDOWN_S", "0.3")
    monkeypatch.setenv("TMOG_SUPERVISOR_INTERVAL_S", "0.05")
    monkeypatch.setenv("TMOG_RETRY_BASE_S", "0.001")

    n = 80
    ds, (x, cat, y) = TestFeatureBuilder.of(
        ("x", T.Real, list(np.linspace(-2, 2, n))),
        ("cat", T.PickList, ["a", "b"] * (n // 2)),
        ("y", T.RealNN, [float(i % 2) for i in range(n)]), response="y")
    feats = VectorsCombiner().set_input(
        RealVectorizer().set_input(x).get_output(),
        OneHotVectorizer(top_k=3, min_support=1).set_input(cat).get_output(),
    ).get_output()
    pred = OpLogisticRegression(reg_param=0.1).set_input(y, feats).get_output()
    model = OpWorkflow().set_input_dataset(ds).set_result_features(pred).train()

    registry = ModelRegistry(max_batch=8, replicas=2)
    registry.deploy(model, version="v1")
    metrics = ServeMetrics()
    batcher = MicroBatcher(registry, max_batch=8, max_wait_ms=1.0,
                           metrics=metrics).start()
    try:
        rec = {"x": 0.5, "cat": "a"}
        base = batcher.score(rec)
        assert base is not None

        inject.add_rule("serve.score#0:fatal")  # permanent crash on slot 0
        during = [batcher.score(rec) for _ in range(40)]
        assert all(o == base for o in during), \
            "answers must survive the outage (served by the healthy slot)"
        states = [s["circuit"]["state"]
                  for s in batcher.supervisor.health()]
        assert "open" in states, states
        assert metrics.replica_failures >= 1

        inject.clear_rules()                    # heal the fault
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if all(s["healthy"] for s in batcher.supervisor.health()):
                break
            time.sleep(0.05)
        health = batcher.supervisor.health()
        assert all(s["circuit"]["state"] == "closed" for s in health), health
        assert metrics.replica_rebuilds >= 1
        assert batcher.supervisor.recoveries >= 1
        # full service restored: scoring still exact, no further degradation
        deg0 = metrics.degraded_batches
        for _ in range(20):
            assert batcher.score(rec) == base
        assert metrics.degraded_batches == deg0
        # /metrics surface: per-slot health rides on registry.info()
        info = registry.info()
        assert info["health"] is not None and len(info["health"]) == 2
        assert {h["slot"] for h in info["health"]} == {0, 1}
    finally:
        batcher.stop()


def test_all_slots_down_degrades_but_answers(monkeypatch):
    """Every replica crashed: the batcher sheds to the host row path
    (degraded_batches) instead of failing requests."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.impl.classification.logistic import \
        OpLogisticRegression
    from transmogrifai_tpu.impl.feature.vectorizers import (RealVectorizer,
                                                            VectorsCombiner)
    from transmogrifai_tpu.serve import MicroBatcher, ModelRegistry, ServeMetrics
    from transmogrifai_tpu.testkit import TestFeatureBuilder

    monkeypatch.setenv("TMOG_CIRCUIT_THRESHOLD", "1")
    monkeypatch.setenv("TMOG_CIRCUIT_COOLDOWN_S", "30")  # stays open
    monkeypatch.setenv("TMOG_RETRY_BASE_S", "0.001")

    n = 40
    ds, (x, y) = TestFeatureBuilder.of(
        ("x", T.Real, list(np.linspace(-2, 2, n))),
        ("y", T.RealNN, [float(i % 2) for i in range(n)]), response="y")
    feats = VectorsCombiner().set_input(
        RealVectorizer().set_input(x).get_output()).get_output()
    pred = OpLogisticRegression(reg_param=0.1).set_input(y, feats).get_output()
    model = OpWorkflow().set_input_dataset(ds).set_result_features(pred).train()

    registry = ModelRegistry(max_batch=8, replicas=2)
    registry.deploy(model, version="v1")
    metrics = ServeMetrics()
    batcher = MicroBatcher(registry, max_batch=8, max_wait_ms=1.0,
                           metrics=metrics).start()
    try:
        rec = {"x": 0.25}
        base = batcher.score(rec)
        inject.add_rule("serve.score:fatal")    # ALL slots
        outs = [batcher.score(rec) for _ in range(10)]
        assert all(o == base for o in outs)
        assert metrics.degraded_batches >= 1
    finally:
        batcher.stop()


# ---------------------------------------------------------------------------
# crash-safe model saves
# ---------------------------------------------------------------------------
def test_save_model_crash_safe_and_corrupt_errors(tmp_path):
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.impl.classification.logistic import \
        OpLogisticRegression
    from transmogrifai_tpu.impl.feature.vectorizers import (RealVectorizer,
                                                            VectorsCombiner)
    from transmogrifai_tpu.testkit import TestFeatureBuilder
    from transmogrifai_tpu.workflow.serialization import (MODEL_ARRAYS,
                                                          MODEL_MANIFEST,
                                                          load_model,
                                                          save_model)

    n = 40
    ds, (x, y) = TestFeatureBuilder.of(
        ("x", T.Real, list(np.linspace(-2, 2, n))),
        ("y", T.RealNN, [float(i % 2) for i in range(n)]), response="y")
    feats = VectorsCombiner().set_input(
        RealVectorizer().set_input(x).get_output()).get_output()
    pred = OpLogisticRegression(reg_param=0.1).set_input(y, feats).get_output()
    model = OpWorkflow().set_input_dataset(ds).set_result_features(pred).train()

    loc = tmp_path / "model"
    save_model(model, str(loc))
    assert load_model(str(loc)) is not None
    # no stray temp files survive an atomic save
    assert not list(loc.glob("*.tmp"))

    # interrupted save (no manifest) -> a clear, actionable error
    partial = tmp_path / "partial"
    os.makedirs(partial)
    np.savez_compressed(partial / MODEL_ARRAYS, a=np.zeros(1))
    with pytest.raises(FileNotFoundError, match="interrupted save"):
        load_model(str(partial))

    # a damaged manifest / arrays file names the broken file
    with open(loc / MODEL_MANIFEST, "a") as fh:
        fh.write("garbage{{{")
    with pytest.raises(ValueError, match="Corrupt model manifest"):
        load_model(str(loc))
    save_model(model, str(loc))  # repair
    with open(loc / MODEL_ARRAYS, "wb") as fh:
        fh.write(b"torn")
    with pytest.raises(ValueError, match="Corrupt model arrays"):
        load_model(str(loc))


# ---------------------------------------------------------------------------
# continual loop: a failed iteration backs off instead of dying
# ---------------------------------------------------------------------------
class _FakeWindow:
    def __init__(self, n=8):
        self.n = n

    def __len__(self):
        return self.n

    def take(self, idx):
        return _FakeWindow(len(idx))


class _FakeRegistry:
    def active(self):
        raise LookupError("no active model")


def test_continual_iteration_failure_backs_off(monkeypatch, tmp_path):
    from transmogrifai_tpu.continual.controller import (ControllerConfig,
                                                        RetrainController)
    from transmogrifai_tpu.continual.controller import scope as cont_scope
    from transmogrifai_tpu.continual.loop import ContinualLoop

    monkeypatch.setenv("TMOG_TELEMETRY", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("TMOG_CONTINUAL_BACKOFF_S", "10")
    clk = [100.0]
    controller = RetrainController(
        ControllerConfig(threshold=0.01, hysteresis=1, min_count=1,
                         cooldown_s=0.0), clock=lambda: clk[0])
    loop = ContinualLoop(
        _FakeRegistry(), metrics=None, workflow_factory=lambda ds: None,
        window_provider=_FakeWindow, evaluator=None, controller=controller,
        clock=lambda: clk[0])
    scores = {"x": {"js": 1.0, "count": 100.0}}
    fail0 = cont_scope.get("iteration_failures")
    skip0 = cont_scope.get("backoff_skips")

    inject.add_rule("continual.retrain:fatal")
    out1 = loop.run_once(scores)
    assert out1["outcome"] == "iteration_failed"
    assert "InjectedFatal" in out1["error"]
    assert out1["backoff_s"] == pytest.approx(10.0)
    assert cont_scope.get("iteration_failures") == fail0 + 1

    out2 = loop.run_once(scores)               # inside the backoff window
    assert out2["outcome"] == "backoff"
    assert out2["backoff_remaining_s"] > 0
    assert cont_scope.get("backoff_skips") == skip0 + 1

    clk[0] += 11.0                             # backoff expired: retry, and
    out3 = loop.run_once(scores)               # the wait doubles on failure
    assert out3["outcome"] == "iteration_failed"
    assert out3["backoff_s"] == pytest.approx(20.0)
    assert cont_scope.get("iteration_failures") == fail0 + 2
    failed = [d for d in cont_scope.get("decisions", [])
              if d.get("action") == "iteration_failed"]
    assert failed and failed[-1]["consecutive"] == 2
