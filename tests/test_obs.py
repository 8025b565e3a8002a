"""Unified telemetry core: tracer, registry, record, and the legacy views.

Covers the observability acceptance contract:

- span tracer: a profiler annotation always (it lands in the .xplane.pb
  host plane with its attributes as stats), nothing recorded and under 3 us
  a span when the buffer is off; valid Chrome trace-event JSON with
  correctly nested ts/dur and id / parent / req when on;
- registry: thread-hammer with no lost increments (scopes and ServeMetrics),
  consistent snapshots under concurrency;
- ``obs.snapshot()`` superset of the four legacy surfaces, which keep their
  exact shapes;
- JSONL run records: schema-versioned, one self-contained row per call;
- Prometheus text exposition off the same snapshot;
- trace coverage of the instrumented hot paths (sweep launch + shards,
  stream chunks, serve batches, gbt chain markers).
"""
import json
import threading

import numpy as np
import pytest

from transmogrifai_tpu import obs
from transmogrifai_tpu.obs import registry as obs_registry
from transmogrifai_tpu.obs import trace


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled and an empty buffer."""
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
class TestTrace:
    def test_disabled_span_records_nothing_and_is_cheap(self):
        # the off path: the profiler's annotation alone — nothing reaches
        # the buffer, no stack is kept, and a whole enter/exit stays under
        # 3 us (best of several repeats, so a busy machine does not decide)
        import timeit

        assert not trace.enabled()
        with trace.span("a", x=1) as s:
            s.set(y=2)  # no-op surface parity with a buffered span
            assert trace.current() is None
        assert trace.events() == []

        def one():
            with trace.span("x", a=1, b=2):
                pass

        n = 20000
        per_span = min(timeit.repeat(one, number=n, repeat=7)) / n
        assert per_span < 3e-6, f"{per_span * 1e6:.2f} us per span when off"
        assert trace.events() == []

    def test_disabled_records_nothing(self, tmp_path):
        with trace.span("ghost"):
            pass
        trace.instant("ghost.i")
        trace.complete("ghost.c", trace.now(), trace.now())
        trace.enable(str(tmp_path / "t.json"))
        out = trace.export()
        trace.disable()
        assert json.load(open(out))["traceEvents"] == []

    def test_export_is_valid_chrome_trace(self, tmp_path):
        trace.enable(str(tmp_path / "trace.json"))
        with trace.span("outer", kind="test"):
            with trace.span("inner"):
                pass
            trace.instant("marker", n=3)
        out = trace.export()
        doc = json.load(open(out))
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        evs = {e["name"]: e for e in doc["traceEvents"]}
        assert set(evs) == {"outer", "inner", "marker"}
        for e in doc["traceEvents"]:
            assert e["cat"] == "tmog"
            assert isinstance(e["ts"], float) and e["ts"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert evs["outer"]["ph"] == "X" and evs["inner"]["ph"] == "X"
        assert evs["marker"]["ph"] == "i"
        assert evs["outer"]["args"]["kind"] == "test"
        # the tree's three fields ride under args; the format is unchanged
        assert set(evs["outer"]["args"]) == {"kind", "id", "parent", "req"}
        # same-thread nesting is ts/dur containment: inner inside outer
        o, i = evs["outer"], evs["inner"]
        assert o["tid"] == i["tid"]
        assert o["ts"] <= i["ts"]
        assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6

    def test_complete_span_and_midspan_attrs(self, tmp_path):
        trace.enable(str(tmp_path / "t.json"))
        t0 = trace.now()
        with trace.span("s") as sp:
            sp.set(bucket=8)
        trace.complete("xthread", t0, trace.now(), n=2)
        doc = json.load(open(trace.export()))
        evs = {e["name"]: e for e in doc["traceEvents"]}
        assert evs["s"]["args"]["bucket"] == 8
        assert evs["xthread"]["ph"] == "X"
        assert evs["xthread"]["args"] == {"n": 2}
        assert evs["xthread"]["dur"] >= 0

    def test_nested_spans_record_id_parent_req(self):
        trace.enable(path=None)
        with trace.request():
            with trace.span("root"):
                with trace.span("child"):
                    trace.instant("mark")
                with trace.span("sibling"):
                    pass
        with trace.span("outside"):
            pass
        evs = {e["name"]: e["args"] for e in trace.events()}
        ids = [evs[n]["id"] for n in ("root", "child", "sibling", "outside")]
        assert len(set(ids)) == 4 and all(isinstance(i, int) for i in ids)
        assert evs["root"]["parent"] is None
        assert evs["child"]["parent"] == evs["root"]["id"]
        assert evs["sibling"]["parent"] == evs["root"]["id"]
        assert evs["mark"]["parent"] == evs["child"]["id"]
        req = evs["root"]["req"]
        assert req is not None
        assert {evs[n]["req"] for n in ("child", "sibling", "mark")} == {req}
        # a span outside any request has none
        assert evs["outside"]["req"] is None and evs["outside"]["parent"] is None

    def test_request_outermost_wins_and_next_request_differs(self):
        trace.enable(path=None)
        with trace.request():
            with trace.span("a"):
                with trace.request():  # nested: inherits, opens nothing
                    with trace.span("b"):
                        pass
        with trace.request():
            with trace.span("c"):
                pass
        evs = {e["name"]: e["args"] for e in trace.events()}
        assert evs["a"]["req"] == evs["b"]["req"]
        assert evs["b"]["parent"] == evs["a"]["id"]
        assert evs["c"]["req"] not in (None, evs["a"]["req"])

    def test_cross_thread_span_gets_submitters_parent(self):
        from concurrent.futures import ThreadPoolExecutor

        trace.enable(path=None)

        def work(k):
            with trace.span("shard", k=k):
                pass
            return threading.get_ident()

        with trace.request():
            with trace.span("launch"):
                with ThreadPoolExecutor(max_workers=2) as pool:
                    tids = list(pool.map(trace.bind(work), range(2)))
                handle = trace.current()
                t = threading.Thread(target=lambda: _attached(handle))

                def _attached(h):
                    with trace.attach(h):
                        with trace.span("hedge"):
                            pass

                t.start()
                t.join(timeout=10)
                assert not t.is_alive()
                # an unbound worker has no link to the launch
                with ThreadPoolExecutor(max_workers=1) as pool:
                    pool.submit(work, 9).result(timeout=10)
        evs = trace.events()
        launch = next(e for e in evs if e["name"] == "launch")
        shards = [e for e in evs if e["name"] == "shard"]
        bound = [e for e in shards if e["args"]["k"] in (0, 1)]
        assert len(bound) == 2
        assert all(e["tid"] != launch["tid"] for e in bound)
        assert set(tids) == {e["tid"] for e in bound}
        for e in bound + [next(e for e in evs if e["name"] == "hedge")]:
            assert e["args"]["parent"] == launch["args"]["id"]
            assert e["args"]["req"] == launch["args"]["req"]
        loose = next(e for e in shards if e["args"]["k"] == 9)
        assert loose["args"]["parent"] is None and loose["args"]["req"] is None

    def test_span_lands_in_profiler_trace_with_stats(self, tmp_path):
        # the second sink: with a jax.profiler session active the span is in
        # the .xplane.pb host plane under its name, attributes as stats —
        # whether or not the tracer's own buffer is on
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace.span("selector.fit", rows=7, width=3):
                with trace.span("selector.gather", bytes=123, handoff=False) as sp:
                    sp.set(late=1)  # fixed at entry: never reaches the stats
        finally:
            jax.profiler.stop_trace()
        assert trace.events() == []  # buffer off: the profiler alone saw them
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        found = {}
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("selector."):
                        found[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
        assert set(found) == {"selector.fit", "selector.gather"}
        assert found["selector.fit"][2] == {"rows": 7, "width": 3}
        assert found["selector.gather"][2] == {"bytes": 123, "handoff": 0}
        # one clock: the child lies inside the parent
        assert found["selector.fit"][0] <= found["selector.gather"][0]
        assert found["selector.gather"][1] <= found["selector.fit"][1]

    def test_listener_stage_wall_is_its_span(self):
        import time

        from transmogrifai_tpu.utils.listener import OpListener

        class Stage:
            operation_name = "modelSelector"
            uid = "u1"

        trace.enable(path=None)
        lst = OpListener()
        with lst.time_stage(Stage(), "fit", n_rows=11):
            time.sleep(0.01)
        (ev,) = [e for e in trace.events() if e["name"] == "stage.fit"]
        assert ev["args"]["stage"] == "modelSelector"
        assert ev["args"]["rows"] == 11
        (m,) = lst.metrics.stage_metrics
        # one measurement, not two clocks: equal to the float
        assert m.duration_ms == pytest.approx(ev["dur"] / 1e3, rel=1e-9)
        assert m.duration_ms >= 10.0
        # and with the buffer off the wall is still taken
        trace.disable()
        with lst.time_stage(Stage(), "transform"):
            time.sleep(0.002)
        assert lst.metrics.stage_metrics[-1].duration_ms >= 2.0
        assert len([e for e in trace.events()
                    if e["name"].startswith("stage.")]) == 1

    def test_ring_buffer_bounds_memory(self, tmp_path):
        trace.enable(str(tmp_path / "t.json"), buf_events=16)
        for k in range(50):
            trace.instant(f"e{k}")
        doc = json.load(open(trace.export()))
        names = [e["name"] for e in doc["traceEvents"]]
        assert len(names) == 16
        assert names == [f"e{k}" for k in range(34, 50)]  # oldest dropped


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_scope_concurrent_increments_none_lost(self):
        sc = obs_registry.Scope("hammer", {"n": 0, "events": []})
        N_THREADS, N_ITER = 8, 500

        def work(t):
            for i in range(N_ITER):
                sc.inc("n")
                sc.inc("wall", 0.001)
                if i % 50 == 0:
                    sc.append("events", {"t": t, "i": i})
                    snap = sc.snapshot()  # consistent mid-hammer reads
                    assert snap["n"] >= 0

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = sc.snapshot()
        assert snap["n"] == N_THREADS * N_ITER
        assert abs(snap["wall"] - N_THREADS * N_ITER * 0.001) < 1e-6
        assert len(snap["events"]) == N_THREADS * (N_ITER // 50)

    def test_serve_metrics_concurrent_none_lost(self):
        from transmogrifai_tpu.serve.metrics import ServeMetrics

        m = ServeMetrics()
        N_THREADS, N_ITER = 8, 300

        def work():
            for i in range(N_ITER):
                m.inc("requests")
                m.observe_request(1.0 + (i % 7))
                if i % 3 == 0:
                    m.observe_batch(2.0, 3, 4)
                if i % 25 == 0:
                    snap = m.snapshot()
                    assert snap["responses"] <= snap["requests"] * 2

        threads = [threading.Thread(target=work) for _ in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = m.snapshot()
        assert snap["requests"] == N_THREADS * N_ITER
        assert snap["responses"] == N_THREADS * N_ITER
        assert snap["request_latency"]["count"] == N_THREADS * N_ITER
        assert snap["batches"] == N_THREADS * len(range(0, N_ITER, 3))

    def test_scope_reset_recopies_defaults(self):
        sc = obs_registry.Scope("r", {"n": 0, "ev": []})
        sc.inc("n")
        sc.append("ev", {"a": 1})
        sc.reset()
        assert sc.get("n") == 0 and sc.list("ev") == []
        sc.append("ev", {"b": 2})
        sc.reset()
        assert sc.list("ev") == []  # defaults list not shared/mutated

    def test_list_returns_copies(self):
        sc = obs_registry.Scope("c", {"ev": []})
        sc.append("ev", {"a": 1})
        got = sc.list("ev")
        got[0]["a"] = 999
        got.append({"x": 0})
        assert sc.list("ev") == [{"a": 1}]

    def test_provider_and_collision_error_isolation(self):
        reg = obs_registry.Registry()
        reg.scope("s", {"n": 0}).inc("n", 5)
        reg.register_provider("p", lambda: {"v": 1})
        reg.register_provider("boom", lambda: 1 / 0)
        snap = reg.snapshot()
        assert snap["schema_version"] == obs_registry.SCHEMA_VERSION
        assert snap["s"]["n"] == 5
        assert snap["p"] == {"v": 1}
        assert "provider_error" in snap["boom"]

    def test_record_fallback_central_helper(self):
        reg = obs_registry.REGISTRY
        sc = reg.scope("fbtest")
        sc.reset()
        obs_registry.record_fallback("fbtest", "too_few_rows", rows=3, axis=2)
        assert sc.list("fallbacks") == [
            {"reason": "too_few_rows", "rows": 3, "axis": 2}]


# ---------------------------------------------------------------------------
# Legacy views stay intact; snapshot is their superset
# ---------------------------------------------------------------------------
class TestSnapshotSuperset:
    def test_snapshot_superset_of_legacy_surfaces(self):
        from transmogrifai_tpu.ops import sweep as sweep_ops
        from transmogrifai_tpu.serve.metrics import ServeMetrics
        from transmogrifai_tpu.utils import flops
        from transmogrifai_tpu.workflow import stream

        sweep_ops.reset_run_stats()
        stream.reset_stream_stats()
        sweep_ops.record_fallback("unit_test", rows=1)
        stream.record_fallback("unit_test_stream")
        m = ServeMetrics()
        m.inc("requests", 2)

        snap = obs.snapshot()
        # every key of every legacy accessor appears under its scope
        for key, val in sweep_ops.run_stats().items():
            assert snap["sweep"][key] == val
        for key, val in stream.stream_stats().items():
            assert snap["stream"][key] == val
        for key in flops.totals():
            assert key in snap["flops"]
        for key in m.snapshot():
            if key == "queue_depth":
                continue  # per-instance gauge, excluded from the merge
            assert key in snap["serve"], key
        # and the legacy accessors see what was recorded through obs
        assert sweep_ops.run_stats()["fallbacks"][-1]["reason"] == "unit_test"
        assert stream.stream_stats()["fallbacks"][-1]["reason"] == \
            "unit_test_stream"
        assert snap["serve"]["requests"] >= 2

    def test_sweep_launch_lands_in_registry(self):
        from transmogrifai_tpu.impl.selector import defaults as D
        from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
        from transmogrifai_tpu.evaluators.classification import (
            OpBinaryClassificationEvaluator)
        from transmogrifai_tpu.impl.classification.logistic import (
            OpLogisticRegression)
        from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation
        from transmogrifai_tpu.ops import sweep as sweep_ops

        rng = np.random.default_rng(0)
        X = np.ascontiguousarray(rng.normal(size=(120, 6)).astype(np.float32))
        y = (rng.random(120) < 0.5).astype(np.float32)
        ev = OpBinaryClassificationEvaluator()
        cv = OpCrossValidation(ev, num_folds=3, seed=0)
        train_w, val_mask = cv.make_folds(len(y), None)
        plan = build_sweep_plan(
            [(OpLogisticRegression(max_iter=10),
              D.logistic_regression_grid()[:2])],
            X, y, train_w, ev)
        assert plan is not None
        sweep_ops.reset_run_stats()
        plan.run(train_w, val_mask)
        snap = obs.snapshot()
        assert len(snap["sweep"]["launches"]) == 1
        assert snap["sweep"]["launches"][0]["candidates"] == 2


# ---------------------------------------------------------------------------
# Integration: instrumented hot paths produce spans
# ---------------------------------------------------------------------------
class TestTraceCoverage:
    def test_sweep_and_partition_spans(self, tmp_path):
        import jax

        from transmogrifai_tpu.evaluators.classification import (
            OpBinaryClassificationEvaluator)
        from transmogrifai_tpu.impl.classification.logistic import (
            OpLogisticRegression)
        from transmogrifai_tpu.impl.classification.trees import (
            OpXGBoostClassifier)
        from transmogrifai_tpu.impl.selector import defaults as D
        from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
        from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation

        rng = np.random.default_rng(1)
        X = np.ascontiguousarray(rng.normal(size=(96, 5)).astype(np.float32))
        y = (rng.random(96) < 0.5).astype(np.float32)
        ev = OpBinaryClassificationEvaluator()
        cv = OpCrossValidation(ev, num_folds=3, seed=0)
        train_w, val_mask = cv.make_folds(len(y), None)
        plan = build_sweep_plan(
            [(OpLogisticRegression(max_iter=10),
              D.logistic_regression_grid()[:2]),
             (OpXGBoostClassifier(), D.xgboost_grid()[:1])],
            X, y, train_w, ev)
        assert plan is not None
        trace.enable(str(tmp_path / "t.json"))
        plan.run(train_w, val_mask)
        plan.run_sharded(train_w, val_mask, jax.devices()[:2])
        doc = json.load(open(trace.export()))
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"sweep.launch", "sweep.partition", "sweep.shard",
                "sweep.upload", "sweep.dispatch", "sweep.gather",
                "gbt.chain"} <= names

    def test_stream_chunk_spans(self, tmp_path, monkeypatch):
        import transmogrifai_tpu.types as T
        from transmogrifai_tpu import Dataset
        from transmogrifai_tpu.columns import NumericColumn
        from transmogrifai_tpu.features.builder import FeatureBuilder
        from transmogrifai_tpu.impl.feature.transformers import (
            FillMissingWithMean)
        from transmogrifai_tpu.impl.feature.vectorizers import RealVectorizer
        from transmogrifai_tpu.workflow import stream

        monkeypatch.setenv("TMOG_TRANSFORM_CHUNK_ROWS", "32")
        n = 100
        rng = np.random.default_rng(2)
        cols, feats = {}, []
        for j in range(3):
            v = rng.normal(size=n)
            m = rng.random(n) > 0.1
            cols[f"x{j}"] = NumericColumn(T.Real, np.where(m, v, 0.0), m)
            feats.append(FeatureBuilder(f"x{j}", T.Real)
                         .extract(field=f"x{j}").as_predictor())
        ds = Dataset(cols)
        fm = FillMissingWithMean().set_input(feats[0]).fit(ds)
        vec = RealVectorizer().set_input(*feats).fit(ds)
        trace.enable(str(tmp_path / "t.json"))
        out = stream.apply_streamed(ds, [[fm, vec]])
        assert out is not None
        doc = json.load(open(trace.export()))
        names = [e["name"] for e in doc["traceEvents"]]
        assert "stream.execute" in names
        assert names.count("stream.chunk.upload") == 4  # ceil(100 / 32)
        assert names.count("stream.chunk.pull") == 4

    def test_overhead_when_disabled_is_free(self):
        # the span call itself must not allocate or format when off
        import timeit

        base = timeit.timeit(lambda: None, number=20000)
        spans = timeit.timeit(lambda: trace.span("x", a=1), number=20000)
        # generous bound: a no-op span is within ~20x of an empty lambda
        # (both sub-microsecond); catches accidental allocation/formatting
        assert spans < max(base * 20, 0.05)


# ---------------------------------------------------------------------------
# Launch ledger disabled path (same contract as the null span above)
# ---------------------------------------------------------------------------
class TestLedgerDisabled:
    def test_disabled_ledger_is_shared_singleton(self):
        from transmogrifai_tpu.obs import ledger

        ledger.disable()
        l1, l2 = ledger.get(), ledger.get()
        assert l1 is l2
        assert not l1.enabled
        assert l1.now() == 0.0
        assert l1.launch("k", wall_s=1.0, flops=1.0) is None
        assert l1.rows() == []
        assert ledger.rows() == []  # the live ledger saw nothing either

    def test_overhead_when_disabled_is_free(self):
        import timeit

        from transmogrifai_tpu.obs import ledger

        ledger.disable()
        base = timeit.timeit(lambda: None, number=20000)
        hooks = timeit.timeit(
            lambda: ledger.get().launch("x", wall_s=0.0, flops=0.0),
            number=20000)
        # one module-global boolean check + a no-op method: same generous
        # bound the null-span overhead test uses
        assert hooks < max(base * 20, 0.05)

    def test_enable_reflects_in_get_and_snapshot(self):
        from transmogrifai_tpu.obs import ledger

        try:
            ledger.enable()
            ledger.reset()
            lg = ledger.get()
            assert lg.enabled
            lg.launch("k", wall_s=0.5, flops=10.0, bytes=5.0)
            assert len(ledger.rows()) == 1
            snap = obs.snapshot()
            assert snap["ledger"]["enabled"]
            assert snap["ledger"]["n_rows"] == 1
        finally:
            from transmogrifai_tpu.utils import flops

            ledger.disable()
            ledger.reset()
            flops.disable()  # ledger.enable() turned accounting on
            flops.reset()


# ---------------------------------------------------------------------------
# JSONL run records
# ---------------------------------------------------------------------------
class TestRunRecord:
    def test_write_record_schema_and_roundtrip(self, tmp_path):
        out = tmp_path / "telemetry.jsonl"
        p1 = obs.write_record("unit", extra={"k": 1}, path=str(out))
        p2 = obs.write_record("unit2", path=str(out))
        assert p1 == p2 == str(out)
        rows = [json.loads(line) for line in out.read_text().splitlines()]
        assert len(rows) == 2
        for row in rows:
            assert row["schema"] == "tmog.run_record"
            assert row["schema_version"] == obs.SCHEMA_VERSION
            assert row["snapshot"]["schema_version"] == obs.SCHEMA_VERSION
            assert {"sweep", "stream", "flops", "serve"} <= \
                set(row["snapshot"])
            assert "argv" in row["context"] and "pid" in row["context"]
        assert rows[0]["kind"] == "unit" and rows[0]["k"] == 1
        assert rows[1]["kind"] == "unit2"

    def test_telemetry_path_precedence(self, tmp_path, monkeypatch):
        from transmogrifai_tpu.obs import record

        monkeypatch.delenv("TMOG_TELEMETRY", raising=False)
        assert record.telemetry_path() == "telemetry.jsonl"
        monkeypatch.setenv("TMOG_TELEMETRY", str(tmp_path / "env.jsonl"))
        assert record.telemetry_path() == str(tmp_path / "env.jsonl")
        assert record.telemetry_path("explicit.jsonl") == "explicit.jsonl"

    def test_numpy_values_degrade_to_json(self, tmp_path):
        out = tmp_path / "t.jsonl"
        obs.write_record("np", extra={
            "arr": np.arange(3), "scalar": np.float32(1.5)}, path=str(out))
        row = json.loads(out.read_text())
        assert row["arr"] == [0, 1, 2]
        assert row["scalar"] == 1.5


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------
class TestPrometheus:
    def test_flattening_rules(self):
        txt = obs.prometheus_text({
            "schema_version": 1,
            "sweep": {"launches": [{"a": 1}], "compile_s": 0.25,
                      "nested": {"deep": 2}, "flag": True,
                      "bad name": 3, "skipme": float("nan")},
        })
        lines = set(txt.strip().splitlines())
        assert "tmog_schema_version 1" in lines
        assert "tmog_sweep_launches_total 1" in lines  # lists -> length
        assert "tmog_sweep_compile_s 0.25" in lines
        assert "tmog_sweep_nested_deep 2" in lines
        assert "tmog_sweep_flag 1" in lines            # bools -> int
        assert "tmog_sweep_bad_name 3" in lines        # sanitized names
        assert not any("skipme" in ln for ln in lines)  # non-finite dropped

    def test_serve_metrics_endpoint_format(self):
        # the text the server's ?format=prometheus branch produces
        txt = obs.prometheus_text(obs.snapshot())
        assert txt.endswith("\n")
        for ln in txt.strip().splitlines():
            name, _, value = ln.partition(" ")
            assert name.startswith("tmog_")
            float(value)  # every exposed value parses as a number
