"""Benchmark: the REAL ModelSelector default sweep (models trained / second).

The reference's hot path is the ModelSelector CV sweep — numFolds x models x
param-grids individual Spark fits throttled by an 8-thread JVM pool
(OpValidator.scala:299-357).  BASELINE.md sets the target: >=30x wall-clock
vs 32-core Spark-local on the full Titanic default sweep on TPU.

This benchmark times the framework's own code path end-to-end: Titanic
features through the framework's vectorizers, then
``BinaryClassificationModelSelector`` with the FULL REFERENCE DEFAULT grid —
LR (8 grids) + RandomForest (18: MaxDepth x MinInfoGain x
MinInstancesPerNode) + XGBoost (2) = 28 candidates x 3 folds = 84 model
fits — through ``ModelSelector.fit``'s ``find_best_estimator``, including
splitter holdout, DataBalancer preparation, the batched fold x grid XLA
sweeps, and validation metric evaluation
(BinaryClassificationModelSelector.scala:81-135, DefaultSelectorParams.scala).

Backend handling: the bench runs on the platform JAX selects and REFUSES
anything but a TPU (``utils/backend.require_tpu``: exit 1 with the reason) —
a CPU models/s number reads as a 50x regression, not as "no chip".

FLOPs / MFU (round-2 VERDICT #2): utils/flops.py records XLA
``cost_analysis()`` for every sweep kernel launch at its exact shapes; the
JSON reports ``flops_per_rep`` and ``mfu`` against the device's peak.
Honesty note on arithmetic intensity: the LR sweep is matmul-dominated (MXU)
and its MFU reads conventionally; the tree sweep's histogram building is
scatter/cumsum work on the VPU, so its contribution to "MFU" is utilization
of arithmetic throughput, not MXU duty cycle — on a tabular 891-row problem
the sweep is latency/bandwidth-bound by nature, which is exactly why
batching all 84 fits into a handful of launches wins.

Baseline: MEASURED, not invented (round-3 VERDICT #4).  ``baseline_proxy.py``
times the identical 28-grid x 3-fold sweep shape with scikit-learn on this
host's CPU and extrapolates perfect 8-thread scaling (the reference's JVM
pool width) — see BASELINE_MEASURED.json; ``vs_baseline`` divides by that
number.  Falls back to the old 4 models/s estimate only if the measured file
is absent.

Every rep uses a DIFFERENT fold seed — new fold weights, new device buffers
— which is what a fresh run would do.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

TITANIC = "/root/reference/test-data/PassengerDataAllWithHeader.csv"


def baseline_models_per_sec():
    """Measured sklearn-proxy baseline (baseline_proxy.py), with provenance."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE_MEASURED.json")
    try:
        with open(path) as f:
            m = json.load(f)
        return float(m["models_per_sec_8thread_linear"]), "measured-sklearn-8t"
    except Exception:
        return 4.0, "estimate"  # pre-round-4 fallback constant

from transmogrifai_tpu.utils.backend import device_peaks


def init_backend():
    """The device the bench measures on: ``{"platform", "kind", "count"}``.
    Exits 1 with the reason unless JAX selected a TPU; also switches on the
    persistent compile cache (one place: utils/backend)."""
    from transmogrifai_tpu.utils.backend import compile_cache_dir, require_tpu

    dev = require_tpu("bench")
    compile_cache_dir()
    return dev


def device_fields(dev) -> dict:
    """Every report names the device it ran on."""
    return {"platform": dev["platform"], "device_kind": dev["kind"],
            "device_count": dev["count"]}


def titanic_arrays():
    """Titanic -> (X, y) via the framework's own vectorization pipeline."""
    import pandas as pd

    from transmogrifai_tpu.features.builder import from_dataframe
    from transmogrifai_tpu.impl.feature.vectorizers import (
        OneHotVectorizer, RealVectorizer, StandardScalerVectorizer, VectorsCombiner)
    from transmogrifai_tpu.readers.base import CustomReader

    if os.path.exists(TITANIC):
        df = pd.read_csv(TITANIC)
        df.columns = [c.strip() for c in df.columns]
    else:  # synthetic fallback, same schema/scale
        rng = np.random.default_rng(0)
        n = 891
        df = pd.DataFrame({
            "survived": rng.integers(0, 2, n),
            "age": np.where(rng.random(n) < 0.2, np.nan, rng.uniform(1, 80, n)),
            "fare": rng.uniform(5, 500, n),
            "sibSp": rng.integers(0, 5, n),
            "parCh": rng.integers(0, 5, n),
            "sex": rng.choice(["male", "female"], n),
            "embarked": rng.choice(["S", "C", "Q"], n),
            "pClass": rng.integers(1, 4, n).astype(str),
        })
    df.columns = [c[0].lower() + c[1:] for c in df.columns]
    label = "survived"
    num_cols = [c for c in ("age", "fare", "sibSp", "parch", "parCh") if c in df.columns]
    cat_cols = [c for c in ("sex", "embarked", "pclass", "pClass", "cabin")
                if c in df.columns]

    feats, resp = from_dataframe(df, response=label)
    by_name = {f.name: f for f in feats}
    by_name[label] = resp
    reader = CustomReader(df)
    ds = reader.generate_dataset(list(by_name.values()), {})

    num_vec = RealVectorizer().set_input(*[by_name[c] for c in num_cols])
    cat_vec = OneHotVectorizer().set_input(*[by_name[c] for c in cat_cols])
    nm = num_vec.fit(ds)
    cm = cat_vec.fit(ds)
    ds = ds.with_column(nm.get_output().name, nm.transform_dataset(ds))
    ds = ds.with_column(cm.get_output().name, cm.transform_dataset(ds))
    comb = VectorsCombiner().set_input(nm.get_output(), cm.get_output())
    vec = comb.transform_dataset(ds)
    ds = ds.with_column(comb.get_output().name, vec)
    scaler = StandardScalerVectorizer().set_input(comb.get_output())
    X = scaler.fit(ds).transform_dataset(ds).values
    ycol = ds[label]
    y = np.where(ycol.mask, ycol.values, 0.0).astype(np.float32)
    return np.asarray(X, np.float32), y


def transform_bench():
    """``bench.py --transform [rows] [--data-shards D]``: streamed transform wall.

    Times the workflow transform pipeline ONLY (fill + 2 vectorizers +
    combiner + scaler, fitted once on a head sample) over the same rows:
    the per-stage host path (what ran above TMOG_FUSE_MAX_ROWS before
    streaming) and the chunked streaming executor (workflow/stream.py).
    The streamed number reports warm (includes the single compile) and
    steady separately.

    ``--data-shards D`` additionally times the mesh-sharded stream path
    (chunks round-robined over D data devices) against the single-device
    streamed wall and emits ``transform_stream_sharded_speedup``; parity vs
    the host path is asserted for BOTH streamed runs (fill/concat bit-exact
    contract, scaler rtol 2e-6).
    """
    data_shards = 0
    argv = sys.argv[2:]
    if "--data-shards" in argv:
        i = argv.index("--data-shards")
        data_shards = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.columns import Dataset, NumericColumn
    from transmogrifai_tpu.impl.feature.transformers import FillMissingWithMean
    from transmogrifai_tpu.impl.feature.vectorizers import (
        RealVectorizer, StandardScalerVectorizer, VectorsCombiner)
    from transmogrifai_tpu.utils import flops
    from transmogrifai_tpu.workflow import stream

    dev = init_backend()
    rows = next((int(a) for a in argv if a.isdigit()), 1_000_000)
    n_feat = 8
    rng = np.random.default_rng(0)
    cols = {}
    for j in range(n_feat):
        v = rng.normal(size=rows).astype(np.float32)
        m = rng.random(rows) > 0.1
        cols[f"x{j}"] = NumericColumn(T.Real, np.where(m, v, 0.0), m)
    ds = Dataset(cols)
    head = Dataset({k: NumericColumn(c.ftype, c.values[:50_000], c.mask[:50_000])
                    for k, c in ds.columns.items()})

    xs = [FeatureBuilder(f"x{j}", T.Real).extract(field=f"x{j}").as_predictor()
          for j in range(n_feat)]
    fm = FillMissingWithMean().set_input(xs[0]).fit(head)
    m1 = RealVectorizer().set_input(*xs[:4]).fit(head)
    m2 = RealVectorizer(fill_with_mean=False, fill_value=-1.0).set_input(*xs[4:]).fit(head)
    comb = VectorsCombiner().set_input(m1.get_output(), m2.get_output())
    fit_ds = head
    for t in (fm, m1, m2, comb):
        fit_ds = fit_ds.with_column(t.get_output().name, t.transform_dataset(fit_ds))
    sm = StandardScalerVectorizer().set_input(comb.get_output()).fit(fit_ds)
    layers = [[fm, m1, m2], [comb], [sm]]
    final = sm.get_output().name

    # per-stage host path (the pre-streaming fallback above the fuse cliff)
    t0 = time.perf_counter()
    host = ds
    for t in (fm, m1, m2, comb, sm):
        host = host.with_column(t.get_output().name, t.transform_dataset(host))
    host_s = time.perf_counter() - t0

    # live={final}: the workflow's liveness pass materializes only columns
    # needed downstream — intermediates stay device-resident (the host path
    # has no such option; it materializes every stage output)
    if data_shards > 1:
        # pin the baseline pair to one device even when TMOG_MESH is set
        os.environ["TMOG_STREAM_ROUTE"] = "single"
        # unless the user pinned a chunking, pick one that gives every
        # device ~2 chunks; both streamed runs use it (same-work compare)
        if not os.environ.get("TMOG_TRANSFORM_CHUNK_ROWS"):
            c = max(4096, -(-rows // (2 * data_shards)))
            os.environ["TMOG_TRANSFORM_CHUNK_ROWS"] = str(-(-c // 256) * 256)
    flops.enable()
    stream.reset_stream_stats()
    t0 = time.perf_counter()
    out = stream.apply_streamed(ds, layers, live={final})
    warm_s = time.perf_counter() - t0
    assert out is not None, "streaming declined the bench pipeline"
    np.testing.assert_allclose(out[final].values, host[final].values,
                               rtol=2e-6, atol=1e-6)

    stream.reset_stream_stats()
    t0 = time.perf_counter()
    out = stream.apply_streamed(ds, layers, live={final})
    steady_s = time.perf_counter() - t0
    s = stream.stream_stats()
    streamed_flops = flops.totals().get("streamed") or {}
    flops.disable()

    sharded = None
    if data_shards > 1:
        os.environ.pop("TMOG_STREAM_ROUTE", None)
        os.environ["TMOG_STREAM_SHARDS"] = str(data_shards)
        stream.reset_stream_stats()
        t0 = time.perf_counter()
        out_sh = stream.apply_streamed(ds, layers, live={final})
        sharded_warm_s = time.perf_counter() - t0
        assert out_sh is not None, "sharded streaming declined the bench pipeline"
        np.testing.assert_allclose(out_sh[final].values, host[final].values,
                                   rtol=2e-6, atol=1e-6)
        stream.reset_stream_stats()
        t0 = time.perf_counter()
        out_sh = stream.apply_streamed(ds, layers, live={final})
        sharded_steady_s = time.perf_counter() - t0
        ss = stream.stream_stats()
        os.environ.pop("TMOG_STREAM_SHARDS", None)
        # honesty stamp: N virtual shards on < N physical cores time-slice
        # one core, so the "speedup" measures scheduler noise, not scaling —
        # the perf gate must not regress (or celebrate) such a number
        core_bound = (os.cpu_count() or 1) < data_shards
        sharded = {
            "metric": "transform_stream_sharded_speedup",
            "value": round(steady_s / sharded_steady_s, 2),
            "unit": "x vs single-device streamed path",
            "data_shards": data_shards,
            **({"core_bound": True} if core_bound else {}),
            "shards_used": ss["shards"],
            "stream_warm_s": round(sharded_warm_s, 3),
            "stream_steady_s": round(sharded_steady_s, 3),
            "transform_rows_per_sec": round(ss["transform_rows_per_sec"]),
            "chunks": ss["chunks"],
            "compiles_steady": ss["compiles"],
            "overlap_efficiency": round(ss["overlap_efficiency"], 3),
            "prep_s": round(ss["prep_s"], 3),
            "prep_blocked_s": round(ss["prep_blocked_s"], 3),
            "by_device": {k: v["chunks"] for k, v in ss["by_device"].items()},
        }

    report = {
        "metric": "transform_stream_speedup",
        "value": round(host_s / steady_s, 2),
        "unit": "x vs per-stage host path",
        "rows": rows,
        "features": n_feat,
        "vector_width": int(out[final].values.shape[1]),
        "host_wall_s": round(host_s, 3),
        "stream_warm_s": round(warm_s, 3),
        "stream_steady_s": round(steady_s, 3),
        "transform_rows_per_sec": round(s["transform_rows_per_sec"]),
        "chunks": s["chunks"],
        "chunk_rows": s["chunk_rows"],
        "pad_rows": s["pad_rows"],
        "buffers": stream.stream_buffers(),
        "stages_fused": s["stages_fused"],
        "compiles_steady": s["compiles"],
        "bytes_streamed_in": round(s["bytes_in"]),
        "bytes_streamed_out": round(s["bytes_out"]),
        "overlap_efficiency": round(s["overlap_efficiency"], 3),
        "streamed_flops_bucket": streamed_flops,
        **device_fields(dev),
        **({"sharded": sharded} if sharded else {}),
    }
    print(json.dumps(report))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "STREAM_BENCH.json"), "w") as f:
        json.dump(report, f, indent=1)
    from transmogrifai_tpu import obs

    obs.write_record("bench", extra={"report": report})
    if sharded:
        obs.write_record("bench", extra={"report": sharded})


def serve_bench():
    """``bench.py --serve [replicas]``: replicated serving + AOT cache wall.

    Measures the fleet-serving acceptance pair on one host: (1) micro-batch
    throughput and p99 at 1 replica vs N replicas (same client load, same
    model), and (2) cold vs instant-warm deploy wall — the second deploy
    loads every per-bucket executable from the persistent AOT cache
    (TMOG_COMPILE_CACHE, kept under the compile-cache root) instead of
    compiling; each drive reports its cache hits, so a "cold" deploy that
    found entries from an earlier run on the same machine says so.
    """
    import tempfile
    import threading

    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.impl.classification.logistic import (
        OpLogisticRegression)
    from transmogrifai_tpu.impl.feature.vectorizers import (
        OneHotVectorizer, RealVectorizer, VectorsCombiner)
    from transmogrifai_tpu.serve import (MicroBatcher, ModelRegistry,
                                         ServeMetrics)
    from transmogrifai_tpu.serve import compile_cache
    from transmogrifai_tpu.testkit import TestFeatureBuilder
    from transmogrifai_tpu.workflow.model import load_model

    dev = init_backend()
    import jax

    n_replicas = next((int(a) for a in sys.argv[2:] if a.isdigit()),
                      len(jax.devices()))
    n = 256
    ds, (x, cat, y) = TestFeatureBuilder.of(
        ("x", T.Real, list(np.linspace(-2, 2, n))),
        ("cat", T.PickList, ["a", "b", "c", "d"] * (n // 4)),
        ("y", T.RealNN, [float(i % 2) for i in range(n)]), response="y")
    feats = VectorsCombiner().set_input(
        RealVectorizer().set_input(x).get_output(),
        OneHotVectorizer(top_k=5, min_support=1).set_input(cat).get_output(),
    ).get_output()
    pred = OpLogisticRegression(reg_param=0.1).set_input(y, feats).get_output()
    model = OpWorkflow().set_input_dataset(ds).set_result_features(pred).train()

    tmp = tempfile.mkdtemp(prefix="tmog_serve_bench_")
    saved = os.path.join(tmp, "model")
    model.save(saved)
    from transmogrifai_tpu.utils.backend import cache_root

    os.environ["TMOG_COMPILE_CACHE"] = os.path.join(cache_root(), "aotx")
    clients, per_client = 64, 40

    def drive(replicas):
        compile_cache.reset_cache_stats()
        metrics = ServeMetrics()
        registry = ModelRegistry(max_batch=64, metrics=metrics,
                                 replicas=replicas)
        t0 = time.perf_counter()
        registry.deploy(load_model(saved))
        warm_s = time.perf_counter() - t0
        cache = compile_cache.cache_stats()
        batcher = MicroBatcher(registry, max_batch=64, max_wait_ms=2.0,
                               queue_size=8192, metrics=metrics).start()
        errors = []

        def client():
            try:
                for _ in range(per_client):
                    batcher.score({"x": 0.7, "cat": "b"}, timeout_s=120)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        batcher.stop()
        assert not errors, errors[:3]
        snap = metrics.snapshot()
        return {
            "replicas": registry.n_replicas,
            "warmup_s": round(warm_s, 3),
            "qps": round(clients * per_client / dt, 1),
            "p99_ms": snap["request_latency"]["p99_ms"],
            "resilience": {k: snap[k] for k in (
                "degraded_batches", "replica_failures", "replica_rebuilds")},
            "replica_slots_hit": sum(
                1 for s in snap["replicas"].values() if s["batches"]),
            "cache": {k: (round(cache[k], 3) if isinstance(cache[k], float)
                          else cache[k])
                      for k in ("hits", "misses", "compiles", "compile_s",
                                "load_s", "saves")},
        }

    fleet_cold = drive(n_replicas)  # empty cache: every (bucket, chip) compiles
    fleet = drive(n_replicas)       # warm: every executable deserializes
    single = drive(1)               # QPS baseline (cache state irrelevant)
    report = {
        "metric": "serve_replica_qps_speedup",
        "value": round(fleet["qps"] / single["qps"], 2),
        "unit": f"x qps at {fleet['replicas']} replicas vs 1",
        "warm_restart_speedup": round(
            fleet_cold["warmup_s"] / fleet["warmup_s"], 2),
        "single": single,
        "fleet": fleet,
        "fleet_cold": fleet_cold,
        "clients": clients,
        "requests": clients * per_client,
        **device_fields(dev),
    }
    print(json.dumps(report))
    from transmogrifai_tpu import obs

    obs.write_record("bench", extra={"report": report})

    # ---- multi-tenant fleet: N named tenants share the SAME chips ----------
    # aggregate QPS + worst per-tenant p99 at 1 vs 8/16/64 tenants, plus the
    # two lifecycle acceptance checks: an LRU-evicted tenant reactivates
    # through the compile cache's warm path with ZERO fresh XLA compiles, and
    # one tenant's hot-swap opens no capacity gap for its neighbours.
    from transmogrifai_tpu.serve import aot as serve_aot

    shared = load_model(saved)  # one model object: per-tenant warms memo-hit
    t_clients, t_per_client = 32, 8

    def drive_tenants(n_tenants):
        metrics = ServeMetrics()
        registry = ModelRegistry(max_batch=64, metrics=metrics,
                                 replicas=n_replicas)
        t0 = time.perf_counter()
        for i in range(n_tenants):
            registry.deploy(shared, tenant=f"t{i:02d}")
        warm_s = time.perf_counter() - t0
        batcher = MicroBatcher(registry, max_batch=64, max_wait_ms=2.0,
                               queue_size=8192, metrics=metrics).start()
        errors = []

        def client(idx):
            tenant = f"t{idx % n_tenants:02d}"
            try:
                for _ in range(t_per_client):
                    batcher.score({"x": 0.7, "cat": "b"}, timeout_s=120,
                                  tenant=tenant)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(t_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - t0
        assert not errors, errors[:3]

        # LRU eviction -> first-request reactivation: must be instant-warm
        registry.evict_tenant("t00")
        compile_cache.reset_cache_stats()
        serve_aot.reset_warm_stats()
        batcher.score({"x": 0.7, "cat": "b"}, timeout_s=120, tenant="t00")
        react_compiles = compile_cache.cache_stats()["compiles"]
        react_warms = serve_aot.warm_stats()

        # one tenant hot-swaps; a neighbour's traffic must never gap
        gap_errors: list = []
        swapped = {}
        if n_tenants >= 2:
            neighbour = f"t{min(2, n_tenants - 1):02d}"
            stop = threading.Event()

            def neighbour_traffic():
                while not stop.is_set():
                    try:
                        batcher.score({"x": 0.7, "cat": "b"}, timeout_s=120,
                                      tenant=neighbour)
                    except Exception as e:  # noqa: BLE001
                        gap_errors.append(e)

            th = threading.Thread(target=neighbour_traffic)
            th.start()
            before = metrics.snapshot()["tenants"][neighbour]["responses"]
            registry.deploy(load_model(saved), version="swap-v2",
                            tenant="t01")
            stop.set()
            th.join(60)
            after = metrics.snapshot()["tenants"][neighbour]["responses"]
            swapped = {"neighbour": neighbour,
                       "neighbour_responses_during_swap": after - before,
                       "capacity_gap_errors": len(gap_errors)}
            assert not gap_errors, gap_errors[:3]
        batcher.stop()
        snap = metrics.snapshot()
        p99s = [st["request_latency"]["p99_ms"]
                for st in snap["tenants"].values()
                if st["request_latency"]["count"]]
        return {
            "tenants": n_tenants,
            "replicas": registry.n_replicas,
            "warmup_s": round(warm_s, 3),
            "aggregate_qps": round(t_clients * t_per_client / dt, 1),
            "tenant_p99_ms_max": round(max(p99s), 3) if p99s else 0.0,
            "tenant_p99_ms_mean": (round(sum(p99s) / len(p99s), 3)
                                   if p99s else 0.0),
            "reactivation_compiles": react_compiles,
            "reactivation_warms": react_warms,
            "activations": snap["tenant_activations"],
            "reactivations": snap["tenant_reactivations"],
            "evictions": snap["tenant_evictions"],
            **swapped,
        }

    mt_single = drive_tenants(1)
    mt = {n: drive_tenants(n) for n in (8, 16, 64)}
    mt_report = {
        "metric": "serve_multi_tenant_qps",
        "value": round(mt[16]["aggregate_qps"] / mt_single["aggregate_qps"],
                       3),
        "unit": "x aggregate qps at 16 tenants vs 1 on the same chips",
        "single_tenant": mt_single,
        **{f"tenants_{n}": r for n, r in mt.items()},
        "reactivation_compiles": max(r["reactivation_compiles"]
                                     for r in mt.values()),
        "capacity_gap_errors": max(r.get("capacity_gap_errors", 0)
                                   for r in mt.values()),
        **device_fields(dev),
    }
    print(json.dumps(mt_report))
    obs.write_record("bench", extra={"report": mt_report})


def make_selector(seed: int = 42):
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)

    return BinaryClassificationModelSelector.with_cross_validation(
        num_folds=3, seed=seed)


def continual_bench():
    """``bench.py --continual [rows]``: warm-start retrain vs cold sweep wall.

    The continual-learning acceptance pair: a drift-triggered retrain prunes
    the selector grid to the incumbent winner's neighborhood
    (``ModelSelector.warm_start``), so its wall must be a fraction of the
    cold full-grid sweep that elected the champion.  Times both on the same
    synthetic two-era data the closed-loop harness uses and reports the
    speedup plus pruned-vs-full candidate counts.
    """
    from tools.continual_loop import _build, _workflow
    from transmogrifai_tpu.continual import incumbent_summary

    dev = init_backend()
    rows = next((int(a) for a in sys.argv[2:] if a.isdigit()), 256)

    ds_a, feats_a = _build(rows, 0.0)
    wf_cold = _workflow(ds_a, feats_a, 3)
    sel = next(s for s in wf_cold.stages
               if getattr(s, "is_model_selector", False))
    full = sum(len(g) for _, g in sel.models)
    t0 = time.perf_counter()
    champion = wf_cold.train()
    cold_s = time.perf_counter() - t0

    summary = incumbent_summary(champion)
    ds_b, feats_b = _build(rows, 3.0)
    wf_warm = _workflow(ds_b, feats_b, 3)
    sel_warm = next(s for s in wf_warm.stages
                    if getattr(s, "is_model_selector", False))
    sel_warm.warm_start(summary, explore=1)
    pruned, _ = sel_warm.validator.warm_start_counts
    t0 = time.perf_counter()
    wf_warm.train()
    warm_s = time.perf_counter() - t0

    report = {
        "metric": "continual_warm_retrain_speedup",
        "value": round(cold_s / warm_s, 2) if warm_s else None,
        "unit": f"x wall, {pruned}-grid warm retrain vs {full}-grid cold",
        "rows": rows,
        "cold_sweep_wall_s": round(cold_s, 3),
        "warm_retrain_wall_s": round(warm_s, 3),
        "full_candidates": full,
        "pruned_candidates": pruned,
        "incumbent": summary.best_model_type if summary else None,
        **device_fields(dev),
    }
    print(json.dumps(report))
    from transmogrifai_tpu import obs

    obs.write_record("bench", extra={"report": report})


def asha_bench():
    """``bench.py --asha [n_candidates]``: rung-scheduled search vs grid.

    The successive-halving acceptance pair: ASHA over a 500+ candidate
    superset of the stock binary space must (1) finish within a small
    multiple of the exhaustive 28-grid wall — that ratio is the perfgate
    metric (lower-better) — and (2) re-elect the exhaustive winner's
    family with a best metric inside a pinned tolerance (the parity
    metric, higher-better).  Both sides get one warmup pass so the timed
    walls compare steady executions, not compile queues.
    """
    from transmogrifai_tpu.impl.selector.defaults import asha_search_space
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)

    dev = init_backend()
    n_cands = next((int(a) for a in sys.argv[2:] if a.isdigit()), 500)
    X, y = titanic_arrays()

    # exhaustive reference: the stock 28-grid (warm pass compiles)
    make_selector(seed=7).find_best_estimator(X, y)
    t0 = time.perf_counter()
    _, _, grid_summary = make_selector(seed=101).find_best_estimator(X, y)
    grid_s = time.perf_counter() - t0
    n_grid = len(grid_summary.results)

    def asha_selector(seed):
        return BinaryClassificationModelSelector.with_cross_validation(
            num_folds=3, seed=seed,
            models_and_parameters=asha_search_space(n_cands),
            search_strategy="asha")

    asha_selector(7).find_best_estimator(X, y)  # warm pass
    t0 = time.perf_counter()
    _, _, asha_summary = asha_selector(101).find_best_estimator(X, y)
    asha_s = time.perf_counter() - t0

    rungs = asha_summary.asha["rungs"]
    gb, ab = grid_summary.best, asha_summary.best
    winner_match = gb.model_name == ab.model_name
    metric_delta = abs(float(ab.metric_value) - float(gb.metric_value))
    evaluated = sum(r["candidates_in"] for r in rungs)

    wall_report = {
        "metric": "asha_500_vs_grid28_wall_ratio",
        "value": round(asha_s / max(grid_s, 1e-9), 3),
        "unit": f"x wall, {len(asha_summary.results)}-candidate ASHA vs "
                f"{n_grid}-grid exhaustive",
        "asha_wall_s": round(asha_s, 3),
        "grid_wall_s": round(grid_s, 3),
        "n_candidates": len(asha_summary.results),
        "n_grid": n_grid,
        "rungs_run": len(rungs),
        "reduction": asha_summary.asha["reduction"],
        "async": asha_summary.asha["async"],
        "candidate_evals": evaluated,
        **device_fields(dev),
    }
    parity_report = {
        "metric": "asha_best_metric_parity",
        "value": round(max(0.0, 1.0 - metric_delta), 4),
        "unit": "1 - |asha best - grid best| (same evaluator)",
        "winner_match": 1.0 if winner_match else 0.0,
        "grid_winner": gb.model_name,
        "grid_best_metric": round(float(gb.metric_value), 4),
        "asha_winner": ab.model_name,
        "asha_best_metric": round(float(ab.metric_value), 4),
        "metric_delta": round(metric_delta, 4),
        **device_fields(dev),
    }
    print(json.dumps(wall_report))
    print(json.dumps(parity_report))
    from transmogrifai_tpu import obs

    obs.write_record("bench", extra={"report": wall_report})
    obs.write_record("bench", extra={"report": parity_report})


def family_flops_breakdown(sel, X, y, train_w, val_mask):
    """Per-family single-launch XLA flops of the default sweep (LR/RF/XGB).

    Each family's fragment subset is lowered STANDALONE at the bench's exact
    fold shapes via ``flops.cost_of`` (no accumulation into the running
    totals), so the one ``sweep.run`` bucket decomposes into who actually
    burns the FLOPs.  Returns {} when the fused builder declines a family.
    """
    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.ops.sweep import _run
    from transmogrifai_tpu.utils import flops

    fam_of = {"OpLogisticRegression": "LR", "OpLinearRegression": "LR",
              "OpRandomForestClassifier": "RF", "OpRandomForestRegressor": "RF",
              "OpDecisionTreeClassifier": "RF", "OpDecisionTreeRegressor": "RF",
              "OpGBTClassifier": "XGB", "OpXGBoostClassifier": "XGB",
              "OpGBTRegressor": "XGB", "OpXGBoostRegressor": "XGB"}
    tw = np.asarray(train_w, np.float32)
    vw = np.asarray(val_mask, np.float32)
    fams = {}
    for est, grids in sel.models:
        label = fam_of.get(type(est).__name__, "other")
        try:
            plan = build_sweep_plan([(est, grids)], X, y, tw,
                                    sel.validator.evaluator)
            if plan is None:
                continue
            cost = flops.cost_of(_run, plan.spec, plan.X, tuple(plan.xbs),
                                 plan.y, tw, vw, plan.blob)
        except Exception:
            continue
        if cost is None:
            continue
        fams[label] = fams.get(label, 0.0) + cost["flops"]
    return {k: round(v) for k, v in fams.items()}


def main():
    dev = init_backend()

    import jax

    from transmogrifai_tpu.utils import flops

    device_kind = jax.devices()[0].device_kind
    X, y = titanic_arrays()

    # reference default sweep: LR 8 + RF 18 + XGB 2 = 28 candidates
    sel = make_selector()
    n_grids = sum(len(g) for _, g in sel.models)
    n_models = sel.validator.num_folds * n_grids

    # warmup: compiles every kernel in the sweep (cached thereafter).  The
    # persistent compile cache is already on (init_backend), so a
    # warm-cache bench run demonstrates the instant-warm number outside
    # serve, and the AOT compile telemetry splits the cold wall into compile
    # vs dispatch.
    from transmogrifai_tpu.ops import sweep as sweep_ops
    sweep_ops.reset_run_stats()
    t_first = time.perf_counter()
    sel.find_best_estimator(X, y)
    warm = time.perf_counter() - t_first
    warmup_compile_s = float(sweep_ops.run_stats()["compile_s"])
    warmup_dispatch_s = max(warm - warmup_compile_s, 0.0)

    from transmogrifai_tpu.obs import ledger, timeline, trace

    flops.enable()
    flops.reset()
    ledger.enable()
    ledger.reset()
    reps = 3
    trace_was_on = trace.enabled()
    if not trace_was_on:
        trace.enable(path=None)  # in-memory: feed the bubble profiler
    t0 = time.perf_counter()
    with trace.span("bench.window", reps=reps):
        for r in range(reps):
            # new seed -> new folds -> new device buffers (what a fresh
            # run would do)
            sel2 = make_selector(seed=100 + r)
            _, _, summary = sel2.find_best_estimator(X, y)
            assert summary.best.metric_value == summary.best.metric_value
    dt = (time.perf_counter() - t0) / reps
    try:
        bubble = timeline.bubble_report(window="bench.window",
                                        wall_s=dt * reps)
    except ValueError:
        bubble = None
    if not trace_was_on:
        trace.disable()
    acct = flops.totals()
    flops.disable()
    # roofline ledger: per-launch FLOPs/bytes vs the device peaks, factored
    # per family — the "which lever does each family need" report
    try:
        roof = ledger.ledger_report(window_wall_s=dt * reps,
                                    device_kind=device_kind,
                                    platform=platform, reps=reps)
    except ValueError:
        roof = None
    ledger.disable()
    ledger.reset()

    # sweep-launch telemetry (reset per validate: this is the LAST rep's),
    # so a multi-chip run shows its shard count + per-shard wall/compile —
    # the aggregate models/s above already spans all shards
    sweep_stats = sweep_ops.run_stats()

    models_per_sec = n_models / dt
    base, base_src = baseline_models_per_sec()
    out = {
        "metric": "selector_sweep_models_per_sec",
        "value": round(models_per_sec, 2),
        "unit": "models/s",
        "vs_baseline": round(models_per_sec / base, 2),
        "baseline_models_per_sec": base,
        "baseline_source": base_src,
        **device_fields(dev),
        "sweep": f"{n_grids} grids x {sel.validator.num_folds} folds "
                 "(LR 8 + RF 18 + XGB 2 reference defaults)",
        "warmup_s": round(warm, 2),
        # cold-warmup decomposition: XLA compile seconds (AOT telemetry)
        # vs everything else (dispatch/upload/host) — the compile share is
        # what the persistent compile cache erases on a warm restart
        "warmup_compile_s": round(warmup_compile_s, 2),
        "warmup_dispatch_s": round(warmup_dispatch_s, 2),
        "steady_s": round(dt, 2),
        "sweep_shards": sweep_stats["sweep_shards"],
        "data_shards": sweep_stats["data_shards"],
        # candidate packing (TMOG_SWEEP_PACK): packed launches built in the
        # last rep, and sequential dispatches avoided vs one-launch-per-
        # candidate (always present so baselines can compare)
        "sweep_pack_count": int(sweep_stats.get("sweep_pack_count") or 0),
        "launches_avoided": int(sweep_stats.get("launches_avoided") or 0),
    }
    # sequential GBT launch-levels on the critical path: the full
    # dependency chain (steps x depth; K=4 round-collapse turns the
    # reference 200x10 = 2000 levels into 500), minus measured
    # cross-device overlap under TMOG_GBT_PIPELINE (gbt_chain_eff)
    if sweep_stats.get("gbt_chain_levels"):
        out["gbt_sequential_launches"] = (
            sweep_stats.get("gbt_sequential_launches")
            or sweep_stats["gbt_chain_levels"])
        out["gbt_chain_levels"] = sweep_stats["gbt_chain_levels"]
        out["gbt_chain_steps"] = sweep_stats["gbt_chain_steps"]
    hs = acct.get("hist_subtracted") or {}
    if hs.get("levels"):
        out["hist_subtracted_per_rep"] = {
            "levels": round(hs["levels"] / reps),
            "flops_avoided": round(hs["flops_avoided"] / reps)}
    per_shard = [s for l in sweep_stats["launches"] if l["shards"] > 1
                 for s in l["per_shard"]]
    if per_shard:
        out["sweep_per_shard"] = per_shard
    # straggler defense: duplicate dispatches fired + the losers' discarded
    # wall as a fraction of total sweep wall (perfgate lower-better policy —
    # the key is always present so baselines can compare it)
    hedges_fired = int(sweep_stats.get("hedges_fired") or 0)
    wasted_s = float(sweep_stats.get("hedge_wasted_s") or 0.0)
    total_wall = sum(s.get("wall_s", 0.0) for s in per_shard) or dt
    out["hedges_fired"] = hedges_fired
    out["hedge_wasted_s"] = round(wasted_s, 4)
    out["hedge_wasted_fraction"] = round(
        wasted_s / max(total_wall + wasted_s, 1e-9), 4)
    # predicted-vs-measured per-shard cost error (MAPE + makespan ratios):
    # every bench run appends its own eval row to the telemetry record, so
    # the learned cost model's eval set grows for free
    try:
        from transmogrifai_tpu import costmodel
        cm_eval = costmodel.eval_launches(sweep_stats["launches"])
        if cm_eval:
            out["costmodel_eval"] = cm_eval
    except Exception:
        pass
    # row-sharded launches: per-axis collective traffic + the memory story
    # (peak per-device X/y bytes vs what full replication would have held)
    coll_axes = {}
    for l in sweep_stats["launches"]:
        for ax, c in (l.get("collectives") or {}).items():
            agg = coll_axes.setdefault(ax, {"count": 0, "bytes": 0})
            agg["count"] += c["count"]
            agg["bytes"] += c["bytes"]
    if coll_axes:
        out["collective_bytes_by_axis"] = coll_axes
    pdb = next((l["per_device_bytes"] for l in reversed(sweep_stats["launches"])
                if l.get("rowsharded")), None)
    if pdb:
        out["per_device_bytes"] = pdb
        out["per_device_bytes_vs_replicated"] = round(
            (pdb["X"] + pdb["y"]) / max(pdb["X_replicated"] + pdb["y_replicated"], 1), 4)
    # per-rep collective accounting from the flops bucket (count + bytes per
    # axis, psum/all_gather split) — the communication half of MFU honesty
    if acct.get("collectives"):
        out["collectives_per_rep"] = {
            ax: {k: (round(v / reps) if isinstance(v, (int, float)) else v)
                 for k, v in c.items()}
            for ax, c in acct["collectives"].items()}
    if acct.get("by_device"):
        out["flops_by_device"] = {k: round(v["flops"] / reps)
                                  for k, v in acct["by_device"].items()}
    if acct["calls"]:
        flops_per_rep = acct["flops"] / reps
        out["flops_per_rep"] = round(flops_per_rep)
        out["flops_by_kernel"] = {k: round(v["flops"] / reps)
                                  for k, v in acct["by_fn"].items()}
        # decompose the single fused sweep.run bucket per model family by
        # lowering each family's fragment subset standalone at the same
        # shapes; residual (metrics glue, XLA fusion deltas) stays labeled
        tw, vm = sel.validator.make_folds(X.shape[0], y)
        fam = family_flops_breakdown(sel, X, y, tw, vm)
        if not fam and roof:
            # standalone re-lowering failed (BENCH_r05 fell back to the
            # single sweep.run bucket here): the ledger's per-family split
            # of the same cost_analysis totals is always available
            fam = {k: round(v["flops"] / reps)
                   for k, v in roof["by_family"].items()}
        if fam:
            out["flops_by_family"] = fam
            if "sweep.run" in out["flops_by_kernel"]:
                total = out["flops_by_kernel"].pop("sweep.run")
                for k, v in sorted(fam.items()):
                    out["flops_by_kernel"][f"sweep.run[{k}]"] = v
                rest = round(total - sum(fam.values()))
                if rest > 0:
                    out["flops_by_kernel"]["sweep.run[other]"] = rest
        out["bytes_per_rep"] = round(acct["bytes_accessed"] / reps)
        if roof:
            out["bytes_by_family"] = {
                k: round(v["bytes"] / reps)
                for k, v in roof["by_family"].items()}
        peak = device_peaks(device_kind, platform)["peak_flops"]
        out["mfu"] = round(flops_per_rep / dt / peak, 6)
        out["peak_flops"] = peak
    else:
        out["flops_per_rep"] = None
        out["flops_note"] = "cost_analysis unavailable on this backend"
    if bubble:
        # keep the headline report lean: bubble fractions inline, the full
        # per-lane report in the JSONL record only
        out["bubble_fraction"] = bubble["bubble_fraction"]
        print(timeline.format_report(bubble), file=sys.stderr)
    if roof:
        out["mfu_decomposition"] = roof["mfu_decomposition"]
        out["launch_bound_fraction"] = roof["launch_bound_fraction"]
        print(ledger.format_report(roof), file=sys.stderr)
    print(json.dumps(out))
    from transmogrifai_tpu import obs

    extra = {"report": out}
    if bubble:
        extra["bubble_report"] = bubble
    if roof:
        extra["roofline"] = roof
    obs.write_record("bench", extra=extra)


if __name__ == "__main__":
    if "--transform" in sys.argv:
        transform_bench()
    elif "--serve" in sys.argv:
        serve_bench()
    elif "--continual" in sys.argv:
        continual_bench()
    elif "--asha" in sys.argv:
        asha_bench()
    else:
        main()
