"""BASELINE config #5 proof: synthetic 10M x 500 end-to-end AutoML at scale.

Pipeline (the real product path, not a side harness):
  500 raw typed features (460 Real + 40 PickList) -> CustomReader vectorized
  ingest -> Transmogrifier defaults -> SanityChecker with the row-sharded
  STREAMING stats path (two chunked passes over the mesh data axis; the
  O(p^2) feature-feature correlation as blocked centered-Gram MXU matmuls —
  SURVEY §2.7 axis 1 + §5.7) -> BinaryClassificationModelSelector with a
  64-candidate 5-fold CV grid (LR 44 FISTA + SVC 12 + MLP 8 — every
  candidate on the batched fold x grid XLA path; NaiveBayes excluded, see
  ``build``) -> train+holdout evaluation.

Scale choices, stated honestly:
- The ModelSelector trains on DataBalancer-prepared data capped at
  ``max_training_sample`` (reference SplitterParamDefaults 1E6; default here
  500k so the sweep's X fits one chip's HBM comfortably) — the reference
  applies exactly this cap.
- SanityChecker keeps the reference's 100k sample cap
  (``sample_upper_limit``, SanityChecker.scala:58-92) — identical
  semantics; the UNCAPPED one-pass streaming stats path is proven
  separately at multi-million-row scale
  (tests/test_sharded_stats.py + the round-5 3M-row device measurement).
- ``transmogrify`` runs without the label (no per-feature decision-tree
  bucketizers), matching the reference's plain ``.transmogrify()`` default.
- Workflow-level CV is opted out (``with_selector_cv``) to bound wall-clock:
  per-fold SanityChecker refits at 10M rows would 6x the stats passes; the
  equivalence of the two CV modes is tested at small scale
  (tests/test_workflow_cv.py).

Rows default to 10M; TMOG_SCALE_ROWS overrides (CI smoke uses ~100k).
Emits one JSON line with per-phase wall-clock + sweep models/s, and appends
the listener's per-stage metrics.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: BASELINE config #5's published shape: 500 raw features, and the
#: selector's training-sample cap
FULL_NUM, FULL_CAT, FULL_MAX_TRAIN = 460, 40, 500_000

N_ROWS = int(os.environ.get("TMOG_SCALE_ROWS", 10_000_000))
N_NUM = int(os.environ.get("TMOG_SCALE_NUM", FULL_NUM))
N_CAT = int(os.environ.get("TMOG_SCALE_CAT", FULL_CAT))
MAX_TRAIN = int(os.environ.get("TMOG_SCALE_MAX_TRAIN", FULL_MAX_TRAIN))
FOLDS = 5


def synthesize(n: int, seed=7, n_num: int = N_NUM, n_cat: int = N_CAT):
    """Synthetic COLUMNAR dataset (zero-copy into the reader's Dataset fast
    path — no 20 GB pandas shadow): informative numerics, correlated pairs,
    categorical signal, and a binary label — enough structure for the
    SanityChecker and selector to have something real to do.  ``seed`` may
    be a SeedSequence-style list — scale100m.py seeds per host so two hosts
    never synthesize the same rows."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu.columns import Dataset, NumericColumn, ObjectColumn

    rng = np.random.default_rng(seed)
    cols = {}
    ones = np.ones(n, bool)
    signal = rng.normal(size=n).astype(np.float32)
    prev = None
    for j in range(n_num):
        noise = rng.normal(size=n).astype(np.float32)
        if j % 50 == 0:        # strongly informative
            v = signal * np.float32(0.8) + noise * np.float32(0.6)
        elif j % 50 == 1:      # near-duplicate of the previous (corr ~0.999)
            v = prev + noise * np.float32(0.02)
        elif j % 50 == 2:      # constant -> min-variance drop
            v = np.full(n, 3.14, np.float32)
        else:
            v = noise
        cols[f"num_{j}"] = NumericColumn(T.Real, v, ones)
        prev = v
    cats = np.array([f"c{k}" for k in range(8)], dtype=object)
    for j in range(n_cat):
        idx = rng.integers(0, 8, n)
        if j % 10 == 0:  # label-associated category
            idx = np.where((signal > 0.5) & (rng.random(n) < 0.7), 0, idx)
        cols[f"cat_{j}"] = ObjectColumn(T.PickList, cats[idx])
    logits = signal * 1.5 + cols["num_0"].values * 0.5
    y = (logits + rng.logistic(size=n) > 0).astype(np.float32)
    cols["label"] = NumericColumn(T.RealNN, y, ones)
    return Dataset(cols)


def features(n_num: int = N_NUM, n_cat: int = N_CAT):
    """(label, sanity-checked feature vector) of the pipeline: the typed raw
    features -> Transmogrifier defaults -> SanityChecker on the streaming
    stats path."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import FeatureBuilder
    from transmogrifai_tpu.impl.feature.transmogrifier import transmogrify
    from transmogrifai_tpu.dsl import sanity_check  # noqa: F401 (registers DSL)

    label = FeatureBuilder("label", T.RealNN).extract(field="label").as_response()
    feats = [FeatureBuilder(f"num_{j}", T.Real).extract(field=f"num_{j}").as_predictor()
             for j in range(n_num)]
    feats += [FeatureBuilder(f"cat_{j}", T.PickList).extract(field=f"cat_{j}").as_predictor()
              for j in range(n_cat)]
    vec = transmogrify(feats)
    return label, vec.sanity_check(label, sharded_stats=True)


def candidates():
    """The 64-candidate grid, all on the batched fold x grid XLA path."""
    from transmogrifai_tpu.impl.selector.defaults import RandomParamBuilder
    from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
    from transmogrifai_tpu.impl.classification.svc import OpLinearSVC
    from transmogrifai_tpu.impl.classification.mlp import (
        OpMultilayerPerceptronClassifier)

    # NaiveBayes is excluded: vectorized numerics are signed and Spark NB
    # (like ours) rejects negative features — the reference leaves NB off by
    # default too.
    lr_grids = (RandomParamBuilder(seed=11)
                .exponential("reg_param", 1e-4, 0.3)
                .uniform("elastic_net_param", 0.05, 0.95)
                .subset(44))
    svc_grids = [{"reg_param": r} for r in
                 (1e-4, 3e-4, 1e-3, 3e-3, 0.01, 0.02, 0.03, 0.06, 0.1, 0.15,
                  0.2, 0.3)]
    mlp_grids = [{"step_size": s, "seed": sd}
                 for s in (0.01, 0.03, 0.1, 0.2) for sd in (1, 2)]
    grid = [
        (OpLogisticRegression(max_iter=200), lr_grids),
        (OpLinearSVC(max_iter=200), svc_grids),
        (OpMultilayerPerceptronClassifier(hidden_layers=(16,), max_iter=120),
         mlp_grids),
    ]
    n_cands = sum(len(g) for _, g in grid)
    assert n_cands == 64, n_cands
    return grid


def build(df, n_num: int = N_NUM, n_cat: int = N_CAT,
          max_train: int = MAX_TRAIN):
    from transmogrifai_tpu import OpWorkflow
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.impl.tuning.splitters import DataBalancer

    label, checked = features(n_num, n_cat)
    grid = candidates()
    sel = BinaryClassificationModelSelector.with_cross_validation(
        splitter=DataBalancer(sample_fraction=0.1, reserve_test_fraction=0.1,
                              max_training_sample=max_train),
        num_folds=FOLDS, seed=42,
        models_and_parameters=grid)
    pred = sel.set_input(label, checked).get_output()
    wf = (OpWorkflow().set_result_features(pred).set_input_dataset(df)
          .with_selector_cv())
    return wf, sum(len(g) for _, g in grid)


def stage_times(listener) -> dict:
    """``stage.phase`` -> seconds, summed over the listener's stage metrics
    (vectorizer fits, SanityChecker streaming passes, selector sweep)."""
    times = {}
    for m in listener.metrics.stage_metrics:
        key = f"{m.stage_name}.{m.phase}"
        times[key] = round(times.get(key, 0.0) + m.duration_ms / 1e3, 2)
    return times


def main():
    from transmogrifai_tpu.utils.backend import compile_cache_dir, require_tpu

    dev = require_tpu("scale10m")
    compile_cache_dir()
    from transmogrifai_tpu.utils.listener import OpListener

    def log(msg):
        print(f"[scale10m +{time.perf_counter() - t_start:.0f}s] {msg}",
              file=sys.stderr, flush=True)

    t_start = time.perf_counter()
    phases = {}
    log(f"platform={dev['platform']} kind={dev['kind']} "
        f"devices={dev['count']} rows={N_ROWS}")
    t0 = time.perf_counter()
    df = synthesize(N_ROWS)
    phases["generate_s"] = round(time.perf_counter() - t0, 2)
    log(f"synthesized {N_ROWS} rows x {N_NUM + N_CAT} features")

    t0 = time.perf_counter()
    wf, n_cands = build(df)
    listener = OpListener(app_name="scale10m", collect_stage_metrics=True)
    _orig = listener.time_stage

    def _loud_time_stage(stage, phase, n_rows=0):
        log(f"stage {getattr(stage, 'operation_name', stage)}.{phase} ({n_rows} rows)")
        return _orig(stage, phase, n_rows)

    listener.time_stage = _loud_time_stage
    with listener.install():
        model = wf.train()
    phases["train_s"] = round(time.perf_counter() - t0, 2)
    log("train done")

    stage_walls = stage_times(listener)
    # read the winner straight off the fitted SelectedModel (no key spelunking)
    best_model = None
    for st in model.stages:
        s = getattr(st, "summary", None)
        if s is not None and getattr(s, "best_model_name", None):
            best_model = s.best_model_name
    sweep_s = next((v for k, v in stage_walls.items()
                    if "odelSelector" in k and k.endswith(".fit")), None)
    # width of the sanity-checked vector the selector trained on (the
    # selector's second input; the result feature itself is the Prediction)
    vec_width = None
    try:
        sel_stage = next(st for st in model.stages
                         if getattr(st, "summary", None) is not None)
        vcol = model.train_data[sel_stage.inputs[1].name]
        vec_width = int(vcol.values.shape[1])
    except Exception:
        pass
    # honest metric name: only a run at the full 10M rows may claim the
    # scale10m metric; smoke runs are labelled by their actual row count
    metric = ("scale10m_train_wall_clock" if N_ROWS >= 10_000_000
              else f"scale_smoke_{N_ROWS}_rows_train_wall_clock")
    out = {
        "metric": metric,
        "value": phases["train_s"],
        "unit": "s",
        "rows": N_ROWS, "raw_features": N_NUM + N_CAT,
        "vector_width": vec_width,
        "platform": dev["platform"], "device_kind": dev["kind"],
        "device_count": dev["count"],
        "phases": phases,
        "stage_times_s": stage_walls,
        "sweep_candidates": n_cands, "folds": FOLDS,
        "models_trained": n_cands * FOLDS,
        "sweep_s": sweep_s,
        "best_model": best_model,
    }
    # streaming-transform telemetry (workflow/stream.py): train() resets the
    # window, so these numbers are THIS run's — chunk counts + the <=1
    # steady-state compile prove the transform layers streamed rather than
    # falling back to per-stage host transforms above TMOG_FUSE_MAX_ROWS
    from transmogrifai_tpu.workflow import stream
    s = stream.stream_stats()
    if s["streams"]:
        out["stream"] = {
            "streams": s["streams"], "chunks": s["chunks"],
            "chunk_rows": s["chunk_rows"], "pad_rows": s["pad_rows"],
            "stages_fused": s["stages_fused"], "stages_host": s["stages_host"],
            "device_only": s["device_only"], "compiles": s["compiles"],
            "bytes_streamed_in": round(s["bytes_in"]),
            "bytes_streamed_out": round(s["bytes_out"]),
            "device_handoffs": s["device_handoffs"],
            "handoff_bytes": round(s["handoff_bytes"]),
            "transform_rows_per_sec": round(s["transform_rows_per_sec"]),
            "overlap_efficiency": round(s["overlap_efficiency"], 3),
            "fallbacks": s["fallbacks"],
            # mesh-sharded stream telemetry: shard count the router used,
            # host-prep walls (blocked share is what overlap_efficiency
            # reads from), winner-score stages routed through the sharded
            # head, and the per-device chunk/byte/wall split — an uneven
            # by_device map at scale means a straggling data shard
            "shards": s["shards"],
            "prep_s": round(s["prep_s"], 3),
            "prep_blocked_s": round(s["prep_blocked_s"], 3),
            "score_stages": s["score_stages"],
            "score_chunks": s["score_chunks"],
            "by_device": {
                k: {"chunks": v["chunks"], "rows": v["rows"],
                    "bytes_in": round(v["bytes_in"]),
                    "bytes_out": round(v["bytes_out"]),
                    "upload_s": round(v["upload_s"], 3),
                    "pull_wait_s": round(v["pull_wait_s"], 3)}
                for k, v in (s["by_device"] or {}).items()
            },
        }
    # sharded-vs-single score pass (the "modelSelector.transform is
    # single-chip" wall the mesh-sharded stream path attacks): when more
    # than one stream device is active, score the trained model over the
    # raw rows both ways and record the walls per stage — the single pass
    # pins TMOG_STREAM_ROUTE=single, the sharded pass uses the mesh
    try:
        from transmogrifai_tpu.parallel import mesh as pmesh
        if len(pmesh.stream_devices()) > 1:
            def _timed_score(tag):
                lst = OpListener(app_name=f"scale10m-score-{tag}",
                                 collect_stage_metrics=True)
                stream.reset_stream_stats()
                t0 = time.perf_counter()
                with lst.install():
                    model.score(df)
                wall = time.perf_counter() - t0
                per_stage = {}
                for m in lst.metrics.stage_metrics:
                    key = f"{m.stage_name}.{m.phase}"
                    per_stage[key] = round(per_stage.get(key, 0.0)
                                           + m.duration_ms / 1e3, 2)
                return wall, per_stage, stream.stream_stats()

            os.environ["TMOG_STREAM_ROUTE"] = "single"
            single_s, single_stages, _ = _timed_score("single")
            os.environ.pop("TMOG_STREAM_ROUTE", None)
            sharded_s, sharded_stages, ss = _timed_score("sharded")
            out["score_walls"] = {
                "single_s": round(single_s, 2),
                "sharded_s": round(sharded_s, 2),
                "speedup": round(single_s / max(sharded_s, 1e-9), 2),
                "shards": ss["shards"],
                "score_stages": ss["score_stages"],
                "score_chunks": ss["score_chunks"],
                "single_stage_s": single_stages,
                "sharded_stage_s": sharded_stages,
                "by_device": {k: v["chunks"]
                              for k, v in (ss["by_device"] or {}).items()},
            }
            log(f"score single {single_s:.2f}s sharded {sharded_s:.2f}s")
    except Exception as e:  # telemetry must never fail the scale run
        out["score_walls"] = {"error": str(e)}
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "SCALE_r05.json"), "w") as f:
        json.dump(out, f, indent=1)
    from transmogrifai_tpu import obs

    obs.write_record("scale", extra={"report": out})


if __name__ == "__main__":
    main()
