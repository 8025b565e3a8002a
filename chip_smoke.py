#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that train -> score -> serve still runs
on the chip, through the entry points a user calls.

One process (the only one that touches JAX), data from a seed, weights
learned in the run.  Default run, on one chip:

- **A** the app path at full grid: ``helloworld/titanic.py``'s
  ``OpAppWithRunner`` harness, run types ``train`` then ``score`` — typed
  features -> transmogrify -> SanityChecker -> the reference default
  selector grid uncut (LR 8 + RF 18 + XGB 2 = 28 candidates x 3 folds) ->
  save -> load -> score.
- **B** real width: the ``scale10m.py`` pipeline (500 raw features, its
  64-candidate 5-fold LR + SVC + MLP grid, streamed transforms, streaming
  SanityChecker stats, winner scoring over all rows) at ``--rows`` rows.
- **C** serve: ``ModelRegistry.deploy`` of A's saved model behind
  ``ModelServer``, ``POST /score`` over HTTP at 1, 3 and 64 rows,
  ``/metrics``, and a second deploy that must come from the AOT tier with
  zero compiles.

``--chips 4`` runs instead, and only, the paths that exist across chips:
the selector sweep on the default mesh and on an explicit 2x2 mesh against
the same sweep on one device, and one serve replica per chip.

Every earlier line of stdout is one JSON object of facts (stage walls,
compile seconds, cache hits, peak bytes).  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`` and
the exit code 0 only when every phase ran, every check held, the platform
is ``tpu``, the device count is what ``--chips`` says and phase B ran at no
fewer than ``PHASE_B_MIN_ROWS`` rows.  Anything else — a CPU rehearsal at a
tiny ``--rows`` included — ends ``"ok": false`` and non-zero.  Without a TPU
and without ``--rows`` it stops before the phases: the real size on a CPU
proves nothing and takes hours.
"""
from __future__ import annotations

import argparse
import dataclasses
import faulthandler
import json
import logging
import os
import sys
import tempfile
import time
import traceback
import urllib.request

# ---- the shapes this script launches (tests/test_tpu_compile.py compiles
# ---- the same programs for the v5e from these constants) -----------------
#: phase A: the reference default binary grid, uncut
TITANIC_FOLDS = 3
TITANIC_CANDIDATES = 28
#: .claude/skills/verify/SKILL.md: the synthetic Titanic frame scores >= 0.70
AUROC_FLOOR = 0.70
#: phase B rows: width and grid are never cut, rows are what the 1200 s
#: limit allows with room for a slow host (CHANGES.md, PR 23, has the walls
#: that set it).  Past the 200k fuse-row threshold the transforms stream
#: (workflow/dag._fuse_max_rows); never below PHASE_B_MIN_ROWS.
PHASE_B_ROWS = 1_000_000
PHASE_B_MIN_ROWS = 100_000
PHASE_B_CANDIDATES = 64
#: scale10m's DataBalancer reserves this fraction as the holdout
PHASE_B_HOLDOUT = 0.1
#: phase C
SERVE_MAX_BATCH = 64
SERVE_REQUEST_ROWS = (1, 3, 64)
SERVE_REQUESTS = 36
#: four-chip sweep parity, as the mesh tests pin it: tree candidates within
#: tests/test_hist_subtract_parity.TREE_METRIC_ATOL (histogram subtraction
#: is on by default), linear ones within tests/test_mesh_selector's bound
TREE_METRIC_ATOL = 0.05
LINEAR_METRIC_RTOL, LINEAR_METRIC_ATOL = 1e-4, 1e-5


def phase_b_sweep_rows(rows: int) -> int:
    """Rows phase B's selector sweeps: the train split, under scale10m's
    training-sample cap."""
    import scale10m

    return min(rows - int(rows * PHASE_B_HOLDOUT), scale10m.FULL_MAX_TRAIN)


def emit(**facts) -> None:
    print(json.dumps(facts, default=float), flush=True)


class CheckFailed(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


class CompileCounter:
    """Programs jax compiled or read from its persistent cache, from jax's
    own monitoring events (every backend compile request, cache hits)."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.seconds = self.saved_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += secs

    def snapshot(self) -> dict:
        return {"programs": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "compile_s": round(self.seconds, 2),
                "saved_s": round(self.saved_s, 2)}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 2) for k in now}


class WarningLog(logging.Handler):
    """The package logs (does not raise) when the fused sweep falls back to
    the per-family path; the smoke treats that as a failed check."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.messages = []
        logging.getLogger("transmogrifai_tpu").addHandler(self)

    def emit(self, record):
        self.messages.append(record.getMessage())

    def fused_fallbacks(self):
        return [m for m in self.messages if "fused sweep" in m
                or "Batched grid fit failed" in m]


@dataclasses.dataclass
class Run:
    """What every phase shares."""
    work: str            # scratch directory for saved models and scores
    seed: int
    compiles: CompileCounter
    warnings: WarningLog


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def selector_summary(model):
    return next(s.summary for s in model.stages
                if getattr(s, "summary", None) is not None)


def titanic_app(kept: list):
    """helloworld's app, keeping the in-memory model ``train`` produced (the
    runner saves it and drops it)."""
    from helloworld.titanic import OpTitanicSimple

    class App(OpTitanicSimple):
        def build_runner(self):
            runner = super().build_runner()
            train = runner.workflow.train

            def train_and_keep(*a, **kw):
                model = train(*a, **kw)
                kept.append(model)
                return model

            runner.workflow.train = train_and_keep
            return runner

    return App()


# ---------------------------------------------------------------------------
# Phase A — the app path at full grid
# ---------------------------------------------------------------------------
def phase_a(run: Run) -> dict:
    import numpy as np

    from transmogrifai_tpu.ops import sweep

    kept: list = []
    app = titanic_app(kept)
    model_dir = os.path.join(run.work, "titanic_model")
    score_dir = os.path.join(run.work, "titanic_scores")

    t0 = time.perf_counter()
    app.main(["--run-type", "train", "--model-location", model_dir])
    train_s = time.perf_counter() - t0
    stats = sweep.run_stats()
    summary = selector_summary(kept[0])
    results = summary.validation_results
    check(stats["launches"] and all(
        launch["candidates"] == TITANIC_CANDIDATES
        for launch in stats["launches"]),
        f"fused launch did not run the full grid: {stats['launches']}")
    check(stats["fallbacks"] == [], f"sweep fallbacks: {stats['fallbacks']}")
    check(not run.warnings.fused_fallbacks(),
          f"fused sweep fell back: {run.warnings.fused_fallbacks()}")
    check(len(results) == TITANIC_CANDIDATES
          and all(len(r["foldMetrics"]) == TITANIC_FOLDS for r in results),
          "selector summary is not 28 candidates x 3 folds")

    t0 = time.perf_counter()
    res = app.main(["--run-type", "score", "--model-location", model_dir,
                    "--write-location", score_dir])
    score_s = time.perf_counter() - t0
    auroc = float(res.metrics["AuROC"])
    check(np.isfinite(auroc) and auroc > AUROC_FLOOR,
          f"AuROC {auroc} not above {AUROC_FLOOR}")

    # re-scored predictions (saved -> loaded model, via the app) equal the
    # in-memory model's
    with open(os.path.join(score_dir, "scores.json")) as f:
        rescored = json.load(f)
    name = kept[0].result_features[0].name
    col = kept[0].score()[name]
    check(len(rescored) == len(col) == res.n_scored, "row counts differ")
    got_pred = np.array([r[name]["prediction"] for r in rescored])
    got_prob = np.array([r[name]["probability_1"] for r in rescored])
    check(np.array_equal(got_pred, col.prediction),
          "loaded model's predictions differ from the in-memory model's")
    prob_diff = float(np.abs(got_prob - col.probability[:, 1]).max())
    check(prob_diff <= 1e-6, f"probabilities differ by {prob_diff}")
    return {"train_s": round(train_s, 2), "score_s": round(score_s, 2),
            "rows": res.n_scored, "auroc": round(auroc, 4),
            "winner": summary.best_model_name, "winner_grid": summary.best_grid,
            "launches": len(stats["launches"]),
            "rescore_max_prob_diff": prob_diff, "model_dir": model_dir}


# ---------------------------------------------------------------------------
# Phase B — real width
# ---------------------------------------------------------------------------
def phase_b(run: Run, rows: int) -> dict:
    import numpy as np

    import scale10m
    from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
    from transmogrifai_tpu.ops import sweep
    from transmogrifai_tpu.utils.listener import OpListener
    from transmogrifai_tpu.workflow import dag, stream

    width = dict(n_num=scale10m.FULL_NUM, n_cat=scale10m.FULL_CAT)
    t0 = time.perf_counter()
    df = scale10m.synthesize(rows, seed=run.seed, **width)
    generate_s = time.perf_counter() - t0

    wf, n_cands = scale10m.build(df, max_train=scale10m.FULL_MAX_TRAIN,
                                 **width)
    check(n_cands == PHASE_B_CANDIDATES, f"grid was cut to {n_cands}")
    listener = OpListener(app_name="chip_smoke.B", collect_stage_metrics=True)
    n_warn = len(run.warnings.fused_fallbacks())
    t0 = time.perf_counter()
    with listener.install():
        model = wf.train()
    train_s = time.perf_counter() - t0
    sweep_stats = sweep.run_stats()
    train_stream = stream.stream_stats()
    summary = selector_summary(model)
    results = summary.validation_results
    check(len(results) == PHASE_B_CANDIDATES
          and all(len(r["foldMetrics"]) == scale10m.FOLDS for r in results),
          "selector summary is not 64 candidates x 5 folds")
    check(sum(launch["candidates"] for launch in sweep_stats["launches"])
          == PHASE_B_CANDIDATES and sweep_stats["fallbacks"] == []
          and len(run.warnings.fused_fallbacks()) == n_warn,
          f"fused sweep did not run the grid: {sweep_stats['launches']} "
          f"{sweep_stats['fallbacks']} "
          f"{run.warnings.fused_fallbacks()[n_warn:]}")
    # a stream that declines (a one-stage layer has nothing to fuse) records
    # why; one that broke records a ``*_failed`` reason
    declined = sorted({f["reason"] for f in train_stream["fallbacks"]})
    check(not any(r.endswith("_failed") for r in declined),
          f"stream failures: {train_stream['fallbacks']}")
    if rows > dag._fuse_max_rows():  # past it the transforms must stream
        check(train_stream["streams"] >= 1
              and train_stream["stages_fused"] >= 2,
              f"transforms did not stream: {train_stream}")
    sel_stage = next(s for s in model.stages
                     if getattr(s, "summary", None) is not None)
    # past the fuse-row threshold train_data keeps no intermediate column:
    # the width is what the fitted SanityChecker lets through
    vec_width = len(model.get_update_stage_of(
        sel_stage.inputs[1].name).indices_to_keep)

    # two identical transform + winner-scoring passes over all rows; the
    # second must compile nothing
    name = model.result_features[0].name
    passes = []
    for _ in range(2):
        stream.reset_stream_stats()
        before = run.compiles.snapshot()
        t0 = time.perf_counter()
        scored = model.score(df)[name]
        wall = time.perf_counter() - t0
        s = stream.stream_stats()
        passes.append({"wall_s": round(wall, 2), "chunks": s["chunks"],
                       "chunk_rows": s["chunk_rows"],
                       "stream_compiles": s["compiles"],
                       "programs": run.compiles.since(before)["programs"]})
    check(passes[1]["stream_compiles"] == 0 and passes[1]["programs"] == 0,
          f"second identical pass compiled: {passes[1]}")
    check(len(scored) == rows and scored.probability.shape == (rows, 2)
          and np.isfinite(scored.probability).all(),
          "winner scores are not finite [rows, 2]")
    y = np.asarray(df["label"].values)
    auroc = float(OpBinaryClassificationEvaluator().evaluate_arrays(
        y, scored.prediction, scored.probability)["AuROC"])
    check(auroc > AUROC_FLOOR, f"AuROC {auroc} over all rows")
    # the same rows through the per-layer path (below the fuse-row threshold
    # nothing streams): the streamed pass must agree with it
    head = 2048
    ref = model.score(df.head(head))[name]
    head_diff = float(np.abs(ref.probability - scored.probability[:head]).max())
    check(head_diff <= 1e-4,
          f"streamed scores differ from the per-layer path by {head_diff}")
    return {"rows": rows, "raw_features": sum(width.values()),
            "vector_width": vec_width, "candidates": n_cands,
            "folds": scale10m.FOLDS, "sweep_rows": phase_b_sweep_rows(rows),
            "generate_s": round(generate_s, 2), "train_s": round(train_s, 2),
            "stage_walls_s": dict(sorted(
                scale10m.stage_times(listener).items(),
                key=lambda kv: -kv[1])[:8]),
            "sweep_launches": [
                {k: launch.get(k) for k in ("candidates", "split")}
                for launch in sweep_stats["launches"]],
            "train_stream": {k: train_stream[k] for k in (
                "streams", "chunks", "chunk_rows", "stages_fused",
                "compiles", "device_handoffs")},
            "stream_declined": declined,
            "score_passes": passes, "winner": summary.best_model_name,
            "auroc_all_rows": round(auroc, 4),
            "streamed_vs_per_layer_max_diff": head_diff}


# ---------------------------------------------------------------------------
# Phase C — serve
# ---------------------------------------------------------------------------
def _post(url: str, body) -> tuple:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.load(resp)


def _serve_frame():
    from helloworld.titanic import titanic_data

    return titanic_data().drop(columns=["Survived"])


def _serve_records(frame, n: int, seed: int) -> list:
    rows = frame.sample(n=n, random_state=seed).to_dict(orient="records")
    return json.loads(json.dumps(rows, default=lambda v: v.item()))


def _scores_equal(got: list, want: list) -> float:
    """Largest absolute difference between two lists of score dicts."""
    worst = 0.0
    check(len(got) == len(want), "reply length differs from request")
    for g, w in zip(got, want):
        check(g is not None and g.keys() == w.keys(), f"reply {g} vs {w}")
        for name in w:
            for k, v in w[name].items():
                worst = max(worst, abs(float(g[name][k]) - float(v)))
    return worst


def _deploy(model_dir: str, replicas=None):
    from transmogrifai_tpu.serve import (ModelRegistry, ServeMetrics,
                                         compile_cache)
    from transmogrifai_tpu.workflow.model import load_model

    compile_cache.reset_cache_stats()
    registry = ModelRegistry(max_batch=SERVE_MAX_BATCH, metrics=ServeMetrics(),
                             replicas=replicas)
    t0 = time.perf_counter()
    entry = registry.deploy(load_model(model_dir))
    stats = compile_cache.cache_stats()
    facts = {"deploy_s": round(time.perf_counter() - t0, 2),
             **{k: round(stats[k], 2) for k in (
                 "hits", "misses", "compiles", "compile_s", "load_s",
                 "saves", "save_errors")},
             "fallbacks": [f["reason"] for f in stats["fallbacks"]]}
    return registry, entry, facts


def phase_c(run: Run, model_dir: str) -> dict:
    from transmogrifai_tpu.local import batch_score_function
    from transmogrifai_tpu.serve import ModelServer

    registry, entry, first = _deploy(model_dir)
    check(entry.warmed and all(r.scorer is not None for r in entry.replicas),
          "replicas did not warm through the AOT bucket scorer")
    reference = batch_score_function(entry.model)
    server = ModelServer(registry, max_batch=SERVE_MAX_BATCH).start()
    frame, worst, latencies = _serve_frame(), 0.0, []
    try:
        for i in range(SERVE_REQUESTS):
            n = SERVE_REQUEST_ROWS[i % len(SERVE_REQUEST_ROWS)]
            records = _serve_records(frame, n, run.seed + i)
            t0 = time.perf_counter()
            status, reply = _post(f"{server.url}/score",
                                  records[0] if n == 1 else records)
            latencies.append(time.perf_counter() - t0)
            check(status == 200, f"POST /score -> {status}: {reply}")
            got = [reply["score"]] if n == 1 else reply["scores"]
            worst = max(worst, _scores_equal(got, reference(records)))
        with urllib.request.urlopen(f"{server.url}/metrics", timeout=30) as r:
            check(r.status == 200, "/metrics")
            serve = json.load(r)["serve"]
    finally:
        server.stop()
    check(worst <= 1e-6,
          f"served scores differ from batch_score_function by {worst}")
    check(serve["responses"] >= SERVE_REQUESTS and serve["errors"] == 0,
          f"/metrics: {serve['responses']} responses, "
          f"{serve['errors']} errors")
    check(len(serve["bucket_counts"]) > 1,
          f"one shape bucket only: {serve['bucket_counts']}")

    # the same model again, AOT tier on: every executable must deserialize
    _, _, warm = _deploy(model_dir)
    check(warm["compiles"] == 0 and warm["misses"] == 0 and warm["hits"] > 0,
          f"warm re-deploy compiled: {warm}")
    latencies.sort()
    return {"first_deploy": first, "second_deploy": warm,
            "requests": SERVE_REQUESTS, "request_rows": SERVE_REQUEST_ROWS,
            "bucket_counts": serve["bucket_counts"],
            "max_score_diff": worst,
            "request_median_ms": round(
                1e3 * latencies[len(latencies) // 2], 2)}


# ---------------------------------------------------------------------------
# Four chips — the paths that exist only across chips
# ---------------------------------------------------------------------------
def _metric_parity(sel, meshed, single) -> dict:
    import numpy as np

    from transmogrifai_tpu.impl.classification.logistic import (
        OpLogisticRegression)

    check(meshed.best.model_name == single.best.model_name
          and meshed.best.grid == single.best.grid,
          f"winner differs: {meshed.best.model_name} {meshed.best.grid} vs "
          f"{single.best.model_name} {single.best.grid}")
    linear_uids = {est.uid for est, _ in sel.models
                   if isinstance(est, OpLogisticRegression)}
    diffs = {"linear": 0.0, "tree": 0.0}
    for rm, rs in zip(meshed.results, single.results):
        fam = "linear" if rs.model_uid in linear_uids else "tree"
        diffs[fam] = max(diffs[fam], abs(rm.metric_value - rs.metric_value))
        ok = (np.isclose(rm.metric_value, rs.metric_value,
                         rtol=LINEAR_METRIC_RTOL, atol=LINEAR_METRIC_ATOL)
              if fam == "linear" else
              abs(rm.metric_value - rs.metric_value) <= TREE_METRIC_ATOL)
        check(ok, f"{rs.model_name} {rs.grid}: {rm.metric_value} on the mesh "
                  f"vs {rs.metric_value} on one device")
    return {"winner": single.best.model_name,
            "max_diff_linear": diffs["linear"], "max_diff_tree": diffs["tree"]}


def phase_multichip(run: Run) -> dict:
    import jax
    import numpy as np

    from helloworld.titanic import build_workflow, titanic_data
    from transmogrifai_tpu.ops import sweep
    from transmogrifai_tpu.parallel.mesh import (make_mesh, serve_chip_index,
                                                 serve_devices)
    from transmogrifai_tpu.readers import DataReaders

    # (a) phase A's selector sweep: one device, the default mesh, 2x2
    wf, pred = build_workflow()
    sel = pred.origin_stage
    label_f, vec_f = sel.inputs
    wf.set_reader(DataReaders.Simple.custom(titanic_data(),
                                            key="PassengerId"))
    data = wf.compute_data_up_to(vec_f, label_f)
    X = np.asarray(data[vec_f.name].values, np.float32)
    y = np.asarray(data[label_f.name].values, np.float32)
    validator = sel.validator
    runs, facts = {}, {"X": list(X.shape)}
    for tag, mesh in (("one_device", None), ("default_mesh", "auto"),
                      ("mesh_2x2", make_mesh(n_data=2, n_model=2))):
        validator.mesh = mesh
        before = run.compiles.snapshot()
        t0 = time.perf_counter()
        try:
            runs[tag] = validator.validate(sel.models, X, y)
        except Exception as e:
            raise CheckFailed(f"{tag}: {type(e).__name__}: {e}") from e
        wall = time.perf_counter() - t0
        stats = sweep.run_stats()
        check(stats["fallbacks"] == []
              and not run.warnings.fused_fallbacks(),
              f"{tag}: {stats['fallbacks']} {run.warnings.fused_fallbacks()}")
        shard_devs = [p.get("devices") or [p["device"]]
                      for launch in stats["launches"]
                      for p in launch.get("per_shard", [])]
        n_devs = len({d for ds in shard_devs for d in ds})
        facts[tag] = {"wall_s": round(wall, 2),
                      "sweep_shards": stats["sweep_shards"],
                      "data_shards": stats["data_shards"],
                      "shard_devices": shard_devs,
                      "compiles": run.compiles.since(before)}
        if tag == "default_mesh":
            check(stats["sweep_shards"] == 4 and n_devs == 4,
                  f"default mesh: {stats['sweep_shards']} shards on "
                  f"{shard_devs}")
        if tag == "mesh_2x2":
            axes = sorted({ax for launch in stats["launches"]
                           for ax in launch.get("collectives", {})})
            check(stats["data_shards"] == 2 and stats["sweep_shards"] == 2
                  and axes == ["data"] and n_devs == 4,
                  f"2x2 mesh: data_shards {stats['data_shards']}, "
                  f"collective axes {axes}, devices {shard_devs}")
            facts[tag]["collective_axes"] = axes
        if tag != "one_device":
            facts[tag]["parity"] = _metric_parity(sel, runs[tag],
                                                  runs["one_device"])
        # one line per sweep as it finishes: a chip that halts takes the
        # process with it, and the phase line below would never print
        emit(phase="multichip", sweep=tag, **facts.pop(tag))
    validator.mesh = "auto"

    # (b) the winner, refit through the workflow, served from one replica
    # per chip
    best = runs["one_device"].best
    sel.models = [(est, [best.grid]) for est, _ in sel.models
                  if est.uid == best.model_uid]
    model_dir = os.path.join(run.work, "winner_model")
    wf.train().save(model_dir)
    registry, entry, first = _deploy(model_dir)
    devices = serve_devices()
    chips = serve_chip_index(devices)
    check(len(entry.replicas) == 4 and chips == [0, 1, 2, 3]
          and [r.device for r in entry.replicas] == devices,
          f"replica slots {chips} on {devices}")
    records = _serve_records(_serve_frame(), SERVE_REQUEST_ROWS[-1],
                             run.seed)
    outs = [r.score(list(records)) for r in entry.replicas]
    check(all(o == outs[0] for o in outs[1:]),
          "replicas disagree on the same records")
    resident = {d.id for a in jax.live_arrays() for d in a.devices()}
    check({d.id for d in devices} <= resident,
          f"replica arrays live on devices {sorted(resident)} only")
    _, _, warm = _deploy(model_dir)
    check(warm["compiles"] == 0 and warm["misses"] == 0 and warm["hits"] > 0,
          f"warm re-deploy compiled: {warm}")
    facts["serve"] = {"replica_devices": [str(d) for d in devices],
                      "chip_index": chips, "first_deploy": first,
                      "second_deploy": warm}
    return facts


# ---------------------------------------------------------------------------
def run_phase(run: Run, tag: str, fn, *args):
    """Run one phase; its facts (or its failure) go on their own line."""
    before = run.compiles.snapshot()
    t0 = time.perf_counter()
    try:
        facts = fn(run, *args)
        ok, err = True, None
    except Exception as e:  # noqa: BLE001 — a failed phase is a result
        facts, ok = {}, False
        err = f"{type(e).__name__}: {e}"
        traceback.print_exc()
    emit(phase=tag, ok=ok,
         **({"error": err, "warnings": run.warnings.messages[-6:]}
            if err else {}),
         wall_s=round(time.perf_counter() - t0, 2),
         jax_compiles=run.compiles.since(before),
         peak_bytes_in_use=peak_bytes(), **facts)
    return ok, facts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase (builder's run)")
    ap.add_argument("--rows", type=int, default=None,
                    help=f"phase B rows (default {PHASE_B_ROWS}); below "
                         f"{PHASE_B_MIN_ROWS} is a rehearsal and ends "
                         "\"ok\": false")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from transmogrifai_tpu import native
    from transmogrifai_tpu.utils import backend

    faulthandler.enable()  # a fatal signal from the runtime leaves a trace
    device = backend.device_summary()
    on_chip = device["platform"] == "tpu" and device["count"] == args.chips
    if not on_chip and args.rows is None and args.chips == 1:
        emit(ok=False, error=f"needs {args.chips} TPU chip(s), JAX selected "
                             f"{device}; pass --rows to rehearse")
        print(json.dumps({"ok": False, "device": device}))
        return 1
    rows = PHASE_B_ROWS if args.rows is None else args.rows

    compiles = CompileCounter()
    cache_dir = backend.compile_cache_dir()
    # the serving tier's serialized executables live under the same root
    os.environ["TMOG_COMPILE_CACHE"] = os.path.join(backend.cache_root(),
                                                    "aotx")
    emit(device=device, chips=args.chips, rows=rows, seed=args.seed,
         peaks=(backend.device_peaks(device["kind"], device["platform"])
                if device["platform"] == "tpu" else None),
         jax_cache_dir=cache_dir, aot_cache_dir=os.environ["TMOG_COMPILE_CACHE"],
         native="library" if native.lib is not None else "python fallback")

    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        run = Run(work, args.seed, compiles, WarningLog())
        if args.chips == 4:
            oks = [run_phase(run, "multichip", phase_multichip)[0]]
        else:
            ok_a, a = run_phase(run, "A", phase_a)
            ok_b, _ = run_phase(run, "B", phase_b, rows)
            ok_c = ok_a and run_phase(run, "C", phase_c, a["model_dir"])[0]
            oks = [ok_a, ok_b, ok_c]
    real_size = args.chips == 4 or rows >= PHASE_B_MIN_ROWS
    emit(total_s=round(time.perf_counter() - t_all, 2),
         jax_compiles=compiles.snapshot(), peak_bytes_in_use=peak_bytes(),
         phases_ok=oks, on_chip=on_chip, real_size=real_size)
    ok = all(oks) and on_chip and real_size
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
