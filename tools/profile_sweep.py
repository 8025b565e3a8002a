"""Per-family wall-clock profile of the Titanic default sweep (dev tool).

``--shards N`` instead partitions the default fused spec with the SAME cost
model the multi-chip sweep uses (parallel/spec_partition) and prints
predicted vs MEASURED per-shard cost — each shard run sequentially on one
device — so partitioner balance regressions are diagnosable without a pod.

``--data-shards D`` (optionally with ``--shards M``) launches the REAL
row-sharded sweep on a (D x M) mesh of local devices and prints, per model
column, predicted vs measured wall plus the per-axis collective bytes and
the replicated-vs-rowsharded peak per-device X/y bytes — the memory claim
the data axis exists to make.  On CPU use
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import argparse
import os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import titanic_arrays
from transmogrifai_tpu.utils.backend import device_summary

args = argparse.ArgumentParser(description=__doc__)
args.add_argument("--shards", type=int, default=0,
                  help="partition the default grid into N cost-balanced "
                       "shards and print predicted vs measured per-shard "
                       "cost (0 = legacy per-family profile)")
args.add_argument("--data-shards", type=int, default=0,
                  help="row-shard the default sweep over a (D x max(shards,1)) "
                       "mesh and print per-axis collective bytes + "
                       "replicated-vs-rowsharded peak per-device bytes")
args.add_argument("--costmodel", action="store_true",
                  help="predict-before-compile: load the trained cost model "
                       "(TMOG_COSTMODEL_PATH) and print predicted per-shard "
                       "wall BEFORE compiling, then predicted-vs-measured "
                       "error (MAPE, makespan ratio) after the run")
args = args.parse_args()

platform = device_summary()["platform"]
print("platform:", platform)

from transmogrifai_tpu.evaluators.classification import OpBinaryClassificationEvaluator
from transmogrifai_tpu.impl.classification.logistic import OpLogisticRegression
from transmogrifai_tpu.impl.classification.trees import (
    OpRandomForestClassifier, OpXGBoostClassifier)
from transmogrifai_tpu.impl.selector import defaults as D
from transmogrifai_tpu.impl.tuning.validators import OpCrossValidation

X, y = titanic_arrays()
print("X", X.shape)

ev = OpBinaryClassificationEvaluator()


def timed(name, candidates, reps=3):
    cv = OpCrossValidation(ev, num_folds=3, seed=42)
    t0 = time.perf_counter()
    cv.validate(candidates, X, y)
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in range(reps):
        cv = OpCrossValidation(ev, num_folds=3, seed=100 + r)
        cv.validate(candidates, X, y)
    dt = (time.perf_counter() - t0) / reps
    n = sum(len(g) for _, g in candidates)
    print(f"{name:30s} grids={n:3d} warm={warm:7.2f}s steady={dt:7.3f}s"
          f"  ({3*n/dt:8.1f} models/s)")
    return dt


def _print_gbt_telemetry(sweep_ops) -> None:
    """Critical-path telemetry: sequential GBT chain + histogram subtraction."""
    from transmogrifai_tpu.utils import flops
    chains = [l["gbt_chain"] for l in sweep_ops.run_stats()["launches"]
              if l.get("gbt_chain")]
    if chains:
        ch = max(chains, key=lambda c: c["levels"])
        print(f"gbt chain: {ch['steps']} sequential boosting steps = "
              f"{ch['levels']} levels (TMOG_GBT_ROUND_COLLAPSE shortens)")
    hs = flops.hist_subtracted_totals()
    if hs.get("levels"):
        print(f"hist subtraction: {hs['levels']} level-builds halved, "
              f"~{hs['flops_avoided']:,} hist flops avoided "
              "(TMOG_HIST_SUBTRACT=0 disables)")


def _print_hedge_telemetry(sweep_ops) -> dict:
    """Straggler-defense telemetry: hedges fired, discarded loser wall, and
    the per-device health EWMAs feeding the next partition.  Returns the
    dict that rides in the run's JSONL record."""
    from transmogrifai_tpu.resilience import health as _health

    stats = sweep_ops.run_stats()
    out = {"hedges_fired": int(stats.get("hedges_fired") or 0),
           "hedge_wasted_s": round(float(stats.get("hedge_wasted_s") or 0.0),
                                   4)}
    snap = _health.tracker().snapshot()
    if snap.get("devices"):
        out["device_health"] = snap
    if out["hedges_fired"]:
        print(f"hedges: {out['hedges_fired']} fired, "
              f"{out['hedge_wasted_s']:.3f}s loser wall discarded "
              "(TMOG_HEDGE=0 disables)")
    for dev, h in (snap.get("devices") or {}).items():
        if h.get("slowdown", 1.0) > 1.5:
            print(f"  device {dev}: slowdown~{h['slowdown']:.2f}x "
                  f"({h.get('observations', 0)} obs)")
    return out


def _print_pack_telemetry(sweep_ops) -> dict:
    """MFU-gap telemetry (PR 17): candidate packing + GBT pipelining.
    Returns the dict that rides in the run's JSONL record."""
    stats = sweep_ops.run_stats()
    out = {"sweep_pack_count": int(stats.get("sweep_pack_count") or 0),
           "launches_avoided": int(stats.get("launches_avoided") or 0),
           "gbt_sequential_launches":
               int(stats.get("gbt_sequential_launches") or 0)}
    if out["sweep_pack_count"]:
        packed = out["sweep_pack_count"] + out["launches_avoided"]
        print(f"packing: {packed} candidates in {out['sweep_pack_count']} "
              f"packed launches ({out['launches_avoided']} launches avoided; "
              "TMOG_SWEEP_PACK=0 disables)")
    effs = [l["gbt_chain_eff"] for l in stats.get("launches") or []
            if l.get("gbt_chain_eff")]
    if effs:
        eff = max(effs, key=lambda e: e["levels"])
        out["gbt_overlap_fraction"] = eff.get("overlap_fraction", 0.0)
        print(f"gbt pipeline: {eff['levels']} effective sequential levels "
              f"(overlap~{out['gbt_overlap_fraction']:.0%}; "
              "TMOG_GBT_PIPELINE=0 disables)")
    return out


def _load_costmodel():
    """The trained artifact at TMOG_COSTMODEL_PATH, or None (with a note)."""
    from transmogrifai_tpu import costmodel as cm
    from transmogrifai_tpu.costmodel.model import CostModel

    path = cm.model_path()
    try:
        model = CostModel.load(path)
    except Exception as e:
        print(f"costmodel: cannot load {path} ({e}); train one with "
              "`python -m transmogrifai_tpu.costmodel`")
        return None
    print(f"costmodel: {path} (n_samples={model.n_samples}, "
          f"t0={model.t0:.3e})")
    return model


def profile_shards(n_shards: int, reps: int = 3,
                   use_costmodel: bool = False):
    """Predicted vs measured per-shard cost of the default 28-candidate grid.

    Returns ``(cm_eval, bubble_report, roofline)``: the predicted-vs-
    measured eval dict (MAPE, makespan ratios) when ``--costmodel``
    supplied a trained model, the timeline bubble report over the measured
    window, and the launch-ledger roofline report — all appended to the
    run's JSONL record."""
    import jax

    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.obs import ledger, timeline, trace
    from transmogrifai_tpu.ops.sweep import run_sweep
    from transmogrifai_tpu.parallel.spec_partition import (partition_spec,
                                                           predicted_balance)

    cands = [(OpLogisticRegression(max_iter=50), D.logistic_regression_grid()),
             (OpRandomForestClassifier(), D.random_forest_grid()),
             (OpXGBoostClassifier(), D.xgboost_grid())]
    F = 3
    cv = OpCrossValidation(ev, num_folds=F, seed=42)
    train_w, val_mask = cv.make_folds(len(y), None)
    plan = build_sweep_plan(cands, np.ascontiguousarray(X, np.float32), y,
                            train_w, ev)
    if plan is None:
        print("default grid did not build a fused plan; nothing to profile")
        return None, None, None
    from transmogrifai_tpu.ops import sweep as sweep_ops
    from transmogrifai_tpu.utils import flops
    flops.enable()
    flops.reset()
    ledger.enable()
    ledger.reset()
    sweep_ops.reset_run_stats()
    shards = partition_spec(plan.spec, plan.blob, n_shards, plan.n_rows,
                            plan.n_features, F)
    mx, mean = predicted_balance(shards)
    print(f"shards={len(shards)} predicted max/mean={mx / max(mean, 1e-9):.3f}")
    model = _load_costmodel() if use_costmodel else None
    model_preds = []
    if model is not None:
        # predict-before-compile: the learned wall estimate exists BEFORE
        # any XLA lowering — this is what a scheduler could use to skip or
        # re-balance a pathological partition up front
        from transmogrifai_tpu.costmodel.features import shard_feature_dict
        devs = jax.devices()
        ctx = {"device_count": float(len(devs)),
               "is_tpu": 1.0 if devs[0].platform == "tpu" else 0.0}
        for i, sh in enumerate(shards):
            feat = shard_feature_dict(sh.spec, plan.n_rows, plan.n_features,
                                      F)
            feat.update(ctx)
            model_preds.append(model.predict(feat))
        print("predict-before-compile (learned):")
        for i, p in enumerate(model_preds):
            print(f"  shard {i}: wall~{p['wall_s']:.4f}s "
                  f"compile~{p['compile_s']:.2f}s "
                  f"calib~{p['calib_wall_s']:.4f}s")
    tw = np.asarray(train_w, np.float32)
    vw = np.asarray(val_mask, np.float32)
    trace_was_on = trace.enabled()
    if not trace_was_on:
        trace.enable(path=None)  # in-memory only: feed the bubble profiler
    walls = []
    t_win = time.perf_counter()
    with trace.span("profile.window", shards=len(shards), reps=reps):
        for i, sh in enumerate(shards):
            # sequential, all on the default device: isolates per-shard COST
            # (the thing the partitioner predicts) from device contention
            with trace.span("sweep.compile", shard=i):
                out = run_sweep(sh.spec, plan.X, plan.xbs, plan.y, tw, vw,
                                sh.blob)
                np.asarray(out)  # warm (compile)
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run_sweep(sh.spec, plan.X, plan.xbs, plan.y, tw, vw,
                                sh.blob)
                with trace.span("sweep.gather", shard=i) as _gsp:
                    out = np.asarray(out)
                    _gsp.set(bytes=int(out.nbytes))
            walls.append((time.perf_counter() - t0) / reps)
    wall_meas = time.perf_counter() - t_win
    wmean = float(np.mean(walls))
    print(f"{'shard':>5s} {'cands':>5s} {'predicted':>12s} {'pred/mean':>9s} "
          f"{'measured_s':>10s} {'meas/mean':>9s}")
    for i, (sh, w) in enumerate(zip(shards, walls)):
        print(f"{i:5d} {sh.n_candidates:5d} {sh.cost:12.3e} "
              f"{sh.cost / max(mean, 1e-9):9.3f} {w:10.4f} "
              f"{w / max(wmean, 1e-9):9.3f}")
    print(f"measured max/mean={max(walls) / max(wmean, 1e-9):.3f}")
    cm_eval = None
    if model_preds:
        pred = np.array([p["wall_s"] for p in model_preds])
        meas = np.array(walls)
        cm_eval = {
            "mape": round(float(np.mean(np.abs(pred - meas)
                                        / np.maximum(meas, 1e-9))), 4),
            "measured_makespan_ratio": round(
                float(meas.max() / max(meas.mean(), 1e-9)), 4),
            "predicted_makespan_ratio": round(
                float(pred.max() / max(pred.mean(), 1e-9)), 4),
            "shards": len(walls),
        }
        print(f"costmodel: MAPE={cm_eval['mape']:.3f} makespan ratio "
              f"predicted={cm_eval['predicted_makespan_ratio']:.3f} "
              f"measured={cm_eval['measured_makespan_ratio']:.3f}")
    bub = None
    try:
        bub = timeline.bubble_report(window="profile.window",
                                     wall_s=wall_meas)
        print(timeline.format_report(bub))
    except ValueError as e:
        print(f"bubble report unavailable: {e}")
    roof = None
    try:
        roof = ledger.ledger_report(window_wall_s=wall_meas,
                                    device_kind=jax.devices()[0].device_kind,
                                    platform=jax.devices()[0].platform,
                                    reps=reps)
        print(ledger.format_report(roof))
    except ValueError as e:
        print(f"roofline report unavailable: {e}")
    ledger.disable()
    ledger.reset()
    if not trace_was_on:
        trace.disable()
    _print_gbt_telemetry(sweep_ops)
    flops.disable()
    return cm_eval, bub, roof


def profile_rowsharded(n_data: int, n_model: int, reps: int = 3) -> None:
    """Real (data x model) mesh launch: parity, balance, memory, traffic."""
    import jax

    from transmogrifai_tpu.impl.sweep_fragments import build_sweep_plan
    from transmogrifai_tpu.ops import sweep as sweep_ops
    from transmogrifai_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < n_data * n_model:
        print(f"need {n_data * n_model} devices for a {n_data}x{n_model} mesh, "
              f"have {len(jax.devices())} (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8 on CPU)")
        return
    cands = [(OpLogisticRegression(max_iter=50), D.logistic_regression_grid()),
             (OpRandomForestClassifier(), D.random_forest_grid()),
             (OpXGBoostClassifier(), D.xgboost_grid())]
    F = 3
    cv = OpCrossValidation(ev, num_folds=F, seed=42)
    train_w, val_mask = cv.make_folds(len(y), None)
    plan = build_sweep_plan(cands, np.ascontiguousarray(X, np.float32), y,
                            train_w, ev)
    if plan is None:
        print("default grid did not build a fused plan; nothing to profile")
        return
    from transmogrifai_tpu.utils import flops
    mesh = make_mesh(n_data=n_data, n_model=n_model)
    single = plan.run(train_w, val_mask)
    sweep_ops.reset_run_stats()
    flops.enable()
    flops.reset()
    mrs = plan.run_rowsharded(train_w, val_mask, mesh)  # warm (compiles)
    diff = np.max(np.abs(mrs - single))
    print(f"mesh {n_data}x{n_model}: parity max|diff|={diff:.3g} "
          "vs single-device fused")
    if diff > 1e-6:
        # expected on real discrete data: psum partial-sum ordering gives
        # ulp-level G/H differences that compound over a boosting group's
        # sequential rounds until a near-tied split flips (the standard
        # distributed-XGBoost nondeterminism); LR/RF stay exact.  The
        # synthetic-grid parity tests hold the 1e-6 bar.
        print("  (>1e-6: GBT split-tie flips under psum reduction order; "
              "see README 'The data axis')")
    t0 = time.perf_counter()
    for _ in range(reps):
        plan.run_rowsharded(train_w, val_mask, mesh)
    steady = (time.perf_counter() - t0) / reps
    launch = sweep_ops.run_stats()["launches"][-1]
    n_models = F * sum(s["candidates"] for s in launch["per_shard"])
    print(f"steady {steady:.3f}s  ({n_models / steady:.1f} models/s)")
    costs = [s["predicted_cost"] for s in launch["per_shard"]]
    cmean = max(float(np.mean(costs)), 1e-9)
    wmean = max(float(np.mean([s["wall_s"] for s in launch["per_shard"]])), 1e-9)
    print(f"{'column':>6s} {'cands':>5s} {'rows_local':>10s} {'pred/mean':>9s} "
          f"{'meas/mean':>9s}")
    for i, s in enumerate(launch["per_shard"]):
        print(f"{i:6d} {s['candidates']:5d} {s['rows_local']:10d} "
              f"{s['predicted_cost'] / cmean:9.3f} {s['wall_s'] / wmean:9.3f}")
    for ax, c in launch["collectives"].items():
        print(f"collectives[{ax}]: count={c['count']} bytes={c['bytes']:,}"
              + "".join(f" {k}={v}" for k, v in sorted(c.items())
                        if k.endswith("_count")))
    pdb = launch["per_device_bytes"]
    print(f"per-device X+y bytes: rowsharded={pdb['X'] + pdb['y']:,} "
          f"replicated={pdb['X_replicated'] + pdb['y_replicated']:,} "
          f"(x{(pdb['X_replicated'] + pdb['y_replicated']) / max(pdb['X'] + pdb['y'], 1):.2f} saved)")
    _print_gbt_telemetry(sweep_ops)
    flops.disable()


from transmogrifai_tpu import obs  # noqa: E402

if args.data_shards > 0:
    profile_rowsharded(args.data_shards, max(args.shards, 1))
    extra = {"mode": "rowsharded"}
    try:
        from transmogrifai_tpu import costmodel
        from transmogrifai_tpu.ops import sweep as sweep_ops

        cm_eval = costmodel.eval_launches(sweep_ops.run_stats()["launches"])
        if cm_eval:
            extra["costmodel_eval"] = cm_eval
        extra["hedge"] = _print_hedge_telemetry(sweep_ops)
        extra["pack"] = _print_pack_telemetry(sweep_ops)
    except Exception:
        pass
    obs.write_record("profile_sweep", extra=extra)
    sys.exit(0)

if args.shards > 0:
    cm_eval, bub, roof = profile_shards(args.shards,
                                        use_costmodel=args.costmodel)
    extra = {"mode": "shards"}
    if cm_eval:
        extra["costmodel_eval"] = cm_eval
    if bub:
        extra["bubble_report"] = bub
    if roof:
        extra["roofline"] = roof
        extra["mfu_decomposition"] = roof["mfu_decomposition"]
    try:
        from transmogrifai_tpu.ops import sweep as sweep_ops

        extra["hedge"] = _print_hedge_telemetry(sweep_ops)
        extra["pack"] = _print_pack_telemetry(sweep_ops)
    except Exception:
        pass
    obs.write_record("profile_sweep", extra=extra)
    sys.exit(0)

rf = D.random_forest_grid()
by_depth = {}
for g in rf:
    by_depth.setdefault(g["max_depth"], []).append(g)

timed("LR x8", [(OpLogisticRegression(), D.logistic_regression_grid())])
for dep, gs in sorted(by_depth.items()):
    timed(f"RF depth={dep} x{len(gs)}", [(OpRandomForestClassifier(), gs)])
timed("RF all x18", [(OpRandomForestClassifier(), rf)])
timed("XGB x2", [(OpXGBoostClassifier(), D.xgboost_grid())])

from transmogrifai_tpu.ops import sweep as sweep_ops  # noqa: E402
_print_gbt_telemetry(sweep_ops)
obs.write_record("profile_sweep",
                 extra={"mode": "families",
                        "hedge": _print_hedge_telemetry(sweep_ops),
                        "pack": _print_pack_telemetry(sweep_ops)})
