"""Step driver: ``MultiClassificationModelSelector``'s ``fit`` on the prepared
label and vector columns — DataCutter preparation under its training-sample
cap, the fused fold x grid sweep with a [F, C, n, k] score block, the metric
pull, the winner's refit and its train and holdout evaluation.  The step,
the work and the shapes are ``selector_fit``'s; what differs is who builds
the selector (``program.build_workflow`` is binary by construction) and what
one step is asked for afterwards: among it the [F, C, n, k] score block the
last timed sweep's training program handed its metric pass, which the sweep
keeps on the device for whoever asked (``ops.sweep.keep_scores``, asked here
before the warm-up fit, so every step runs as the timed ones do).

Set-up refuses at once (exit 1, one line on stderr) where the program lacks
what ``cfg["requires"]`` names, before a column is transformed or a program
compiled: ``module:attribute``, or ``module:callable(parameter)`` for a
keyword the callable must take.  A program whose ``DataCutter`` has no
``max_training_sample`` would sweep all 270,000 training rows, have its fused
plan refused by the plan's own size guard, and fall to the per-family path
that does not end at these sizes (``PERF.md``, PR 29).
"""
from __future__ import annotations

import importlib
import inspect

import numpy as np

from benchmarks import program
from benchmarks.entries import selector_fit as base
from benchmarks.entries import selector_fit_requires as requires_entry

rehearsal_config = base.rehearsal_config
step, work, shapes = base.step, base.work, base.shapes


def missing(requires) -> list:
    """The required names the program does not have: ``module:attribute`` as
    ``selector_fit_requires`` checks it, ``module:callable(parameter)`` by
    the callable's signature."""
    out = []
    for name in requires:
        plain, _, param = name.rstrip(")").partition("(")
        if requires_entry.missing([plain]):
            out.append(name)
        elif param:
            module, attr = plain.split(":")
            obj = getattr(importlib.import_module(module), attr)
            if param not in inspect.signature(obj).parameters:
                out.append(name)
    return out


def build_workflow(cfg, dataset, table):
    """(workflow, selector stage, label feature, checked vector feature):
    ``program.build_workflow`` with the multiclass selector and its cutter."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import FeatureBuilder, OpWorkflow
    from transmogrifai_tpu.dsl import sanity_check  # noqa: F401 (registers DSL)
    from transmogrifai_tpu.impl.feature.transmogrifier import transmogrify
    from transmogrifai_tpu.impl.selector.factories import (
        MultiClassificationModelSelector)
    from transmogrifai_tpu.impl.tuning.splitters import DataCutter

    label = FeatureBuilder(table.LABEL, T.RealNN).extract(
        field=table.LABEL).as_response()
    feats = [FeatureBuilder(n, getattr(T, t)).extract(field=n).as_predictor()
             for n, t in table.features(cfg)]
    checked = transmogrify(feats).sanity_check(
        label, sharded_stats=bool(cfg["sanity_checker"]["sharded_stats"]))
    cut = cfg["cutter"]
    sel = MultiClassificationModelSelector.with_cross_validation(
        splitter=DataCutter(
            max_label_categories=int(cut["max_label_categories"]),
            min_label_fraction=float(cut["min_label_fraction"]),
            reserve_test_fraction=cfg["holdout_fraction"],
            seed=int(cfg["cv_seed"]),
            max_training_sample=int(cfg["max_training_sample"])),
        num_folds=int(cfg["folds"]), seed=int(cfg["cv_seed"]),
        models_and_parameters=program.candidates(cfg))
    pred = sel.set_input(label, checked).get_output()
    wf = (OpWorkflow().set_result_features(pred).set_input_dataset(dataset)
          .with_selector_cv())
    return wf, sel, label, checked


def setup(ctx) -> None:
    cfg = ctx.cfg
    lacks = missing(cfg.get("requires", ()))
    if lacks:
        raise SystemExit(f"{cfg['name']}: the program lacks {lacks}, which "
                         "this configuration requires; it cannot be run here")
    wf, sel, label, vec = build_workflow(
        cfg, program.to_dataset(ctx.cols, ctx.table), ctx.table)
    with ctx.span("bench.setup.prepare_columns"):
        data = wf.compute_data_up_to(vec, label)
    ctx.state.update(sel=sel, data=data, vec_name=vec.name,
                     label_name=label.name,
                     n_candidates=sum(len(g) for _, g in sel.models))
    from transmogrifai_tpu.ops import sweep

    sweep.keep_scores(True)
    with ctx.span("bench.setup.warm_fit"):
        step(ctx)


def answers(ctx) -> dict:
    """What the last timed step produced: every candidate's fold Errors, the
    winner, the refit's holdout Error and F1 and — scored here, after the
    window, with the refit's own parameters — its class probabilities on the
    holdout rows, in the table's row order; the sweep's score block (every
    candidate's class distribution of every sweep row on every fold, pulled
    from the device here and let go of); the vector.  Refuses a sweep that
    did not run at the configuration's class count."""
    from transmogrifai_tpu.ops import sweep

    block, classes = sweep.last_scores(), sweep.run_stats()["classes"]
    sweep.keep_scores(False)
    if block is None or classes != int(ctx.cfg["classes"]):
        raise RuntimeError(
            f"the last sweep kept {'no' if block is None else 'a'} score "
            f"block, at {classes} classes (0: no single-device launch); the "
            f"configuration states {ctx.cfg['classes']}")
    s = ctx.state
    model, sm = s["last"], s["last"].summary
    X = np.asarray(s["data"][s["vec_name"]].values)
    y = np.asarray(s["data"][s["label_name"]].values, np.float32)
    _, hold = s["sel"].splitter.split(len(y), y)
    _, _, prob = model.predictor_class.predict_arrays(model.model_params, X[hold])
    return {
        "fold_metrics": [list(r["foldMetrics"]) for r in sm.validation_results],
        "mean_metrics": [float(r["metricValue"]) for r in sm.validation_results],
        "errors": [r["error"] for r in sm.validation_results],
        "winner_type": sm.best_model_type,
        "winner_grid": dict(sm.best_grid),
        "holdout": {k: float(sm.holdout_evaluation[k]) for k in ("Error", "F1")},
        "holdout_prob": np.asarray(prob, np.float32),
        "score_block": np.asarray(block, np.float32),
        "vector": X,
    }
