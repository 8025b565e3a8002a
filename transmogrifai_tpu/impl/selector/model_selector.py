"""ModelSelector — the AutoML heart: validate a model grid, pick + refit best.

Reference parity: core/.../impl/selector/ModelSelector.scala:72 —
``fit()`` (:145): split holdout -> splitter.preValidationPrepare ->
``findBestEstimator`` (:116, the CV sweep) -> refit best on the full prepared
train -> evaluate train+holdout with every evaluator -> ``SelectedModel``
(:224) with a ``ModelSelectorSummary`` (ModelSelectorSummary.scala:61) in
output metadata.

TPU-first: the sweep is the vmapped fold x grid program (see
tuning/validators.py); the final refit is one more jit'd fit.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import types as T
from ...columns import Column, Dataset, NumericColumn, VectorColumn
from ...evaluators.base import OpEvaluatorBase
from ...obs import trace
from ...stages.base import AllowLabelAsInput, BinaryEstimator
from ..tuning.splitters import Splitter, SplitterSummary
from ..tuning.validators import OpValidator, ValidationSummary
from .predictor import PredictorEstimator, PredictorModel

#: Prediction/label column keys in summaries (reference ModelSelectorNames)
HOLDOUT_EVAL = "holdoutEvaluation"
TRAIN_EVAL = "trainEvaluation"


def _scrub(obj: Any) -> Any:
    """Plain-JSON scrub: numpy scalars/arrays -> python values."""
    if isinstance(obj, dict):
        return {str(k): _scrub(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_scrub(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, (np.floating, np.integer, np.bool_)):
        return obj.item()
    return obj


def _is_number(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _neighborhood_grids(grids: Sequence[Dict[str, Any]],
                        winner: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Grids within one grid-axis step of the winner.

    Axes are inferred from the candidate family's OWN configured grid (per-
    param sorted unique values), so pruning needs no coupling to
    ``defaults.py`` — custom grids prune the same way.  Numeric params keep
    winner +/- 1 index on the sorted value axis; non-numeric params pin to
    the winner's value; a winner value absent from the axis (hand-edited
    summary) leaves that axis unpruned rather than guessing."""
    allowed: Dict[str, Optional[set]] = {}
    for p in {k for g in grids for k in g}:
        wv = winner.get(p)
        if wv is None:
            allowed[p] = None  # winner doesn't constrain this axis
            continue
        axis = sorted({g[p] for g in grids if p in g and _is_number(g[p])})
        if _is_number(wv) and wv in axis:
            i = axis.index(wv)
            allowed[p] = set(axis[max(0, i - 1):i + 2])
        elif _is_number(wv):
            allowed[p] = None
        else:
            allowed[p] = {wv}
    return [g for g in grids
            if all(allowed.get(p) is None or g[p] in allowed[p] for p in g)]


def prune_candidates(models: Sequence[Tuple[PredictorEstimator,
                                            Sequence[Dict[str, Any]]]],
                     summary: "ModelSelectorSummary", explore: int = 1
                     ) -> List[Tuple[PredictorEstimator, List[Dict[str, Any]]]]:
    """Warm-start grid pruning: the incumbent winner's neighborhood plus a
    small exploration set.

    The winning family (matched by ``best_model_type``) keeps only grids
    within one axis step of ``best_grid``; every other family keeps
    ``explore`` evenly-spaced grids so a regime change can still flip the
    family.  An unmatched summary returns the models unpruned — a cold
    sweep is the safe degradation."""
    matched = any(type(est).__name__ == summary.best_model_type
                  for est, _ in models)
    if not matched:
        return [(est, list(g)) for est, g in models]
    out: List[Tuple[PredictorEstimator, List[Dict[str, Any]]]] = []
    for est, grids in models:
        grids = list(grids) or [{}]
        if type(est).__name__ == summary.best_model_type:
            kept = _neighborhood_grids(grids, dict(summary.best_grid or {}))
            out.append((est, kept or grids))
        elif explore > 0:
            idx = sorted({int(round(i)) for i in
                          np.linspace(0, len(grids) - 1,
                                      min(explore, len(grids)))})
            out.append((est, [grids[i] for i in idx]))
    return out


@dataclass
class ModelSelectorSummary:
    """Serializable selection report (ModelSelectorSummary.scala:61)."""

    validation_type: str
    validation_parameters: Dict[str, Any]
    data_prep_parameters: Dict[str, Any]
    data_prep_results: Optional[Dict[str, Any]]
    evaluation_metric: str
    problem_type: str
    best_model_uid: str
    best_model_name: str
    best_model_type: str
    best_grid: Dict[str, Any]
    validation_results: List[Dict[str, Any]] = field(default_factory=list)
    train_evaluation: Dict[str, Any] = field(default_factory=dict)
    holdout_evaluation: Optional[Dict[str, Any]] = None

    def to_json(self) -> Dict[str, Any]:
        return _scrub({
            "validationType": self.validation_type,
            "validationParameters": self.validation_parameters,
            "dataPrepParameters": self.data_prep_parameters,
            "dataPrepResults": self.data_prep_results,
            "evaluationMetric": self.evaluation_metric,
            "problemType": self.problem_type,
            "bestModelUID": self.best_model_uid,
            "bestModelName": self.best_model_name,
            "bestModelType": self.best_model_type,
            "bestGrid": self.best_grid,
            "validationResults": self.validation_results,
            "trainEvaluation": self.train_evaluation,
            "holdoutEvaluation": self.holdout_evaluation,
        })

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "ModelSelectorSummary":
        return ModelSelectorSummary(
            validation_type=d["validationType"],
            validation_parameters=d.get("validationParameters", {}),
            data_prep_parameters=d.get("dataPrepParameters", {}),
            data_prep_results=d.get("dataPrepResults"),
            evaluation_metric=d.get("evaluationMetric", ""),
            problem_type=d.get("problemType", "Unknown"),
            best_model_uid=d.get("bestModelUID", ""),
            best_model_name=d.get("bestModelName", ""),
            best_model_type=d.get("bestModelType", ""),
            best_grid=d.get("bestGrid", {}),
            validation_results=d.get("validationResults", []),
            train_evaluation=d.get("trainEvaluation", {}),
            holdout_evaluation=d.get("holdoutEvaluation"),
        )


class ModelSelector(BinaryEstimator, AllowLabelAsInput):
    """(RealNN label, OPVector features) -> Prediction, selecting the best of
    a model grid (ModelSelector.scala:72)."""

    is_model_selector = True
    problem_type = "Unknown"

    def __init__(self, validator: OpValidator, splitter: Optional[Splitter],
                 models: Sequence[Tuple[PredictorEstimator, Sequence[Dict[str, Any]]]],
                 evaluators: Sequence[OpEvaluatorBase] = (),
                 search_strategy: str = "grid",
                 uid: Optional[str] = None):
        super().__init__(operation_name="modelSelector", output_type=T.Prediction,
                         uid=uid)
        self.validator = validator
        self.splitter = splitter
        self.models = [(est, list(grids) or [{}]) for est, grids in models]
        if not self.models:
            raise ValueError("ModelSelector needs at least one candidate model")
        if search_strategy not in ("grid", "asha"):
            raise ValueError(f"unknown search_strategy {search_strategy!r} "
                             "(expected 'grid' or 'asha')")
        #: "grid" = exhaustive sweep (bit-identical to the pre-search code);
        #: "asha" = successive-halving rung scheduler (search/asha) for
        #: candidate spaces too large to fit at full budget
        self.search_strategy = search_strategy
        self.evaluators = list(evaluators)
        self.validation_summary: Optional[ValidationSummary] = None
        #: pre-selected (estimator, grid, summary) from workflow-level CV —
        #: when set, ``fit`` skips its own validation sweep and refits this
        #: winner (reference ``bestEstimator``, ModelSelector.scala:116,145)
        self.best_estimator: Optional[Tuple[PredictorEstimator, Dict[str, Any],
                                            ValidationSummary]] = None

    def check_input_types(self, features) -> None:
        super().check_input_types(features)
        label, vec = features
        if not label.is_response:
            raise ValueError("First ModelSelector input (label) must be a response "
                             "feature (CheckIsResponseValues analog)")
        if not issubclass(vec.ftype, T.OPVector):
            raise ValueError("Second ModelSelector input must be OPVector, got "
                             f"{vec.ftype.__name__}")

    # ---- the sweep on raw arrays (findBestEstimator analog) ----------------
    def find_best_estimator(self, X: np.ndarray, y: np.ndarray,
                            prep_w: Optional[np.ndarray] = None
                            ) -> Tuple[PredictorEstimator, Dict[str, Any],
                                       ValidationSummary]:
        if self.search_strategy == "asha":
            from ...search import run_asha

            summary = run_asha(self.models, self.validator, X, y, prep_w)
        else:
            summary = self.validator.validate(self.models, X, y, prep_w)
        best = summary.best
        est = next(e for e, _ in self.models if e.uid == best.model_uid)
        return est, best.grid, summary

    # ---- workflow-level CV (OpWorkflow.scala:403-453) ----------------------
    def find_best_estimator_cv(self, during_layers, ds: Dataset
                               ) -> Tuple[PredictorEstimator, Dict[str, Any],
                                          ValidationSummary]:
        """Leakage-free sweep: per CV fold, REFIT the selector's upstream
        feature estimators (``during_layers``) on the fold's training rows
        only, transform the fold's validation rows with those fold-fitted
        models, and sweep the candidate grid on the fold-local features.

        Reference: OpValidator.applyDAG per-fold feature-DAG refit
        (OpValidator.scala:250) driven from OpWorkflow.fitStages
        (OpWorkflow.scala:403-453); equivalence with selector-level CV is the
        OpWorkflowCVTest contract.
        """
        from ...parallel.mesh import use_mesh
        from ...workflow import dag as dag_util

        label_f, vec_f = self.inputs
        lab = ds[label_f.name]
        if not lab.mask.all():  # unlabeled rows never train or validate
            ds = ds.take(np.where(lab.mask)[0])
        y_all = ds[label_f.name].values.astype(np.float32)
        n = len(y_all)
        v = self.validator
        train_w, val_mask = v.make_folds(n, y_all if v.stratify else None)

        fold_summaries = []
        with use_mesh(v._resolve_mesh()):
            for f in range(train_w.shape[0]):
                tr_idx = np.where(train_w[f] > 0)[0]
                va_idx = np.where(val_mask[f])[0]
                ds_tr = ds.take(tr_idx)
                fitted = dag_util.fit_and_transform_dag(during_layers, ds_tr)
                by_uid = {s.uid: s for s in fitted.fitted_stages}
                models_dag = [[by_uid[s.uid] for s in layer]
                              for layer in during_layers]
                ds_va = dag_util.apply_transformations_dag(ds.take(va_idx),
                                                           models_dag)
                Xtr = fitted.train[vec_f.name].values
                Xva = ds_va[vec_f.name].values
                ytr, yva = y_all[tr_idx], y_all[va_idx]
                prep_w = (self.splitter.prepare_weights(ytr)
                          if self.splitter is not None else
                          np.ones(len(ytr), np.float32))
                X = np.vstack([Xtr, Xva]).astype(np.float32)
                y = np.concatenate([ytr, yva])
                w_row = np.concatenate([prep_w,
                                        np.zeros(len(yva), np.float32)])
                vm = np.zeros(len(y), dtype=bool)
                vm[len(ytr):] = True
                s = ValidationSummary(
                    validation_type=f"workflow-{v.validation_type}",
                    evaluator_name=v.evaluator.name,
                    metric_name=v.evaluator.default_metric,
                    is_larger_better=v.evaluator.is_larger_better)
                v._sweep(self.models, X, y, w_row[None, :], vm[None, :], s)
                fold_summaries.append(s)

        merged = fold_summaries[0]
        for s in fold_summaries[1:]:
            for acc, r in zip(merged.results, s.results):
                acc.fold_metrics.extend(r.fold_metrics)
                if r.error and not acc.error:
                    acc.error = r.error
        for acc in merged.results:
            if acc.fold_metrics and not acc.error:
                acc.metric_value = float(np.mean(acc.fold_metrics))
            else:
                acc.metric_value = (-np.inf if v.evaluator.is_larger_better
                                    else np.inf)
        if all(r.error for r in merged.results):
            raise RuntimeError("All models in the workflow-CV grid failed to fit")
        vals = [r.metric_value for r in merged.results]
        merged.best_index = int(np.argmax(vals) if v.evaluator.is_larger_better
                                else np.argmin(vals))
        best = merged.best
        est = next(e for e, _ in self.models if e.uid == best.model_uid)
        self.best_estimator = (est, best.grid, merged)
        return self.best_estimator

    # ---- warm start (continual retrain) ------------------------------------
    def warm_start(self, summary: "ModelSelectorSummary",
                   explore: int = 1) -> "ModelSelector":
        """Prune this selector's sweep grid to the incumbent winner's
        neighborhood (+ ``explore`` grids per other family) so a
        drift-triggered retrain costs a fraction of the cold sweep.  The
        pruned-vs-full counts are stamped into ``ops.sweep.run_stats()`` by
        the validator after the sweep runs."""
        full = sum(len(g) for _, g in self.models)
        self.models = prune_candidates(self.models, summary, explore)
        pruned = sum(len(g) for _, g in self.models)
        self.validator.warm_start_counts = (pruned, full)
        return self

    # ---- fit (ModelSelector.scala:145) -------------------------------------
    def fit_columns(self, cols: Sequence[Column], dataset: Dataset) -> "SelectedModel":
        label_col, vec_col = cols
        assert isinstance(label_col, NumericColumn) and isinstance(vec_col, VectorColumn)
        # one request id for the whole fit (inherited from OpWorkflow.train
        # when that is the caller); every phase below is a span under this one
        with trace.request(), trace.span(
                "selector.fit", rows=len(label_col.values),
                width=int(vec_col.values.shape[1]),
                candidates=sum(len(g) for _, g in self.models),
                folds=getattr(self.validator, "num_folds", 1)):
            return self._fit_columns(label_col, vec_col)

    def _fit_columns(self, label_col: NumericColumn,
                     vec_col: VectorColumn) -> "SelectedModel":
        keep = label_col.mask
        # avoid a full-matrix copy when no labels are missing (10M x p data)
        X = vec_col.values if keep.all() else vec_col.values[keep]
        y = label_col.values[keep].astype(np.float32)
        n = len(y)
        row_bytes = int(X.shape[1]) * X.itemsize

        # 1. holdout reservation (splitter.split, Splitter.scala:58)
        with trace.span("selector.split", rows=n) as sp:
            if self.splitter is not None and self.splitter.reserve_test_fraction > 0.0:
                train_idx, hold_idx = self.splitter.split(n, y)
            else:
                train_idx, hold_idx = np.arange(n), np.array([], dtype=np.int64)
            ytr = y[train_idx]
            sp.set(train_rows=len(train_idx), holdout_rows=len(hold_idx))

        cap = getattr(self.splitter, "max_training_sample", None) \
            if self.splitter is not None else None
        with trace.span("selector.prepare", cap=cap or 0) as sp:
            # 2. preValidationPrepare (DataBalancer.estimate etc.)
            prep_summary: Optional[SplitterSummary] = None
            prep_w = None
            if self.splitter is not None:
                prep_summary = self.splitter.pre_validation_prepare(ytr)
                prep_w = self.splitter.prepare_weights(ytr)

            # 2b. maxTrainingSample cap BEFORE materializing the sweep matrix
            # (reference splitters downsample in preValidationPrepare /
            # validationPrepare — DataSplitter.scala:65, DataBalancer.scala:84).
            # Rows are drawn UNIFORMLY without replacement and the preparation
            # weights are kept on the survivors, so the sweep still trains on
            # the splitter's balanced distribution (a weighted
            # without-replacement draw cannot upsample the minority and
            # flattens the weights as the pool shrinks — it would neither
            # match the balancer nor the raw distribution).
            if cap and len(train_idx) > cap:
                rng = np.random.default_rng(self.validator.seed)
                sub = np.sort(rng.choice(len(train_idx), size=int(cap),
                                         replace=False))
                train_idx = train_idx[sub]
                ytr = y[train_idx]
                if prep_w is not None:
                    prep_w = prep_w[sub]
            sp.set(kept_rows=len(train_idx))

        from ...workflow import stream as _stream

        with trace.span("selector.gather", bytes=len(train_idx) * row_bytes,
                        handoff=_stream.device_view(vec_col.values) is not None):
            Xtr = X[train_idx]
            # device-side handoff: when the streaming transform executor
            # produced this feature matrix, its chunks are still
            # device-resident — gather the training rows ON DEVICE and seed
            # the sweep's devcache under Xtr's identity, so the fused sweep
            # finds a resident buffer instead of re-uploading the host matrix
            # (workflow/stream.handoff_rows)
            _stream.handoff_rows(
                vec_col.values, Xtr,
                train_idx if keep.all() else np.flatnonzero(keep)[train_idx])

        # 3. the sweep (skipped when workflow-level CV already chose a winner)
        if self.best_estimator is not None:
            best_est, best_grid, vsummary = self.best_estimator
        else:
            with trace.span("selector.validate", strategy=self.search_strategy):
                best_est, best_grid, vsummary = self.find_best_estimator(
                    Xtr, ytr, prep_w)
        self.validation_summary = vsummary

        # 4. final refit on the full prepared train (validationPrepare ->
        #    bestEstimator.fit, ModelSelector.scala:181)
        with trace.span("selector.refit", family=type(best_est).__name__) as sp:
            refit = best_est.copy_with_params(best_grid)
            if self.splitter is not None:
                ridx = self.splitter.prepare_indices(ytr)
            else:
                ridx = np.arange(len(ytr))
            sp.set(rows=len(ridx), bytes=len(ridx) * row_bytes)
            params = refit.fit_arrays(Xtr[ridx], ytr[ridx])

        # 5. evaluate train + holdout with every evaluator; train metrics are
        #    computed on the PREPARED training data (the reference evaluates
        #    after validationPrepare — e.g. DataCutter-dropped labels are not
        #    counted as guaranteed errors, ModelSelector.scala:181-187)
        evaluators = self.evaluators or [self.validator.evaluator]
        with trace.span("selector.evaluate", holdout_rows=len(hold_idx),
                        bytes=(len(ridx) + len(hold_idx)) * row_bytes):
            pred_tr, raw_tr, prob_tr = refit.predict_arrays(params, Xtr[ridx])
            train_eval: Dict[str, Any] = {}
            for ev in evaluators:
                train_eval.update(ev.evaluate_arrays(
                    ytr[ridx], np.asarray(pred_tr),
                    None if prob_tr is None else np.asarray(prob_tr)))
            holdout_eval = None
            if len(hold_idx):
                Xho, yho = X[hold_idx], y[hold_idx]
                pred_ho, _, prob_ho = refit.predict_arrays(params, Xho)
                holdout_eval = {}
                for ev in evaluators:
                    holdout_eval.update(ev.evaluate_arrays(
                        yho, np.asarray(pred_ho),
                        None if prob_ho is None else np.asarray(prob_ho)))

        summary = ModelSelectorSummary(
            validation_type=vsummary.validation_type,
            validation_parameters={"seed": self.validator.seed,
                                   "stratify": self.validator.stratify,
                                   **({"numFolds": getattr(self.validator, "num_folds")}
                                      if hasattr(self.validator, "num_folds") else {}),
                                   **({"trainRatio": getattr(self.validator, "train_ratio")}
                                      if hasattr(self.validator, "train_ratio") else {})},
            data_prep_parameters=(prep_summary.params if prep_summary else {}),
            data_prep_results=(prep_summary.prepared if prep_summary else None),
            evaluation_metric=vsummary.metric_name,
            problem_type=self.problem_type,
            best_model_uid=vsummary.best.model_uid,
            best_model_name=vsummary.best.model_name,
            best_model_type=vsummary.best.model_type,
            best_grid=dict(best_grid),
            validation_results=vsummary.to_json()["results"],
            train_evaluation=train_eval,
            holdout_evaluation=holdout_eval,
        )
        model = SelectedModel(predictor_class=type(refit), model_params=params,
                              operation_name=self.operation_name)
        model.summary = summary
        model.metadata = dict(self.metadata)
        model.metadata["model_selector_summary"] = summary.to_json()
        return model


class SelectedModel(PredictorModel):
    """The winning candidate wrapped as a transformer (ModelSelector.scala:224)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.summary: Optional[ModelSelectorSummary] = None

    def transform_columns(self, cols):
        out = super().transform_columns(cols)
        # summary travels on the output column (reference: summary metadata in
        # the output column schema) so SelectedModelCombiner can read it
        if self.summary is not None:
            out.metadata = {"model_selector_summary": self.summary.to_json()}
        return out
