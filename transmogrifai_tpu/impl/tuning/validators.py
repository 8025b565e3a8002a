"""Validators — cross-validation / train-validation-split over a model grid.

Reference parity: core/.../impl/tuning/OpValidator.scala:94 (base),
OpCrossValidation.scala:42 (k folds via MLUtils.kFold, optional label
stratification :200-236, grid-averaged fold metrics ``findBestModel``:60),
OpTrainValidationSplit.scala:35 (single 0.75 split); defaults
``ValidatorParamDefaults``: numFolds=3, trainRatio=0.75, parallelism=8,
failed models tolerated (each fit Future recovers to None,
OpValidator.scala:323-353) — only all-models-failed aborts.

TPU-first redesign: where the reference trains numFolds x models x grids as
JVM-thread Futures, here

- folds are WEIGHT MASKS over one resident dataset (train_w zeroes held-out
  rows), so every fold trains on identical static shapes,
- estimators that implement ``fit_grid_folds`` train their whole
  fold x param-grid block as ONE vmapped XLA program (ops/linear kernels);
  others fall back to a per-candidate jit'd fit loop,
- ``parallelism`` is kept for API parity but is meaningless — the sweep is
  a single device launch, not a thread pool.
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...evaluators.base import OpEvaluatorBase
from ...obs import trace

log = logging.getLogger(__name__)

#: reference ValidatorParamDefaults (OpValidator.scala:373-380)
DEFAULT_NUM_FOLDS = 3
DEFAULT_TRAIN_RATIO = 0.75
DEFAULT_PARALLELISM = 8


@dataclass
class ModelEvaluation:
    """Per-candidate validation record (reference ModelEvaluation in
    ModelSelectorSummary.scala)."""

    model_uid: str
    model_name: str
    model_type: str
    grid: Dict[str, Any]
    metric_name: str
    fold_metrics: List[float]
    metric_value: float  # mean over folds
    error: Optional[str] = None


@dataclass
class ValidationSummary:
    """All candidates' results + the winner."""

    validation_type: str
    evaluator_name: str
    metric_name: str
    is_larger_better: bool
    results: List[ModelEvaluation] = field(default_factory=list)
    best_index: int = -1

    @property
    def best(self) -> ModelEvaluation:
        return self.results[self.best_index]

    def to_json(self) -> Dict[str, Any]:
        return {
            "validationType": self.validation_type,
            "evaluator": self.evaluator_name,
            "metric": self.metric_name,
            "isLargerBetter": self.is_larger_better,
            "bestModelUID": self.best.model_uid if self.results else None,
            "bestModelName": self.best.model_name if self.results else None,
            "bestGrid": self.best.grid if self.results else None,
            "results": [
                {"modelUID": r.model_uid, "modelName": r.model_name,
                 "modelType": r.model_type, "grid": {k: _j(v) for k, v in r.grid.items()},
                 "metric": r.metric_name, "foldMetrics": r.fold_metrics,
                 "metricValue": r.metric_value, "error": r.error}
                for r in self.results
            ],
        }


def _j(v):
    if isinstance(v, (np.floating, np.integer, np.bool_)):
        return v.item()
    return v


class OpValidator:
    """Base validator (OpValidator.scala:94)."""

    validation_type = "validator"

    def __init__(self, evaluator: OpEvaluatorBase, seed: int = 42,
                 stratify: bool = False, parallelism: int = DEFAULT_PARALLELISM,
                 mesh: Any = "auto"):
        self.evaluator = evaluator
        self.seed = seed
        self.stratify = stratify
        self.parallelism = parallelism  # API parity; the sweep is one launch
        #: "auto" = all local devices on the model axis; None = single device;
        #: or an explicit jax.sharding.Mesh.  The TPU replacement for the
        #: reference's 8-thread pool (OpValidator.scala:373-380).
        self.mesh = mesh

    def _resolve_mesh(self):
        from ...parallel.mesh import auto_mesh, env_mesh

        if isinstance(self.mesh, str) and self.mesh == "auto":
            # TMOG_MESH ("2x4" = data x model) overrides the all-model-axis
            # default; unset/unsatisfiable requests fall through to auto
            m = env_mesh()
            return m if m is not None else auto_mesh()
        return self.mesh

    # ---- folds -------------------------------------------------------------
    def make_folds(self, n: int, y: Optional[np.ndarray]
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """(train_w f32[F, n], val_mask bool[F, n])."""
        raise NotImplementedError

    # ---- the sweep ---------------------------------------------------------
    def validate(self, candidates: Sequence[Tuple[Any, Sequence[Dict[str, Any]]]],
                 X: np.ndarray, y: np.ndarray,
                 prep_w: Optional[np.ndarray] = None) -> ValidationSummary:
        """Validate every (estimator, param-grid) candidate.

        ``candidates`` mirrors the reference's ``models: Seq[(E, Array[ParamMap])]``
        (ModelSelector.scala:72).  ``prep_w`` is the splitter's preparation
        weight vector (balancing/cutting), folded into every fold's training
        weights.
        """
        n = len(y)
        train_w, val_mask = self.make_folds(n, y if self.stratify else None)
        if prep_w is not None:
            train_w = train_w * prep_w[None, :].astype(np.float32)
            # rows the splitter dropped (weight 0, e.g. DataCutter labels)
            # must not score either — the reference removes them from the
            # whole CV dataset (DataCutter.validationPrepare)
            val_mask = val_mask & (prep_w > 0)[None, :]
        summary = ValidationSummary(
            validation_type=self.validation_type,
            evaluator_name=self.evaluator.name,
            metric_name=self.evaluator.default_metric,
            is_larger_better=self.evaluator.is_larger_better,
        )
        from ...parallel.mesh import use_mesh

        with use_mesh(self._resolve_mesh()):
            self._sweep(candidates, X, y, train_w, val_mask, summary)
        # warm-start accounting: stamp AFTER the sweep (the fused path resets
        # the sweep scope on entry) so pruned-vs-full candidate counts land in
        # run_stats() next to the launches they shrank
        wc = getattr(self, "warm_start_counts", None)
        if wc:
            from ...ops import sweep as sweep_ops

            sweep_ops.record_warm_start(*wc)
        if not summary.results or all(r.error for r in summary.results):
            raise RuntimeError("All models in the selector grid failed to fit")
        vals = [r.metric_value for r in summary.results]
        summary.best_index = int(np.argmax(vals) if self.evaluator.is_larger_better
                                 else np.argmin(vals))
        return summary

    def _sweep(self, candidates, X, y, train_w, val_mask, summary) -> None:
        if self._fused_sweep(candidates, X, y, train_w, val_mask, summary):
            return
        for est, grids in candidates:
            grids = list(grids) or [{}]
            preds = None
            try:
                preds = est.fit_grid_folds(X, y, train_w, grids)
            except NotImplementedError:
                preds = None
            except Exception as e:  # batched path failed: fall back to loop
                log.warning("Batched grid fit failed for %s (%s); falling back",
                            type(est).__name__, e)
                preds = None
            for ci, grid in enumerate(grids):
                fold_metrics: List[float] = []
                err: Optional[str] = None
                try:
                    for f in range(train_w.shape[0]):
                        if preds is not None:
                            pred, raw, prob = preds[f][ci]
                        else:
                            cand = est.copy_with_params(grid)
                            params = cand.fit_arrays(X, y, w=train_w[f])
                            pred, raw, prob = cand.predict_arrays(params, X)
                        vm = val_mask[f]
                        m = self.evaluator.evaluate_arrays(
                            y[vm], np.asarray(pred)[vm],
                            None if prob is None else np.asarray(prob)[vm])
                        fold_metrics.append(float(m[self.evaluator.default_metric]))
                    value = float(np.mean(fold_metrics))
                except Exception as e:
                    # reference: individual model/grid failures are tolerated
                    # (OpValidator.scala:323-353); the sweep proceeds
                    log.warning("Candidate %s%s failed: %s", type(est).__name__, grid, e)
                    err = f"{type(e).__name__}: {e}"
                    value = -np.inf if self.evaluator.is_larger_better else np.inf
                summary.results.append(ModelEvaluation(
                    model_uid=est.uid, model_name=type(est).__name__,
                    model_type=type(est).__name__, grid=dict(grid),
                    metric_name=self.evaluator.default_metric,
                    fold_metrics=fold_metrics, metric_value=value, error=err))

    def _fused_sweep(self, candidates, X, y, train_w, val_mask, summary) -> bool:
        """ONE-launch fold x grid sweep (ops/sweep) when every family and the
        evaluator's default metric have a device program.

        Returns True when the summary was filled.  Latency rationale
        (round-5): the per-family path pays a device round trip per launch,
        upload, and metric pull; the fused program costs one upload + one
        launch + one [F, C, M] metrics pull regardless of grid size.  Disable with
        TMOG_FUSED_SWEEP=0.  Under a multi-device mesh the spec is
        partitioned over the ``model``-axis devices by predicted cost
        (parallel/spec_partition), one fused program per device, dispatched
        asynchronously and gathered (SweepPlan.run_sharded).  When the mesh
        also has a ``data`` axis > 1 and the row count clears the per-shard
        floor, each model column's program additionally runs ROW-SHARDED
        over its column devices (SweepPlan.run_rowsharded) — otherwise the
        launch degrades to the replicated path and records why in
        ``ops.sweep.run_stats()['fallbacks']``.
        """
        from ...ops import sweep as sweep_ops
        from ...parallel.mesh import (active_mesh, data_shards,
                                      min_rows_per_shard, model_devices,
                                      model_shards, rowshard_viable)
        from ...utils.env import env_str

        if env_str("TMOG_FUSED_SWEEP", "1") == "0":
            return False
        n_shards = max(model_shards(), 1)
        n_data = max(data_shards(), 1)
        sweep_ops.reset_run_stats()
        rowsharded = n_data > 1
        if rowsharded and not rowshard_viable(len(y), n_data):
            sweep_ops.record_fallback(
                "too_few_rows_for_data_axis", rows=len(y),
                data_shards=n_data,
                min_rows_per_shard=min_rows_per_shard())
            rowsharded = False
        try:
            from ..sweep_fragments import build_sweep_plan

            # HBM guard: one monolithic program holding every family's
            # workspaces plus the [F, C, n] score block crashed the worker at
            # 450k x 64 candidates (round-5) — bound the per-launch score
            # bytes and run the sweep as a few candidate-chunk launches.
            # The budget is PER SHARD: each device holds only its sub-spec's
            # [F, C_s, n] block, so k shards fit a k-times-bigger grid per
            # launch.  Row-sharded, each device further holds only
            # rows/data_shards of that block.
            from ...utils.env import env_float

            budget = env_float("TMOG_FUSED_SCORES_BYTES", 3e8)
            budget *= n_shards
            rows_local = -(-len(y) // n_data) if rowsharded else len(y)
            per_cand = train_w.shape[0] * rows_local * 4.0
            inner_ev = getattr(self.evaluator, "inner", self.evaluator)
            if "Multi" in type(inner_ev).__name__:  # [F, C, n, k] scores
                per_cand *= max(int(np.max(np.asarray(y))) + 1, 2)
            chunks = _chunk_candidates(
                candidates, max(int(budget // max(per_cand, 1.0)), 1))
            # convert ONCE: devcache keys device buffers by host-array
            # identity, so each chunk's plan must see the SAME ndarray or
            # every chunk re-uploads and re-quantizes the matrix.  The
            # selector's device rows (a jax.Array) stay where they are.
            from ...utils import devcache as _devcache

            X = _devcache.as_f32(X)
            plans = []
            for chunk in chunks:
                with trace.span("sweep.plan", rows=len(y), candidates=sum(
                        len(list(g) or [{}]) for _, g in chunk)):
                    plan = build_sweep_plan(chunk, X, y, train_w,
                                            self.evaluator)
                if plan is None:
                    if n_data > 1:
                        # a custom estimator (or unsupported grid) blocks
                        # fusion entirely — the data axis sits idle and the
                        # per-family path runs replicated; auditable, not
                        # fatal
                        sweep_ops.record_fallback(
                            "unfusable_candidates_block_data_axis")
                    return False
                plans.append(plan)
        except Exception as e:
            log.warning("fused sweep build failed (%s); per-family path", e)
            return False
        try:
            if rowsharded:
                mesh = active_mesh()
                metrics = np.concatenate(
                    [p.run_rowsharded(train_w, val_mask, mesh)
                     for p in plans], axis=1)
            elif n_shards > 1:
                devs = model_devices()
                metrics = np.concatenate(
                    [p.run_sharded(train_w, val_mask, devs) for p in plans],
                    axis=1)
            else:
                metrics = np.concatenate(
                    [p.run(train_w, val_mask) for p in plans], axis=1)
            plan = plans[0]
        except Exception as e:
            log.warning("fused sweep run failed (%s); per-family path", e)
            return False
        mi = plan.metric_names.index(self.evaluator.default_metric)
        bad = -np.inf if self.evaluator.is_larger_better else np.inf
        ci = 0
        for est, grids in candidates:
            for grid in (list(grids) or [{}]):
                fm = [float(v) for v in metrics[:, ci, mi]]
                value = float(np.mean(fm))
                err = None
                if not np.isfinite(value):
                    # marked as a failed candidate (error set) so validate()'s
                    # all-models-failed guard still fires when the whole grid
                    # diverges — never silently selected
                    value = bad
                    err = f"non-finite {self.evaluator.default_metric} on device"
                summary.results.append(ModelEvaluation(
                    model_uid=est.uid, model_name=type(est).__name__,
                    model_type=type(est).__name__, grid=dict(grid),
                    metric_name=self.evaluator.default_metric,
                    fold_metrics=fm, metric_value=value, error=err))
                ci += 1
        return True


def _chunk_candidates(candidates, max_cands: int):
    """Partition (estimator, grids) pairs into chunks of <= max_cands
    candidates, splitting a single family's grid list when necessary.
    Chunk-local candidate order preserves the global order, so the
    concatenated metrics line up with the flat candidate enumeration."""
    chunks, cur, cur_n = [], [], 0
    for est, grids in candidates:
        grids = list(grids) or [{}]
        lo = 0
        while lo < len(grids):
            take = min(len(grids) - lo, max(max_cands - cur_n, 1))
            cur.append((est, grids[lo:lo + take]))
            cur_n += take
            lo += take
            if cur_n >= max_cands:
                chunks.append(cur)
                cur, cur_n = [], 0
    if cur:
        chunks.append(cur)
    return chunks


class OpCrossValidation(OpValidator):
    """k-fold CV (OpCrossValidation.scala:42); stratified option deals each
    label class round-robin across folds (:200-236 in the base)."""

    validation_type = "OpCrossValidation"

    def __init__(self, evaluator: OpEvaluatorBase, num_folds: int = DEFAULT_NUM_FOLDS,
                 seed: int = 42, stratify: bool = False,
                 parallelism: int = DEFAULT_PARALLELISM, mesh: Any = "auto"):
        super().__init__(evaluator, seed=seed, stratify=stratify,
                         parallelism=parallelism, mesh=mesh)
        if num_folds < 2:
            raise ValueError("num_folds must be >= 2")
        self.num_folds = num_folds

    def make_folds(self, n, y):
        from ...parallel.sweep import make_fold_weights

        train_w, val_w = make_fold_weights(n, self.num_folds, seed=self.seed,
                                           stratify_labels=y)
        return train_w, val_w.astype(bool)


class OpTrainValidationSplit(OpValidator):
    """Single train/validation split (OpTrainValidationSplit.scala:35)."""

    validation_type = "OpTrainValidationSplit"

    def __init__(self, evaluator: OpEvaluatorBase, train_ratio: float = DEFAULT_TRAIN_RATIO,
                 seed: int = 42, stratify: bool = False,
                 parallelism: int = DEFAULT_PARALLELISM, mesh: Any = "auto"):
        super().__init__(evaluator, seed=seed, stratify=stratify,
                         parallelism=parallelism, mesh=mesh)
        if not 0.0 < train_ratio < 1.0:
            raise ValueError("train_ratio must be in (0, 1)")
        self.train_ratio = train_ratio

    def make_folds(self, n, y):
        rng = np.random.default_rng(self.seed)
        val = np.zeros(n, dtype=bool)
        if y is not None:
            yv = np.asarray(y)
            for cls in np.unique(yv):
                idx = np.where(yv == cls)[0]
                rng.shuffle(idx)
                k = int(round(len(idx) * (1.0 - self.train_ratio)))
                val[idx[:k]] = True
        else:
            idx = rng.permutation(n)
            val[idx[: int(round(n * (1.0 - self.train_ratio)))]] = True
        train_w = (~val).astype(np.float32)[None, :]
        return train_w, val[None, :]
