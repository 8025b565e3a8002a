"""OpWorkflow — the training entry point.

Reference parity: core/src/main/scala/com/salesforce/op/OpWorkflow.scala:61 —
``setResultFeatures`` reconstructs the DAG from feature lineage (:90, :208),
``train()`` (:347) reads data, optionally runs RawFeatureFilter (:235-261),
fits the DAG layer by layer, and returns an ``OpWorkflowModel``; stage
validation (:295-331); workflow-level CV via ``cut_dag`` (:403-453);
``withModelStages`` warm-start (:468); ``computeDataUpTo`` (:498).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from ..columns import Dataset
from ..features.feature import Feature
from ..features.generator import FeatureGeneratorStage
from ..obs import trace
from ..readers.base import CustomReader, Reader
from ..stages.base import Estimator, Model, PipelineStage, Transformer
from . import dag as dag_util
from .params import OpParams


class OpWorkflowCore:
    """Shared state between OpWorkflow and OpWorkflowModel
    (OpWorkflowCore.scala:53)."""

    def __init__(self):
        self.reader: Optional[Reader] = None
        self.result_features: List[Feature] = []
        self.raw_features: List[Feature] = []
        self.blocklisted_features: List[Feature] = []
        self.blocklisted_map_keys: Dict[str, List[str]] = {}
        self.stages: List[PipelineStage] = []
        self.dag: List[dag_util.Layer] = []
        self.parameters: OpParams = OpParams()

    # ---- input wiring (OpWorkflowCore.scala:147-176) -----------------------
    def set_reader(self, reader: Reader):
        self.reader = reader
        return self

    def set_input_dataset(self, data: Any, key: Union[str, Callable, None] = None):
        self.reader = CustomReader(data, key=key)
        return self

    set_input_rdd = set_input_dataset  # API parity alias

    def set_parameters(self, params: OpParams):
        self.parameters = params
        return self

    def set_stage_parameters(self, overrides: Dict[str, Dict[str, Any]]):
        """Per-stage param injection by class name or uid
        (OpWorkflow.setStageParameters, OpWorkflow.scala:179)."""
        for stage in self.stages:
            for key in (stage.uid, type(stage).__name__):
                if key in overrides:
                    for k, v in overrides[key].items():
                        stage.set_param(k, v)
        return self

    def _generate_raw_data(self, params: Optional[Dict[str, Any]] = None) -> Dataset:
        if self.reader is None:
            raise ValueError("A reader must be set before reading data "
                             "(set_reader / set_input_dataset)")
        p = dict(self.parameters.reader_params)
        p.update(params or {})
        return self.reader.generate_dataset(self.raw_features, p)


class OpWorkflow(OpWorkflowCore):
    """User-facing workflow builder (OpWorkflow.scala:61)."""

    def __init__(self):
        super().__init__()
        self.raw_feature_filter = None  # set by with_raw_feature_filter
        self._fitted_stage_map: Dict[str, PipelineStage] = {}
        self.rff_results = None
        #: None = AUTO (reference semantics, OpWorkflow.scala:376-455): engage
        #: workflow-level CV whenever the DAG contains a ModelSelector —
        #: cut_dag then decides whether label-using upstream estimators force
        #: per-fold feature refits (firstCVTSIndex) or the selector's own
        #: batched CV is equivalent.  True/False force either path.
        self.workflow_cv: Optional[bool] = None

    def with_workflow_cv(self) -> "OpWorkflow":
        """Force workflow-level cross-validation (OpWorkflow.scala:376-455):
        ``train()`` cuts the DAG around the ModelSelector (cut_dag), fits the
        before-DAG once, per fold REFITS the selector's upstream feature
        estimators on the fold-train rows only (leakage-free), sweeps the
        grid, then fits the full during+after DAG with the chosen winner.
        This is already the AUTO default when a ModelSelector is present."""
        self.workflow_cv = True
        return self

    def with_selector_cv(self) -> "OpWorkflow":
        """Opt OUT of workflow-level CV: the ModelSelector runs its own
        fold x grid sweep on the once-transformed data.  Faster, but
        label-using feature estimators (e.g. SanityChecker) then see
        validation rows at fit time — the leakage the reference's automatic
        DAG cutting exists to prevent.  Explicit opt-out only."""
        self.workflow_cv = False
        return self

    def _use_workflow_cv(self) -> bool:
        if self.workflow_cv is not None:
            return self.workflow_cv
        # auto: exactly one selector (cut_dag's requirement; two selectors —
        # the SelectedModelCombiner shape — fit on the plain path, matching
        # the reference where cutDAG throws on >1, FitStagesUtil.scala:310)
        return sum(1 for s in self.stages
                   if getattr(s, "is_model_selector", False)) == 1

    # ---- DAG setup ---------------------------------------------------------
    def set_result_features(self, *features: Feature) -> "OpWorkflow":
        """OpWorkflow.scala:90 — reconstruct the full DAG from lineage."""
        if not features:
            raise ValueError("At least one result feature is required")
        self.result_features = list(features)
        self._rebuild_dag()
        return self

    def _rebuild_dag(self):
        self.dag = dag_util.compute_dag(self.result_features)
        self.stages = [s for layer in self.dag for s in layer]
        raw: Dict[str, Feature] = {}
        for rf in self.result_features:
            for f in rf.raw_features():
                raw[f.uid] = f
        self.raw_features = sorted(raw.values(), key=lambda f: f.name)
        self._validate_stages()

    def _validate_stages(self):
        """uid uniqueness + stage type checks (OpWorkflow.scala:295-331)."""
        seen: Dict[str, PipelineStage] = {}
        for s in self.stages:
            if s.uid in seen and seen[s.uid] is not s:
                raise ValueError(f"Duplicate stage uid {s.uid!r} on distinct stages")
            seen[s.uid] = s
        # >1 ModelSelector is allowed (SelectedModelCombiner ensembles two);
        # only the workflow-CV path restricts to one (cut_dag raises there,
        # matching FitStagesUtil.cutDAG:310)

    # ---- raw feature filter (OpWorkflow.scala:544 withRawFeatureFilter) ----
    def with_raw_feature_filter(self, train_reader: Optional[Reader] = None,
                                score_reader: Optional[Reader] = None, **kwargs) -> "OpWorkflow":
        from ..impl.filters.raw_feature_filter import RawFeatureFilter

        self.raw_feature_filter = RawFeatureFilter(
            train_reader=train_reader, score_reader=score_reader, **kwargs)
        return self

    def with_model_stages(self, model: "OpWorkflowModel") -> "OpWorkflow":
        """Warm-start: reuse fitted stages by uid (OpWorkflow.scala:468)."""
        self._fitted_stage_map = {s.uid: s for s in model.stages if isinstance(s, Model)}
        return self

    # ---- training (OpWorkflow.scala:347) -----------------------------------
    def train(self, params: Optional[Dict[str, Any]] = None) -> "OpWorkflowModel":
        # one request id for every span of this train (obs/trace)
        with trace.request():
            return self._train(params)

    def _train(self, params: Optional[Dict[str, Any]]) -> "OpWorkflowModel":
        from . import stream

        # per-train streaming telemetry window (ops/sweep.reset_run_stats
        # cadence): stream_stats() after train() reports THIS run's chunk
        # counts / streamed bytes / compiles, and stale device views from a
        # prior train cannot serve a new fit's rows
        stream.reset_stream_stats()
        stream.clear_views()
        data = self._generate_raw_data(params)

        if self.raw_feature_filter is not None:
            reader = self.raw_feature_filter.train_reader or self.reader
            result = self.raw_feature_filter.generate_filtered_raw(
                self.raw_features, reader, self.parameters)
            self.rff_results = result
            if result.dropped_features or result.dropped_map_keys:
                self._set_blocklist(result.dropped_features, result.dropped_map_keys)
                data = result.clean(data)

        if self._use_workflow_cv():
            fitted = self._fit_stages_cv(data)
        else:
            fitted = dag_util.fit_and_transform_dag(
                self.dag, data, fitted_so_far=self._fitted_stage_map,
                responses=self._response_names())

        model = OpWorkflowModel()
        model.reader = self.reader
        model.parameters = self.parameters
        model.result_features = self.result_features
        model.raw_features = self.raw_features
        model.blocklisted_features = self.blocklisted_features
        model.blocklisted_map_keys = self.blocklisted_map_keys
        model.stages = fitted.fitted_stages
        model.dag = _dag_of_fitted(self.dag, fitted.fitted_stages)
        model.rff_results = self.rff_results
        model.train_data = fitted.train
        return model

    def _response_names(self) -> set:
        """Names that must survive intermediate-column freeing: responses
        (labels feed evaluators after training) AND the workflow's result
        features — a result produced in an early layer and not consumed
        downstream must still reach ``model.train_data``."""
        return ({f.name for f in self.raw_features if f.is_response}
                | {f.name for f in self.result_features})

    def _set_blocklist(self, dropped: Sequence[Feature], dropped_map_keys: Dict[str, List[str]]):
        """Blocklist propagation: drop raw features + rebuild the DAG without
        them (OpWorkflow.scala:118-167).  Response features and features that
        are the sole parent of a result feature cannot be dropped."""
        dropped_uids = {f.uid for f in dropped if not f.is_response}
        protected = {f.uid for f in self.result_features}
        dropped_uids -= protected
        self.blocklisted_features = [f for f in self.raw_features if f.uid in dropped_uids]
        self.blocklisted_map_keys = dict(dropped_map_keys)
        if not dropped_uids:
            return
        keep = [f for f in self.raw_features if f.uid not in dropped_uids]
        # rebuild stages whose inputs included dropped features
        for layer in self.dag:
            for stage in layer:
                kept_inputs = tuple(f for f in stage.inputs if f.uid not in dropped_uids)
                if len(kept_inputs) != len(stage.inputs):
                    if not kept_inputs:
                        raise ValueError(
                            f"RawFeatureFilter dropped all inputs of stage {stage.uid}")
                    stage.inputs = kept_inputs
        self.raw_features = keep

    def _fit_stages_cv(self, data: Dataset) -> dag_util.FittedDAG:
        """The workflow-level CV path (OpWorkflow.fitStages CV branch,
        OpWorkflow.scala:403-453): cut_dag -> fit before-DAG once ->
        ModelSelector.find_best_estimator_cv (per-fold during-DAG refits) ->
        fit during+after DAG with the winner pinned."""
        cut = dag_util.cut_dag(self.dag)
        if cut.model_selector is None:
            return dag_util.fit_and_transform_dag(
                self.dag, data, fitted_so_far=self._fitted_stage_map,
                responses=self._response_names())
        before = dag_util.fit_and_transform_dag(
            cut.before, data, fitted_so_far=self._fitted_stage_map,
            responses=self._response_names())
        selector = cut.model_selector
        feature_layers = [layer for layer in cut.during
                          if not (len(layer) == 1 and layer[0] is selector)]
        if feature_layers:
            selector.find_best_estimator_cv(feature_layers, before.train)
        # no label-using ancestors: nothing can leak — the selector's own
        # batched weight-mask CV is equivalent and faster (reference
        # firstCVTSIndex == -1 branch)
        rest = dag_util.fit_and_transform_dag(
            cut.during + cut.after, before.train,
            fitted_so_far=self._fitted_stage_map,
            responses=self._response_names())
        return dag_util.FittedDAG(
            train=rest.train, test=None,
            fitted_stages=before.fitted_stages + rest.fitted_stages)

    # ---- partial materialization (OpWorkflow.scala:498) --------------------
    def compute_data_up_to(self, *features: Feature,
                           params: Optional[Dict[str, Any]] = None) -> Dataset:
        """Fit/transform only the sub-DAG needed for the given feature(s)."""
        if not features:
            raise ValueError("compute_data_up_to needs at least one feature")
        sub = dag_util.compute_dag(list(features))
        data = self._generate_raw_data(params)
        fitted = dag_util.fit_and_transform_dag(
            sub, data, responses={f.name for f in features})
        return fitted.train


def _dag_of_fitted(dag: List[dag_util.Layer],
                   fitted: List[PipelineStage]) -> List[dag_util.Layer]:
    by_uid = {s.uid: s for s in fitted}
    return [[by_uid.get(s.uid, s) for s in layer] for layer in dag]


from .model import OpWorkflowModel  # noqa: E402  (cycle: model imports dag utils only)
