"""transmogrifai_tpu — a TPU-native AutoML framework for structured data.

A from-scratch re-design of TransmogrifAI's capability set (typed features
with lineage, automatic feature engineering, sanity checking, model selection
with cross-validation, evaluators, insights, save/load, batch/local scoring)
on a JAX/XLA substrate: columnar datasets instead of Spark DataFrames, fused
jit'd transformations instead of RDD passes, and a vmapped/sharded model
sweep instead of JVM thread pools.

See SURVEY.md at the repo root for the full reference analysis.
"""
from . import types
from .columns import Column, Dataset, NumericColumn, ObjectColumn, PredictionColumn, VectorColumn
from .features.builder import FeatureBuilder, from_dataframe
from .features.feature import Feature, FeatureHistory, TransientFeature
from .features.metadata import VectorColumnMetadata, VectorMetadata
from .stages.base import (
    BinaryEstimator,
    BinaryTransformer,
    Estimator,
    Model,
    PipelineStage,
    SequenceEstimator,
    SequenceTransformer,
    Transformer,
    UnaryEstimator,
    UnaryTransformer,
)
from .workflow.params import OpParams
from .workflow.workflow import OpWorkflow
from .workflow.model import OpWorkflowModel, load_model
from . import dsl  # installs the rich-feature methods on Feature
from .impl.feature.transmogrifier import transmogrify
from .runner import (OpApp, OpAppWithRunner, OpWorkflowRunner, OpWorkflowRunType,
                     OpWorkflowRunnerResult)
from .utils.listener import AppMetrics, OpListener, OpStep, StageMetrics

__version__ = "0.1.0"
__all__ = [n for n in dir() if not n.startswith("_")]
