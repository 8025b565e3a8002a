"""Run metrics & phase tagging — the OpSparkListener / OpStep analog.

Reference parity:
- ``OpSparkListener`` (utils/.../spark/OpSparkListener.scala:62): per-stage
  CPU/duration metrics collected into JSON-serializable ``AppMetrics`` /
  ``StageMetrics`` (:173,231) with app-end handlers
  (OpWorkflowRunner.addApplicationEndHandler:145),
- ``OpStep`` + ``JobGroupUtil`` (utils/.../spark/OpStep.scala:35-45,
  core/.../spark/JobGroupUtil.scala:46): every pipeline phase tagged so work
  groups by phase.

Here the executor is in-process XLA, so the metrics are wall-clock +
(available) device-compile counters per stage, tagged with the active
``OpStep``.  The listener is installed via a contextvar so the DAG engine
reports into it without plumbing.
"""
from __future__ import annotations

import contextlib
import contextvars
import enum
import json
import time
from dataclasses import asdict, dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ..obs import trace


class OpStep(str, enum.Enum):
    """Pipeline phases (OpStep.scala:35-45)."""

    CrossValidation = "CrossValidation"
    DataReadingAndFiltering = "DataReadingAndFiltering"
    FeatureEngineering = "FeatureEngineering"
    ModelIO = "ModelIO"
    Other = "Other"
    ResultsSaving = "ResultsSaving"
    Scoring = "Scoring"


@dataclass
class StageMetrics:
    """One stage execution (OpSparkListener.StageMetrics analog)."""

    stage_name: str
    stage_uid: str
    step: str
    phase: str               # "fit" | "transform"
    started_at_ms: int
    duration_ms: float
    n_rows: int = 0

    def to_json(self) -> Dict[str, Any]:
        return asdict(self)


@dataclass
class AppMetrics:
    """Whole-run metrics (OpSparkListener.AppMetrics analog)."""

    app_name: str = "transmogrifai_tpu"
    run_type: str = ""
    started_at_ms: int = 0
    ended_at_ms: int = 0
    stage_metrics: List[StageMetrics] = field(default_factory=list)
    custom: Dict[str, Any] = field(default_factory=dict)

    @property
    def app_duration_ms(self) -> float:
        return float(self.ended_at_ms - self.started_at_ms)

    def to_json(self) -> Dict[str, Any]:
        return {
            "appName": self.app_name,
            "runType": self.run_type,
            "appStartTime": self.started_at_ms,
            "appEndTime": self.ended_at_ms,
            "appDuration": self.app_duration_ms,
            "stageMetrics": [m.to_json() for m in self.stage_metrics],
            "custom": self.custom,
        }


_current_listener: contextvars.ContextVar[Optional["OpListener"]] = \
    contextvars.ContextVar("op_listener", default=None)


def current_listener() -> Optional["OpListener"]:
    return _current_listener.get()


class OpListener:
    """Collects AppMetrics; install with ``with listener.install(): ...``."""

    def __init__(self, app_name: str = "transmogrifai_tpu", run_type: str = "",
                 collect_stage_metrics: bool = True):
        self.metrics = AppMetrics(app_name=app_name, run_type=run_type,
                                  started_at_ms=int(time.time() * 1000))
        self.collect_stage_metrics = collect_stage_metrics
        self._step: OpStep = OpStep.Other
        self._end_handlers: List[Callable[[AppMetrics], None]] = []
        self._custom_providers: Dict[str, Callable[[], Any]] = {}

    # ---- phase tagging (JobGroupUtil.withJobGroup analog) ------------------
    @contextlib.contextmanager
    def step(self, step: OpStep):
        prev, self._step = self._step, step
        try:
            yield self
        finally:
            self._step = prev

    @property
    def current_step(self) -> OpStep:
        return self._step

    # ---- stage reporting ---------------------------------------------------
    @contextlib.contextmanager
    def time_stage(self, stage, phase: str, n_rows: int = 0):
        """Time one stage as the span ``stage.<phase>``: the stage's wall in
        the metrics IS the span's duration (one pair of clock reads), and a
        profiler capture shows the stage beside the device ops."""
        name = getattr(stage, "operation_name", str(stage))
        started_at = int(time.time() * 1000)
        sp = trace.timed("stage." + phase, stage=name, rows=n_rows)
        try:
            with sp:
                yield
        finally:
            if self.collect_stage_metrics:
                self.metrics.stage_metrics.append(StageMetrics(
                    stage_name=name,
                    stage_uid=getattr(stage, "uid", ""),
                    step=self._step.value, phase=phase, started_at_ms=started_at,
                    duration_ms=sp.seconds * 1000.0,
                    n_rows=n_rows))

    # ---- lifecycle ---------------------------------------------------------
    def add_application_end_handler(self, fn: Callable[[AppMetrics], None]) -> None:
        """OpWorkflowRunner.addApplicationEndHandler:145."""
        self._end_handlers.append(fn)

    def add_custom_provider(self, name: str, fn: Callable[[], Any]) -> None:
        """Register a snapshot fn polled at ``end()`` into ``metrics.custom``.

        Subsystems with their own counters (e.g. serve/'s ServeMetrics) hook
        in here so their final state lands in app_metrics.json alongside the
        stage metrics without the runner knowing their internals."""
        self._custom_providers[name] = fn

    def end(self) -> AppMetrics:
        self.metrics.ended_at_ms = int(time.time() * 1000)
        for name, provider in self._custom_providers.items():
            try:
                self.metrics.custom[name] = provider()
            except Exception:  # snapshots must not break the run
                pass
        for fn in self._end_handlers:
            try:
                fn(self.metrics)
            except Exception:  # handlers must not break the run (reference logs)
                pass
        return self.metrics

    @contextlib.contextmanager
    def install(self):
        token = _current_listener.set(self)
        try:
            yield self
        finally:
            _current_listener.reset(token)
            self.end()

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.metrics.to_json(), fh, indent=2)
