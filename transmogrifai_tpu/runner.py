"""OpWorkflowRunner / OpApp — the production app harness.

Reference parity: core/src/main/scala/com/salesforce/op/OpWorkflowRunner.scala:70
and OpApp.scala:49 —

- run types ``Train | Score | StreamingScore | Features | Evaluate``
  (OpWorkflowRunner.scala:358-365),
- ``run(run_type, params)`` (:296) installs the metrics listener, dispatches,
  writes results/metrics to the configured locations,
- ``OpApp`` (:49) is the CLI entry: parses args (scopt analog = argparse),
  builds the runtime, calls the runner's ``main``; subclass and provide a
  workflow (``OpAppWithRunner:191``).

Where the reference boots a SparkSession + Kryo, here the runtime is the
in-process JAX/XLA client — ``OpApp.configure_runtime`` is the hook for
device/mesh setup (jax.distributed for multi-host).
"""
from __future__ import annotations

import argparse
import enum
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional

from .columns import Dataset
from .evaluators.base import OpEvaluatorBase
from .readers.base import Reader
from .readers.joined import StreamingReader
from .utils.listener import AppMetrics, OpListener, OpStep
from .workflow.model import OpWorkflowModel, load_model
from .workflow.params import OpParams
from .workflow.workflow import OpWorkflow


def _resume_stats() -> Optional[Dict[str, Any]]:
    """Checkpoint/resume accounting for the run record, or None when this
    run touched no checkpoint (``TMOG_CHECKPOINT_DIR`` unset).  Pulled from
    the resilience scope plus the per-subsystem skip counters, so a resumed
    train shows exactly how much work the checkpoints saved it."""
    from . import resilience
    from .obs import registry as obs_registry

    snap = resilience.scope.snapshot()
    out = {k: snap.get(k, 0) for k in (
        "checkpoint_saves", "checkpoint_hits", "checkpoint_corrupt",
        "gbt_rounds_skipped")}
    out["sweep_shard_skips"] = obs_registry.scope("sweep").get(
        "checkpoint_skips")
    out["stream_chunk_skips"] = obs_registry.scope("stream").get(
        "checkpoint_skips")
    if not any(out.values()):
        return None
    return out


class OpWorkflowRunType(str, enum.Enum):
    """OpWorkflowRunner.scala:358-365, plus the online ``Serve`` type."""

    Train = "train"
    Score = "score"
    StreamingScore = "streamingScore"
    Features = "features"
    Evaluate = "evaluate"
    Serve = "serve"
    Continual = "continual"


@dataclass
class OpWorkflowRunnerResult:
    """What a run produced (reference *Result classes per run type)."""

    run_type: OpWorkflowRunType
    model_location: Optional[str] = None
    score_location: Optional[str] = None
    metrics: Optional[Dict[str, Any]] = None
    app_metrics: Optional[AppMetrics] = None
    n_scored: int = 0


class OpWorkflowRunner:
    """Dispatches the five run types over a workflow (OpWorkflowRunner.scala:70)."""

    def __init__(self, workflow: OpWorkflow,
                 train_reader: Optional[Reader] = None,
                 scoring_reader: Optional[Reader] = None,
                 streaming_reader: Optional[StreamingReader] = None,
                 evaluator: Optional[OpEvaluatorBase] = None,
                 features_to_compute: Optional[List] = None):
        self.workflow = workflow
        self.train_reader = train_reader
        self.scoring_reader = scoring_reader
        self.streaming_reader = streaming_reader
        self.evaluator = evaluator
        self.features_to_compute = features_to_compute or []
        self._end_handlers = []

    def add_application_end_handler(self, fn) -> None:
        self._end_handlers.append(fn)

    # ---- dispatch (OpWorkflowRunner.run:296) -------------------------------
    def run(self, run_type: OpWorkflowRunType,
            params: Optional[OpParams] = None) -> OpWorkflowRunnerResult:
        params = params or self.workflow.parameters or OpParams()
        self.workflow.set_parameters(params)
        run_type = OpWorkflowRunType(run_type)
        listener = OpListener(run_type=run_type.value,
                              collect_stage_metrics=params.collect_stage_metrics)
        for fn in self._end_handlers:
            listener.add_application_end_handler(fn)
        with listener.install():
            dispatch = {
                OpWorkflowRunType.Train: self._train,
                OpWorkflowRunType.Score: self._score,
                OpWorkflowRunType.StreamingScore: self._streaming_score,
                OpWorkflowRunType.Features: self._features,
                OpWorkflowRunType.Evaluate: self._evaluate,
                OpWorkflowRunType.Serve: self._serve,
                OpWorkflowRunType.Continual: self._continual,
            }
            result = dispatch[run_type](params, listener)
        result.app_metrics = listener.metrics
        if params.metrics_location:
            os.makedirs(params.metrics_location, exist_ok=True)
            with open(os.path.join(params.metrics_location, "app_metrics.json"), "w") as fh:
                json.dump(listener.metrics.to_json(), fh, indent=2)
            if result.metrics is not None:
                with open(os.path.join(params.metrics_location, "metrics.json"), "w") as fh:
                    json.dump(result.metrics, fh, indent=2)
        return result

    # ---- run types ---------------------------------------------------------
    def _train(self, params: OpParams, listener: OpListener) -> OpWorkflowRunnerResult:
        if self.train_reader is not None:
            self.workflow.set_reader(self.train_reader)
        with listener.step(OpStep.FeatureEngineering):
            model = self.workflow.train()
        loc = params.model_location
        if loc:
            with listener.step(OpStep.ModelIO):
                model.save(loc)
        metrics: Dict[str, Any] = {"summary": model.summary()}
        resume = _resume_stats()
        if resume is not None:  # checkpointed/resumed work this run
            metrics["resume"] = resume
        return OpWorkflowRunnerResult(OpWorkflowRunType.Train, model_location=loc,
                                      metrics=metrics)

    def _load_model(self, params: OpParams, listener: OpListener) -> OpWorkflowModel:
        if not params.model_location:
            raise ValueError("model_location is required for this run type")
        with listener.step(OpStep.ModelIO):
            model = load_model(params.model_location)
        return model

    def _scoring_data(self, model: OpWorkflowModel):
        if self.scoring_reader is not None:
            model.reader = self.scoring_reader
        if model.reader is None:
            raise ValueError("A scoring reader is required (scoring_reader=...)")
        return model

    def _write_scores(self, scored: Dataset, result_names: List[str],
                      params: OpParams) -> Optional[str]:
        if not params.write_location:
            return None
        os.makedirs(params.write_location, exist_ok=True)
        path = os.path.join(params.write_location, "scores.json")
        out: List[Dict[str, Any]] = []
        for i in range(len(scored)):
            row: Dict[str, Any] = {}
            if scored.key is not None:
                row["key"] = scored.key[i]
            for n in result_names:
                v = scored[n].to_scalar(i)
                row[n] = v.to_dict() if hasattr(v, "to_dict") else v.value
            out.append(row)
        with open(path, "w") as fh:
            json.dump(out, fh)
        return path

    def _score(self, params: OpParams, listener: OpListener) -> OpWorkflowRunnerResult:
        model = self._scoring_data(self._load_model(params, listener))
        names = [f.name for f in model.result_features]
        reader_params = params.reader_params or None  # --read-location lands here
        with listener.step(OpStep.Scoring):
            if self.evaluator is not None:
                scored, metrics = model.score_and_evaluate(self.evaluator,
                                                           params=reader_params)
            else:
                scored, metrics = model.score(params=reader_params), None
        with listener.step(OpStep.ResultsSaving):
            path = self._write_scores(scored, names, params)
        return OpWorkflowRunnerResult(OpWorkflowRunType.Score, score_location=path,
                                      metrics=metrics, n_scored=len(scored))

    def _streaming_score(self, params: OpParams, listener: OpListener
                         ) -> OpWorkflowRunnerResult:
        if self.streaming_reader is None:
            raise ValueError("StreamingScore requires a streaming_reader")
        model = self._load_model(params, listener)
        names = [f.name for f in model.result_features]
        fn = model.score_fn()
        n_total, batch_idx = 0, 0
        with listener.step(OpStep.Scoring):
            for batch in self.streaming_reader.stream(model.raw_features,
                                                      params.reader_params):
                scored = fn(batch)
                n_total += len(scored)
                if params.write_location:
                    os.makedirs(params.write_location, exist_ok=True)
                    sub = OpParams.from_json(params.to_json())
                    sub.write_location = os.path.join(params.write_location,
                                                      f"batch_{batch_idx:05d}")
                    self._write_scores(scored, names, sub)
                batch_idx += 1
        return OpWorkflowRunnerResult(OpWorkflowRunType.StreamingScore,
                                      n_scored=n_total,
                                      metrics={"batches": batch_idx})

    def _features(self, params: OpParams, listener: OpListener) -> OpWorkflowRunnerResult:
        """computeDataUpTo (OpWorkflowRunner.scala:190)."""
        feats = self.features_to_compute or self.workflow.result_features
        if not feats:
            raise ValueError("Features run type needs features_to_compute or "
                             "result features on the workflow")
        if self.train_reader is not None:
            self.workflow.set_reader(self.train_reader)
        with listener.step(OpStep.FeatureEngineering):
            data = self.workflow.compute_data_up_to(*feats)
        path = None
        if params.write_location:
            os.makedirs(params.write_location, exist_ok=True)
            path = os.path.join(params.write_location, "features.json")
            data.to_pandas().to_json(path, orient="records")
        return OpWorkflowRunnerResult(OpWorkflowRunType.Features,
                                      score_location=path, n_scored=len(data))

    def _serve(self, params: OpParams, listener: OpListener) -> OpWorkflowRunnerResult:
        """Online serving: load -> deploy (warm) -> HTTP until stopped.

        Settings come from ``params.custom_params["serve"]`` (populated by the
        CLI flags): host, port, max_batch, max_wait_ms, queue_size,
        duration_s (None = serve until Ctrl-C; tests set a finite duration).
        """
        from .serve import ModelRegistry, ModelServer, ServeMetrics

        model = self._load_model(params, listener)
        cfg = dict(params.custom_params.get("serve", {}))
        metrics = ServeMetrics()
        replicas = cfg.get("replicas")
        registry = ModelRegistry(max_batch=int(cfg.get("max_batch", 64)),
                                 metrics=metrics,
                                 replicas=None if replicas is None
                                 else int(replicas))
        server = ModelServer(
            registry,
            host=cfg.get("host", "127.0.0.1"),
            port=int(cfg.get("port", 8123)),
            max_batch=int(cfg.get("max_batch", 64)),
            max_wait_ms=float(cfg.get("max_wait_ms", 2.0)),
            queue_size=int(cfg.get("queue_size", 1024)),
            metrics=metrics)
        listener.add_custom_provider("serve", metrics.snapshot)
        listener.add_custom_provider("serve_registry", registry.info)
        with listener.step(OpStep.Scoring):
            registry.deploy(model, version=cfg.get("version"))
            server.start()
            print(f"Serving model at {server.url}/score "
                  f"(metrics: {server.url}/metrics)", file=sys.stderr)
            duration = cfg.get("duration_s")
            server.wait(None if duration is None else float(duration))
            server.stop()
        snapshot = metrics.snapshot()
        return OpWorkflowRunnerResult(OpWorkflowRunType.Serve,
                                      model_location=params.model_location,
                                      metrics={"serve": snapshot},
                                      n_scored=snapshot["responses"])

    def _continual(self, params: OpParams, listener: OpListener
                   ) -> OpWorkflowRunnerResult:
        """Continual learning: deploy the champion, sketch the recent scoring
        window as serve-side observations, then run the drift -> warm-start
        retrain -> gate -> rolling hot-swap policy loop.

        Settings come from ``params.custom_params["continual"]`` (populated
        by the CLI flags): iterations, interval_s, holdout_fraction, explore,
        max_batch, version.  The scoring reader supplies the recent window;
        the runner's (unfitted) workflow is retrained on it.
        """
        from .continual import ServeSketch, baselines_from_model
        from .continual.controller import scope as continual_scope
        from .continual.loop import ContinualLoop
        from .serve import ModelRegistry, ServeMetrics

        if self.evaluator is None:
            raise ValueError("Continual requires an evaluator (the promotion "
                             "gate scores champion vs challenger with it)")
        reader = self.scoring_reader or self.train_reader
        if reader is None:
            raise ValueError("Continual requires a scoring_reader (the recent "
                             "data window)")
        model = self._load_model(params, listener)
        cfg = dict(params.custom_params.get("continual", {}))
        metrics = ServeMetrics()
        registry = ModelRegistry(max_batch=int(cfg.get("max_batch", 64)),
                                 metrics=metrics)
        registry.deploy(model, version=cfg.get("version"))
        sketch = ServeSketch(baselines_from_model(model))
        metrics.attach_sketch(sketch)
        reader_params = params.reader_params or None

        def window() -> Dataset:
            return reader.generate_dataset(model.raw_features, reader_params)

        def factory(ds: Dataset) -> OpWorkflow:
            return self.workflow.set_input_dataset(ds)

        loop = ContinualLoop(
            registry, metrics, factory, window, self.evaluator,
            holdout_fraction=float(cfg.get("holdout_fraction", 0.25)),
            explore=cfg.get("explore"))
        listener.add_custom_provider("continual", continual_scope.snapshot)
        listener.add_custom_provider("serve_registry", registry.info)
        outcomes: List[Dict[str, Any]] = []
        iters = int(cfg.get("iterations", 1))
        interval = float(cfg.get("interval_s", 0.0))
        with listener.step(OpStep.FeatureEngineering):
            for i in range(iters):
                raw = reader.read(reader_params)
                records = raw.to_dict(orient="records") \
                    if hasattr(raw, "to_dict") else list(raw)
                sketch.observe(records)
                outcomes.append(loop.run_once())
                rb = loop.check_rollback()
                if rb:
                    outcomes.append({"outcome": "rollback", "version": rb})
                if interval and i + 1 < iters:
                    time.sleep(interval)
        promoted = sum(1 for o in outcomes if o.get("outcome") == "promote")
        return OpWorkflowRunnerResult(
            OpWorkflowRunType.Continual,
            model_location=params.model_location,
            metrics={"continual": continual_scope.snapshot(),
                     "outcomes": outcomes, "registry": registry.info()},
            n_scored=promoted)

    def _evaluate(self, params: OpParams, listener: OpListener) -> OpWorkflowRunnerResult:
        if self.evaluator is None:
            raise ValueError("Evaluate requires an evaluator")
        model = self._scoring_data(self._load_model(params, listener))
        with listener.step(OpStep.Scoring):
            metrics = model.evaluate(self.evaluator,
                                     params=params.reader_params or None)
        return OpWorkflowRunnerResult(OpWorkflowRunType.Evaluate, metrics=metrics)


class OpApp:
    """CLI application shell (OpApp.scala:49).

    Subclass, implement ``runner()``, then ``MyApp().main(argv)``:

        python -m my_app --run-type=train --model-location=/tmp/model \
            --param-location=params.json
    """

    app_name: str = "OpApp"

    def configure_runtime(self) -> None:
        """SparkConf/Kryo analog: the runtime setup hook.

        Default: run on whatever platform JAX selected, say which, and
        switch on the persistent compile cache (utils/backend)."""
        from .utils.backend import compile_cache_dir, device_summary

        dev = device_summary()
        print(f"{self.app_name}: platform={dev['platform']} "
              f"kind={dev['kind']} devices={dev['count']} "
              f"compile_cache={compile_cache_dir()}", file=sys.stderr)

    def runner(self, args: argparse.Namespace) -> OpWorkflowRunner:
        raise NotImplementedError

    def parser(self) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(prog=self.app_name)
        p.add_argument("--run-type", required=True,
                       choices=[t.value for t in OpWorkflowRunType])
        p.add_argument("--param-location", help="OpParams JSON file")
        p.add_argument("--model-location")
        p.add_argument("--write-location")
        p.add_argument("--metrics-location")
        p.add_argument("--read-location", help="overrides readerParams.path")
        p.add_argument("--collect-stage-metrics", action="store_true")
        p.add_argument("--distributed", metavar="HOST:PORT", default=None,
                       help="multi-host mode: coordinator address for "
                            "jax.distributed (with --num-processes/"
                            "--process-id or JAX_NUM_PROCESSES/JAX_PROCESS_ID)")
        p.add_argument("--num-processes", type=int, default=None)
        p.add_argument("--process-id", type=int, default=None)
        serve = p.add_argument_group("serve", "options for --run-type=serve")
        serve.add_argument("--host", default="127.0.0.1")
        serve.add_argument("--port", type=int, default=8123)
        serve.add_argument("--max-batch", type=int, default=64,
                           help="largest micro-batch / shape bucket")
        serve.add_argument("--max-wait-ms", type=float, default=2.0,
                           help="max time a request waits for batchmates")
        serve.add_argument("--queue-size", type=int, default=1024,
                           help="admission queue bound (beyond it: HTTP 429)")
        serve.add_argument("--replicas", type=int, default=None,
                           help="per-chip model replicas (default: "
                                "TMOG_SERVE_REPLICAS or one per device)")
        serve.add_argument("--serve-duration", type=float, default=None,
                           help="seconds to serve (default: until Ctrl-C)")
        ct = p.add_argument_group("continual",
                                  "options for --run-type=continual")
        ct.add_argument("--continual-iterations", type=int, default=1,
                        help="policy-loop evaluations to run")
        ct.add_argument("--continual-interval", type=float, default=0.0,
                        help="seconds between policy-loop evaluations")
        ct.add_argument("--holdout-fraction", type=float, default=0.25,
                        help="trailing window fraction held out for the "
                             "champion-challenger gate")
        ct.add_argument("--explore", type=int, default=None,
                        help="exploration candidates per non-winning family "
                             "in warm-started sweeps (default: "
                             "TMOG_WARMSTART_EXPLORE or 1)")
        return p

    def parse_params(self, args: argparse.Namespace) -> OpParams:
        params = OpParams.load(args.param_location) if args.param_location else OpParams()
        for attr in ("model_location", "write_location", "metrics_location"):
            v = getattr(args, attr)
            if v:
                setattr(params, attr, v)
        if args.read_location:
            params.reader_params["path"] = args.read_location
        if args.collect_stage_metrics:
            params.collect_stage_metrics = True
        if args.run_type == OpWorkflowRunType.Serve.value:
            params.custom_params.setdefault("serve", {}).update({
                "host": args.host, "port": args.port,
                "max_batch": args.max_batch, "max_wait_ms": args.max_wait_ms,
                "queue_size": args.queue_size, "replicas": args.replicas,
                "duration_s": args.serve_duration,
            })
        if args.run_type == OpWorkflowRunType.Continual.value:
            params.custom_params.setdefault("continual", {}).update({
                "iterations": args.continual_iterations,
                "interval_s": args.continual_interval,
                "holdout_fraction": args.holdout_fraction,
                "explore": args.explore,
                "max_batch": args.max_batch,
            })
        return params

    def main(self, argv: Optional[List[str]] = None) -> OpWorkflowRunnerResult:
        """OpApp.main:178."""
        args = self.parser().parse_args(argv)
        if args.distributed or (args.num_processes or 0) > 1:
            from .parallel.distributed import initialize_distributed

            info = initialize_distributed(args.distributed, args.num_processes,
                                          args.process_id)
            print(f"{self.app_name}: joined cluster as process "
                  f"{info.process_id}/{info.num_processes} "
                  f"({info.local_devices} local / {info.global_devices} "
                  f"global devices)", file=sys.stderr)
        self.configure_runtime()
        params = self.parse_params(args)
        runner = self.runner(args)
        result = runner.run(OpWorkflowRunType(args.run_type), params)
        print(f"{self.app_name}: {args.run_type} done "
              f"(n_scored={result.n_scored}, model={result.model_location}, "
              f"scores={result.score_location})")
        return result


class OpAppWithRunner(OpApp):
    """OpApp whose runner is provided once (OpApp.scala:191)."""

    def build_runner(self) -> OpWorkflowRunner:
        raise NotImplementedError

    def runner(self, args: argparse.Namespace) -> OpWorkflowRunner:
        return self.build_runner()
