"""Everything of the benchmark that touches the system under test.

Copies of what ``scale10m.py`` / ``chip_smoke.py`` do to drive the program
(build the workflow from a configuration file, count compiles, read the
sweep's launch record), kept here because those scripts may change and the
yardstick may not.  Entries (``benchmarks/entries/*.py``) call these; the
plain reference (``benchmarks/references``) never does.
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, List, Tuple

import numpy as np


class GuardFailed(AssertionError):
    """A step that must not be timed as it ran (memo hit, fallback, ...)."""


# ---------------------------------------------------------------------------
# table -> the program's Dataset; configuration -> workflow
# ---------------------------------------------------------------------------
def to_dataset(cols: Dict[str, np.ndarray], table):
    """The table (``benchmarks/tables/<maker>.py``: ``LABEL``, ``features``)
    as the program's ``Dataset``, every value present."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu.columns import Dataset, NumericColumn, ObjectColumn

    ones = np.ones(len(cols[table.LABEL]), bool)
    out = {}
    for name, v in cols.items():
        if name == table.LABEL:
            out[name] = NumericColumn(T.RealNN, v, ones)
        elif v.dtype == object:
            out[name] = ObjectColumn(T.PickList, v)
        else:
            out[name] = NumericColumn(T.Real, v, ones)
    return Dataset(out)


def candidates(cfg: Dict[str, Any]) -> List[Tuple[Any, List[Dict[str, Any]]]]:
    """The configuration's grid as the selector takes it: per family the
    estimator its file names, built from ``fixed``, and one dict per point."""
    out = []
    for g in cfg["grid"].values():
        module, cls = g["estimator"].split(":")
        fixed = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in g["fixed"].items()}
        est = getattr(importlib.import_module(module), cls)(**fixed)
        out.append((est, [dict(zip(g["keys"], p)) for p in g["points"]]))
    return out


def family_of(cfg: Dict[str, Any], estimator_type: str) -> str:
    """The grid family whose estimator is the class named."""
    for fam, g in cfg["grid"].items():
        if g["estimator"].split(":")[1] == estimator_type:
            return fam
    raise KeyError(f"{estimator_type} is no estimator of the grid")


def build_workflow(cfg: Dict[str, Any], dataset, table):
    """(workflow, selector stage, label feature, checked vector feature)."""
    import transmogrifai_tpu.types as T
    from transmogrifai_tpu import FeatureBuilder, OpWorkflow
    from transmogrifai_tpu.dsl import sanity_check  # noqa: F401 (registers DSL)
    from transmogrifai_tpu.impl.feature.transmogrifier import transmogrify
    from transmogrifai_tpu.impl.selector.factories import (
        BinaryClassificationModelSelector)
    from transmogrifai_tpu.impl.tuning.splitters import DataBalancer

    label = FeatureBuilder(table.LABEL, T.RealNN).extract(
        field=table.LABEL).as_response()
    feats = [FeatureBuilder(n, getattr(T, t)).extract(field=n).as_predictor()
             for n, t in table.features(cfg)]
    checked = transmogrify(feats).sanity_check(
        label, sharded_stats=bool(cfg["sanity_checker"]["sharded_stats"]))
    sel = BinaryClassificationModelSelector.with_cross_validation(
        splitter=DataBalancer(
            sample_fraction=cfg["balancer_sample_fraction"],
            reserve_test_fraction=cfg["holdout_fraction"],
            max_training_sample=int(cfg["max_training_sample"])),
        num_folds=int(cfg["folds"]), seed=int(cfg["cv_seed"]),
        models_and_parameters=candidates(cfg))
    pred = sel.set_input(label, checked).get_output()
    wf = (OpWorkflow().set_result_features(pred).set_input_dataset(dataset)
          .with_selector_cv())
    return wf, sel, label, checked


def rehearsal_config(cfg: Dict[str, Any], rows: int) -> Dict[str, Any]:
    """The same configuration at ``rows`` rows, the cap kept below the train
    split so the cap's branch runs."""
    cfg = dict(cfg)
    cfg["rows"] = int(rows)
    cfg["max_training_sample"] = min(int(cfg["max_training_sample"]),
                                     int(rows * 0.6))
    sc = dict(cfg["sanity_checker"])
    sc["sample_upper_limit"] = min(sc["sample_upper_limit"], max(rows // 2, 1))
    cfg["sanity_checker"] = sc
    return cfg


def split_rows(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(rows the sweep trains on, holdout rows) the configuration gives."""
    hold = int(round(int(cfg["rows"]) * float(cfg["holdout_fraction"])))
    return min(int(cfg["rows"]) - hold, int(cfg["max_training_sample"])), hold


# ---------------------------------------------------------------------------
# counters the guards read
# ---------------------------------------------------------------------------
class CompileCounter:
    """Programs jax compiled or read from its persistent cache, from jax's
    own monitoring events (copy of ``chip_smoke.CompileCounter``)."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def snapshot(self) -> Dict[str, float]:
        return {"programs": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "compile_s": self.seconds}


def sweep_record() -> Dict[str, Any]:
    from transmogrifai_tpu.ops import sweep

    return sweep.run_stats()


def check_sweep_record(stats: Dict[str, Any], n_candidates: int) -> int:
    """The last sweep's launches, or :class:`GuardFailed`: the CV launches
    must cover the grid exactly once, none served from a checkpoint, none
    fallen back.  Returns the number of launches."""
    launches = stats["launches"]
    covered = sum(int(l["candidates"]) for l in launches)
    if covered != n_candidates:
        raise GuardFailed(f"CV launches cover {covered} candidates, the grid "
                          f"has {n_candidates}: {launches}")
    if any(l.get("checkpoint") == "hit" for l in launches) \
            or stats.get("checkpoint_skips", 0):
        raise GuardFailed(f"a launch was served from a checkpoint: {launches}")
    if stats["fallbacks"]:
        raise GuardFailed(f"sweep fell back: {stats['fallbacks']}")
    return len(launches)


def stage_walls(listener) -> Dict[str, float]:
    """``stage.phase`` -> seconds summed over the listener's records."""
    out: Dict[str, float] = {}
    for m in listener.metrics.stage_metrics:
        key = f"{m.stage_name}.{m.phase}"
        out[key] = out.get(key, 0.0) + m.duration_ms / 1e3
    return out


def answers_of(selected) -> Dict[str, Any]:
    """What one timed step produced, as plain values for the comparison."""
    sm = selected.summary
    return {
        "fold_metrics": [list(r["foldMetrics"]) for r in sm.validation_results],
        "mean_metrics": [float(r["metricValue"]) for r in sm.validation_results],
        "errors": [r["error"] for r in sm.validation_results],
        "winner_type": sm.best_model_type,
        "winner_grid": dict(sm.best_grid),
        "holdout": {k: float(sm.holdout_evaluation[k])
                    for k in ("AuPR", "AuROC")},
    }
