"""DAG computation and layered fitting — the FitStagesUtil analog.

Reference parity: core/.../utils/stages/FitStagesUtil.scala:51 —

- ``compute_dag``: stages grouped into antichain layers by max distance from
  the result features (:173-198),
- ``fit_and_transform_dag``: fold over layers fitting estimators then
  transforming train (+test) (:212),
- a whole layer's transformers are applied as one fused pass (:96 —
  applyOpTransformations fuses the layer's row closures into ONE rdd.map;
  here the layer's pure batch functions execute back-to-back on columnar
  data and everything dense runs inside XLA),
- ``cut_dag``: split the DAG into before/during/after the ModelSelector for
  leakage-free workflow-level CV (:302, at most one ModelSelector :310).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..columns import Dataset
from ..features.feature import Feature
from ..features.generator import FeatureGeneratorStage
from ..stages.base import Estimator, Model, PipelineStage, Transformer

Layer = List[PipelineStage]


def compute_dag(result_features: Sequence[Feature]) -> List[Layer]:
    """Stages layered by max distance from the results, farthest first.

    Raw-feature origin stages (FeatureGeneratorStage) are excluded — their
    work happens at read time (reference excludes them the same way:
    FitStagesUtil.computeDAG filters to OPStage estimators/transformers).
    """
    dist: Dict[str, int] = {}
    stages: Dict[str, PipelineStage] = {}
    for rf in result_features:
        for stage, d in rf.parent_stages().items():
            if isinstance(stage, FeatureGeneratorStage):
                continue
            if stage.uid not in dist or dist[stage.uid] < d:
                dist[stage.uid] = d
                stages[stage.uid] = stage
    if not dist:
        return []
    by_layer: Dict[int, Layer] = {}
    for uid, d in dist.items():
        by_layer.setdefault(d, []).append(stages[uid])
    # farthest from result first; deterministic order within a layer
    return [sorted(by_layer[d], key=lambda s: s.uid)
            for d in sorted(by_layer, reverse=True)]


@dataclass
class FittedDAG:
    """Result of fit_and_transform_dag (FitStagesUtil.FittedDAG)."""

    train: Dataset
    test: Optional[Dataset]
    fitted_stages: List[PipelineStage]


#: jitted fused-layer programs keyed by the participating model objects;
#: bounded FIFO (each entry pins its models + a compiled executable, so an
#: unbounded cache would leak across repeated train() calls in one process)
_FUSED_JIT: "collections.OrderedDict[Tuple[int, ...], Tuple[object, list]]" = \
    __import__("collections").OrderedDict()
_FUSED_JIT_MAX = 32
# serving replicas score through this cache concurrently
_FUSED_JIT_LOCK = __import__("threading").Lock()


def _fusable(t, ds: Dataset) -> bool:
    from ..columns import NumericColumn, VectorColumn

    if not (hasattr(t, "jax_transform") and t.n_outputs == 1):
        return False
    cols = [ds.columns.get(f.name) for f in t.inputs]
    if any(c is None for c in cols):
        return False
    if hasattr(t, "jax_host_prep"):
        # stage does its own host-side preprocessing (e.g. categorical code
        # lookup) and feeds small integer arrays into the fused launch
        ready = getattr(t, "jax_host_ready", None)
        return ready(cols) if ready is not None else True
    return all(isinstance(c, (NumericColumn, VectorColumn)) for c in cols)


def fused_stage_coverage(ds: Dataset, transformers: Sequence[Transformer]
                         ) -> Tuple[int, int]:
    """(fusable, total) transformer counts for a layer — the VERDICT r3 #6
    coverage metric (tests assert >= 80% of Titanic transform stages fuse)."""
    return sum(1 for t in transformers if _fusable(t, ds)), len(transformers)


def _fused_layer(ds: Dataset, fusables: Sequence[Transformer]) -> Dict[str, Any]:
    """Compile a whole layer's transforms into ONE jitted XLA computation
    (SURVEY §7: the applyOpTransformations fused-pass analog, one launch per
    layer instead of one per stage).  Metadata is built host-side per stage."""
    import jax
    import jax.numpy as jnp

    from .. import types as T
    from ..columns import NumericColumn, VectorColumn

    # each DISTINCT input column uploads once per launch: stages in one layer
    # commonly share inputs, and a second jnp.asarray on the same host array
    # would be a second device buffer
    flat = []
    pos_of: Dict[Any, int] = {}
    stage_pos = []

    def _upload(key, build):
        i = pos_of.get(key)
        if i is None:
            i = len(flat)
            pos_of[key] = i
            flat.append(build())
        return i

    for t in fusables:
        idxs = []
        if hasattr(t, "jax_host_prep"):
            # host-side prep (e.g. string -> category codes); the expansion
            # and everything downstream run inside the fused XLA launch —
            # prep outputs are per-stage, so they do not dedupe
            for a in t.jax_host_prep([ds[f.name] for f in t.inputs]):
                idxs.append(len(flat))
                flat.append(jnp.asarray(a))
        else:
            for f in t.inputs:
                col = ds[f.name]
                if isinstance(col, NumericColumn):
                    idxs.append(_upload(
                        (f.name, "v"),
                        lambda c=col: jnp.asarray(c.values, jnp.float32)))
                    idxs.append(_upload(
                        (f.name, "m"), lambda c=col: jnp.asarray(c.mask)))
                else:
                    idxs.append(_upload(
                        (f.name, "vec"),
                        lambda c=col: jnp.asarray(c.values, jnp.float32)))
        stage_pos.append(tuple(idxs))
    key = (tuple(id(t) for t in fusables), tuple(stage_pos))
    with _FUSED_JIT_LOCK:
        cached = _FUSED_JIT.get(key)
        if cached is not None:
            _FUSED_JIT.move_to_end(key)
    if cached is None:
        ts = list(fusables)
        sp = tuple(stage_pos)

        def fused(args):
            return [t.jax_transform(*(args[i] for i in idxs))
                    for t, idxs in zip(ts, sp)]

        built = (jax.jit(fused), ts)  # ts ref pins ids against gc reuse
        with _FUSED_JIT_LOCK:
            cached = _FUSED_JIT.setdefault(key, built)
            while len(_FUSED_JIT) > _FUSED_JIT_MAX:
                _FUSED_JIT.popitem(last=False)
    outs = cached[0](flat)
    new_cols = {}
    for t, out in zip(fusables, outs):
        feat = t.get_outputs()[0]
        if getattr(t, "jax_output", "vector") == "numeric":
            vals, mask = out
            new_cols[feat.name] = NumericColumn(
                feat.ftype, np.asarray(vals), np.asarray(mask))
        else:
            vm = t.jax_out_metadata([ds[f.name] for f in t.inputs])
            new_cols[feat.name] = VectorColumn(T.OPVector, np.asarray(out), vm)
    return new_cols


#: above this many rows the single-launch fused layer is skipped: it
#: materializes every fused output full-width back to the host columnar
#: store — at 10M x 500 the device->host pull alone dwarfs the compute.
#: Above the threshold the STREAMING executor (workflow/stream.py) takes
#: over instead of the old per-stage host fallback: fixed-size chunks,
#: double-buffered uploads, device-resident intermediates, terminal-only
#: pulls.  TMOG_STREAM=0 restores the pre-stream host fallback.
def _fuse_max_rows() -> int:
    from ..utils.env import env_int

    return env_int("TMOG_FUSE_MAX_ROWS", 200_000)


def _apply_layer_transforms(ds: Dataset, transformers: Sequence[Transformer],
                            try_stream: bool = True) -> Dataset:
    """Fused layer transform (applyOpTransformations analog,
    FitStagesUtil.scala:96): transformers implementing the ``jax_transform``
    protocol compile into ONE jitted computation per layer; the rest apply
    per stage off the same input batch.  Above the fuse-row threshold the
    layer streams in chunks (workflow/stream.py) instead."""
    if try_stream and len(ds) > _fuse_max_rows():
        from . import stream as stream_mod

        out = stream_mod.apply_streamed(ds, [list(transformers)])
        if out is not None:
            return out
    new_cols = {}
    fusables = ([t for t in transformers if _fusable(t, ds)]
                if len(ds) <= _fuse_max_rows() else [])
    fusable_ids = {id(t) for t in fusables}
    rest = [t for t in transformers if id(t) not in fusable_ids]
    if len(fusables) == 1:  # no fusion win; avoid a second jit cache entry
        rest = list(transformers)
        fusables = []
    if fusables:
        with _maybe_time(_FusedLabel(fusables), "transform", len(ds)):
            new_cols.update(_fused_layer(ds, fusables))
    big = len(ds) > _fuse_max_rows()
    for t in rest:
        out_feats = t.get_outputs()
        with _maybe_time(t, "transform", len(ds)):
            col = None
            if big:
                # past the fuse cliff, unfusable prediction heads (the
                # winner's modelSelector.transform) score in round-robin
                # chunks across the stream devices when a data mesh is
                # active; None keeps the generic single-pass path
                from . import stream as stream_mod

                col = stream_mod.maybe_score_sharded(t, ds)
            if col is None:
                col = t.transform_dataset(ds)
        if t.n_outputs == 1:
            new_cols[out_feats[0].name] = col
        else:
            for f, c in zip(out_feats, col):
                new_cols[f.name] = c
    return ds.with_columns(new_cols)


class _FusedLabel:
    """Listener label for a fused layer launch."""

    def __init__(self, ts):
        self.operation_name = "fused[" + "+".join(
            getattr(t, "operation_name", "?") for t in ts) + "]"
        self.uid = "fused:" + ",".join(getattr(t, "uid", "?") for t in ts)


def _maybe_time(stage, phase: str, n_rows: int):
    """Report into the installed OpListener, if any (OpSparkListener analog)."""
    from ..utils.listener import current_listener

    listener = current_listener()
    if listener is None:
        import contextlib

        return contextlib.nullcontext()
    return listener.time_stage(stage, phase, n_rows)


#: free dead intermediate columns once a dataset exceeds this many cells —
#: the Spark persist/unpersist cadence analog (FitStagesUtil.scala:117,158);
#: below it, keeping intermediates aids debugging and costs nothing
FREE_INTERMEDIATES_CELLS = 100_000_000


def _dead_columns(dag: List[Layer], layer_idx: int, ds: Dataset) -> List[str]:
    """Columns no stage after ``layer_idx`` consumes and that are not
    responses (labels feed evaluators after training)."""
    live = set()
    for later in dag[layer_idx + 1:]:
        for stage in later:
            for f in stage.inputs:
                live.add(f.name)
    if dag:
        for stage in dag[-1]:
            for f in stage.get_outputs():
                live.add(f.name)
    dead = []
    for name, col in ds.columns.items():
        if name in live:
            continue
        if getattr(getattr(col, "ftype", None), "__name__", "") == "Prediction":
            continue
        dead.append(name)
    return dead


def _maybe_free(dag: List[Layer], layer_idx: int, ds: Dataset,
                responses: set) -> Dataset:
    try:
        n = len(ds)
    except Exception:
        return ds
    total_cells = sum(n * (getattr(c, "width", None) or 1)
                      for c in ds.columns.values())
    if total_cells < FREE_INTERMEDIATES_CELLS:
        return ds
    dead = [c for c in _dead_columns(dag, layer_idx, ds) if c not in responses]
    return ds.drop(dead) if dead else ds


def _live_after(dag: List[Layer], layer_idx: int, responses: set) -> Set[str]:
    """Column names still needed after ``layer_idx`` — the complement of
    ``_dead_columns`` for not-yet-materialized stream outputs."""
    live: Set[str] = set(responses)
    for later in dag[layer_idx + 1:]:
        for stage in later:
            for f in stage.inputs:
                live.add(f.name)
    if dag:
        for stage in dag[-1]:
            for f in stage.get_outputs():
                live.add(f.name)
    return live


def _selector_input_names(dag: List[Layer], layer_idx: int) -> Set[str]:
    """Inputs of any downstream ModelSelector — candidates for the stream's
    device-side X handoff into the sweep."""
    return {f.name for later in dag[layer_idx + 1:] for s in later
            if getattr(s, "is_model_selector", False) for f in s.inputs}


def _total_cells(ds: Dataset) -> int:
    try:
        n = len(ds)
    except Exception:
        return 0
    return sum(n * (getattr(c, "width", None) or 1)
               for c in ds.columns.values())


def _apply_pending(ds: Dataset, pending: List[Tuple[int, List[Transformer]]],
                   dag: List[Layer], responses: set,
                   handoff: Optional[Set[str]] = None) -> Dataset:
    """Apply a run of deferred transformer layers, streaming them as ONE
    cross-layer chunked program when the data is past the fuse-row cliff.
    Liveness-based skipping of intermediates only engages past the same
    cell threshold as ``_maybe_free`` — below it, materializing everything
    keeps small-data debugging (and test fixtures) byte-identical."""
    last_li = pending[-1][0]
    if len(ds) > _fuse_max_rows():
        from . import stream as stream_mod

        live = (_live_after(dag, last_li, responses)
                if _total_cells(ds) >= FREE_INTERMEDIATES_CELLS else None)
        out = stream_mod.apply_streamed(
            ds, [ts for _, ts in pending], live=live, handoff=handoff)
        if out is not None:
            return _maybe_free(dag, last_li, out, responses)
    for li, ts in pending:
        ds = _apply_layer_transforms(ds, ts, try_stream=False)
        ds = _maybe_free(dag, li, ds, responses)
    return ds


def fit_and_transform_dag(dag: List[Layer], train: Dataset,
                          test: Optional[Dataset] = None,
                          fitted_so_far: Optional[Dict[str, PipelineStage]] = None,
                          responses: Optional[set] = None,
                          ) -> FittedDAG:
    """Fit estimators layer by layer, transforming train (+test) as we go.

    ``fitted_so_far`` maps stage uid -> already-fitted model — the analog of
    ``OpWorkflow.withModelStages`` warm-starting (OpWorkflow.scala:468): those
    stages are applied, not refitted.  On large data, intermediate columns
    that no later stage consumes are freed after each layer (KeepRawFeatures
    defaults false in the reference, OpWorkflowModel.scala:458-463).

    Transformer-only layers (pre-fitted models and pure transformers) are
    DEFERRED and flushed together right before the next estimator fit needs
    their outputs — past the fuse-row cliff the whole run streams as one
    cross-layer chunked program (workflow/stream.py) instead of bouncing
    each layer's full-width output through the host store.
    """
    fitted_so_far = fitted_so_far or {}
    responses = responses or set()
    fitted: List[PipelineStage] = []
    pending: List[Tuple[int, List[Transformer]]] = []

    def flush(train: Dataset, test: Optional[Dataset]
              ) -> Tuple[Dataset, Optional[Dataset]]:
        if not pending:
            return train, test
        handoff = _selector_input_names(dag, pending[-1][0])
        train = _apply_pending(train, pending, dag, responses,
                               handoff=handoff or None)
        if test is not None:
            test = _apply_pending(test, pending, dag, responses)
        pending.clear()
        return train, test

    for li, layer in enumerate(dag):
        if any(isinstance(s, Estimator) and s.uid not in fitted_so_far
               for s in layer):
            train, test = flush(train, test)
        transformers: List[Transformer] = []
        for stage in layer:
            if stage.uid in fitted_so_far:
                model = fitted_so_far[stage.uid]
                transformers.append(model)
                fitted.append(model)
            elif isinstance(stage, Estimator):
                with _maybe_time(stage, "fit", len(train)):
                    model = stage.fit(train)
                transformers.append(model)
                fitted.append(model)
            elif isinstance(stage, Transformer):
                transformers.append(stage)
                fitted.append(stage)
            else:
                raise TypeError(f"Stage {stage} is neither Estimator nor Transformer")
        pending.append((li, transformers))
    train, test = flush(train, test)
    return FittedDAG(train=train, test=test, fitted_stages=fitted)


def apply_transformations_dag(ds: Dataset, dag: List[Layer],
                              keep: Optional[Sequence[str]] = None) -> Dataset:
    """Scoring path: all stages must already be transformers
    (OpWorkflowCore.applyTransformationsDAG, OpWorkflowCore.scala:324).

    Past the fuse-row cliff the ENTIRE scoring DAG streams as one chunked
    program.  ``keep`` (optional) names the columns the caller consumes
    afterwards (e.g. the result features) — device-resident intermediates
    not in it are never materialized to host; default keeps every output.
    """
    layers: List[List[Transformer]] = []
    for layer in dag:
        transformers = []
        for stage in layer:
            if not isinstance(stage, Transformer):
                raise TypeError(
                    f"Scoring DAG contains unfitted estimator {stage}; fit the workflow first")
            transformers.append(stage)
        layers.append(transformers)
    if layers and len(ds) > _fuse_max_rows():
        from . import stream as stream_mod

        live = None
        if keep is not None and _total_cells(ds) >= FREE_INTERMEDIATES_CELLS:
            live = set(keep) | {f.name for s in dag[-1] for f in s.get_outputs()}
        out = stream_mod.apply_streamed(ds, layers, live=live)
        if out is not None:
            return out
    for transformers in layers:
        ds = _apply_layer_transforms(ds, transformers, try_stream=False)
    return ds


@dataclass
class CutDAG:
    """DAG split around the ModelSelector (FitStagesUtil.CutDAG)."""

    model_selector: Optional[PipelineStage]
    before: List[Layer]
    during: List[Layer]
    after: List[Layer]


def cut_dag(dag: List[Layer]) -> CutDAG:
    """Split for workflow-level CV (FitStagesUtil.cutDAG:302).

    Reference semantics: 'during' (refit per fold) is the suffix of the
    selector's ancestor sub-DAG starting at the FIRST layer containing a
    label-using stage (inputs mix response and predictors — e.g. a
    SanityChecker); label-free feature engineering cannot leak the label, so
    it fits once in 'before' (:330-344 firstCVTSIndex).  Whole layers are
    taken from that point, so transformers downstream of refit estimators
    refit too.  Layers closer to the result than the selector are 'after'.
    The selector itself terminates 'during'.  At most one ModelSelector
    (:310)."""
    selectors = [(i, s) for i, layer in enumerate(dag) for s in layer
                 if getattr(s, "is_model_selector", False)]
    if not selectors:
        return CutDAG(None, before=dag, during=[], after=[])
    if len(selectors) > 1:
        raise ValueError(
            f"Only one ModelSelector is supported per workflow, found {len(selectors)}")
    idx, selector = selectors[0]
    # the selector's ancestor sub-DAG (farthest first, selector not included)
    anc = compute_dag(list(selector.inputs))
    ci = next((i for i, layer in enumerate(anc) for s in layer
               if any(f.is_response for f in s.inputs)
               and any(not f.is_response for f in s.inputs)), None)
    during_feats: List[Layer] = [list(l) for l in anc[ci:]] if ci is not None else []
    during_uids: Set[str] = {s.uid for layer in during_feats for s in layer}

    before: List[Layer] = []
    for layer in dag[:idx + 1]:
        keep = [s for s in layer if s is not selector and s.uid not in during_uids]
        if keep:
            before.append(keep)
    after: List[Layer] = [list(l) for l in dag[idx + 1:]]
    return CutDAG(selector, before=before,
                  during=during_feats + [[selector]], after=after)
