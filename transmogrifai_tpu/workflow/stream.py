"""Streaming cross-layer transform executor — chunked, double-buffered,
device-resident feature materialization.

The per-layer fused path (`workflow/dag._fused_layer`) compiles one layer at
a time and materializes every fused output back into the host columnar store
between layers.  That full-width device->host bounce is why the fused device
path used to be disabled above ``TMOG_FUSE_MAX_ROWS`` — a 10M x 500
device->host round trip per layer dwarfs the compute.  This module removes
the cliff:

- ``build_plan`` walks a run of DAG layers and compiles the entire fusable
  transform sub-DAG (all layers, up to the first unfusable stage per output
  chain) into ONE jitted per-chunk program.  Stage outputs consumed only by
  later fused stages stay device-resident for the whole chunk; only
  *terminal* columns (consumed by a host stage or live downstream) are
  pulled, once per chunk.
- ``execute`` streams fixed-size row chunks through the program: constant
  chunk shape (``TMOG_TRANSFORM_CHUNK_ROWS``) with a zero-padded, mask-aware
  tail so there is exactly ONE compilation per device; background prefetch
  threads slice/pad chunk k+1's host buffers while chunk k computes, and
  async ``jax.device_put`` + dispatch keep ``TMOG_STREAM_BUFFERS`` chunks
  in flight per device; input buffers are donated so XLA reuses them in
  place.
- When a data mesh is active (TMOG_MESH / ``parallel.mesh.use_mesh``) or
  ``TMOG_STREAM_SHARDS`` asks for it, chunks dispatch round-robin across
  ``parallel.mesh.stream_devices()`` (``TMOG_STREAM_ROUTE`` policy): the
  per-chunk program compiles once per device and D chunks compute
  concurrently, one per chip.  Prediction-head stages exposing the
  ``predict_program`` contract additionally score in round-robin chunks
  across the same devices (``score_head_sharded``) so the winner's
  ``modelSelector.transform`` stops being a single-chip full-width pass.
  With TMOG_MESH unset and no explicit shard request the executor is
  bit-identical to the single-device path.
- When a downstream consumer is the model selector, the final feature
  matrix chunks are additionally kept device-side (``device_view`` /
  ``handoff_rows``) and seeded into ``utils.devcache`` so the selector
  sweep's ``devcache.device_array(X, float32)`` finds the resident buffer
  and skips the host->device re-upload entirely.

Chunk-safe ``jax_transform`` contract (documented here, asserted in the
planner): stages must be row-wise — output row i depends only on input
row i — with no data-dependent shapes, and ``jax_host_prep``/``
jax_out_metadata`` must tolerate per-chunk slices (metadata is computed
ONCE at plan time and reused for every chunk).  All shipped jax stages
satisfy this; the same zero-fill + mask idiom is proven by
``parallel/stats.py``'s one-pass streaming moments.

Telemetry mirrors ``ops/sweep.run_stats``: ``stream_stats()`` reports
chunk counts, streamed bytes, compile counts (``<=1`` in steady state) and
the transfer-wait share of wall time (overlap efficiency).
"""
from __future__ import annotations

import os
import queue
import threading
import time
import warnings
import weakref
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .. import types as T
from ..columns import Dataset, NumericColumn, ObjectColumn, VectorColumn
from ..obs import registry as obs_registry
from ..obs import trace
from ..resilience import checkpoint as _ckpt
from ..resilience import inject as _inject
from ..resilience import quarantine as _quar
from ..resilience import retry as _retry
from ..utils import env


# ---------------------------------------------------------------------------
# Env knobs (utils/env empty-string-tolerant helpers) + costmodel autotune.
#
# Resolution order per knob: the USER'S env value always wins; when the env
# slot is unset/empty and the learned cost model (TMOG_COSTMODEL=1) carries
# a streaming proposal trained from recorded telemetry, the proposal
# applies (and is recorded under stream_stats()["autotune"]); otherwise the
# hard default — so with the model off, knob selection is bit-identical to
# the pre-costmodel behavior.
# ---------------------------------------------------------------------------
def _autotune_proposal() -> Dict[str, Any]:
    """The active model's streaming proposal ({} when the model is off,
    unloadable, or has no stream evidence).  Never raises."""
    try:
        from .. import costmodel

        m = costmodel.active_model()
        if m is None:
            return {}
        try:
            from ..parallel import mesh as pmesh

            shards = pmesh.stream_shards()
        except Exception:
            shards = None
        prop = m.stream_proposal(shards=shards)
        if prop:
            _stream_scope.set("autotune", dict(prop))
        return prop
    except Exception:
        return {}


def _knob(name: str, default: int, proposal_key: str,
          floor: Optional[int] = 1) -> int:
    def clamp(v: int) -> int:
        return v if floor is None else max(floor, v)

    if env.env_set(name):
        return clamp(env.env_int(name, default))
    prop = _autotune_proposal().get(proposal_key)
    if prop:
        try:
            return clamp(int(prop))
        except (TypeError, ValueError):
            pass
    return default


def chunk_rows() -> int:
    """Rows per streamed chunk (TMOG_TRANSFORM_CHUNK_ROWS, default 256Ki;
    autotuned from telemetry when unset and TMOG_COSTMODEL=1)."""
    return _knob("TMOG_TRANSFORM_CHUNK_ROWS", 262_144, "chunk_rows")


def stream_buffers() -> int:
    """In-flight chunk window (TMOG_STREAM_BUFFERS, default 2 = double
    buffering: chunk k+1 uploads while chunk k computes; autotuned from
    telemetry when unset and TMOG_COSTMODEL=1)."""
    return _knob("TMOG_STREAM_BUFFERS", 2, "buffers")


def enabled() -> bool:
    """TMOG_STREAM=0 disables streaming (restores the pre-stream host path
    above TMOG_FUSE_MAX_ROWS)."""
    return os.environ.get("TMOG_STREAM", "1") != "0"


def handoff_budget_bytes() -> int:
    """Device-byte budget for keeping selector-bound output chunks resident
    (TMOG_STREAM_HANDOFF_BYTES, default 2 GiB).  Above it the handoff is
    skipped and the selector re-uploads from host as before."""
    return _knob("TMOG_STREAM_HANDOFF_BYTES", 2_147_483_648,
                 "handoff_budget_bytes", floor=None)


def prefetch_workers(n_devices: int = 1) -> int:
    """Background host-prep threads per stream (TMOG_STREAM_PREFETCH).

    0 disables prefetch (chunk slicing/padding runs inline on the dispatch
    thread — the pre-pipelined behavior, where ``overlap_efficiency``
    honestly reports ~0).  Default: one worker per stream device, capped at
    4 — host prep is numpy memcpy-bound and oversubscribing it just churns
    the GIL."""
    if env.env_set("TMOG_STREAM_PREFETCH"):
        return max(0, env.env_int("TMOG_STREAM_PREFETCH", 1))
    return max(1, min(int(n_devices), 4))


def _stream_devices() -> list:
    """Dispatch targets for this stream: ``[None]`` (legacy default device)
    unless a data mesh / TMOG_STREAM_SHARDS requests sharding — see
    ``parallel.mesh.stream_devices``.  Never raises."""
    try:
        from ..parallel import mesh as pmesh

        return pmesh.stream_devices()
    except Exception:
        return [None]


# ---------------------------------------------------------------------------
# Telemetry (ops/sweep.run_stats pattern) — storage lives in the central obs
# registry (scope "stream"); stream_stats() below is the backward-compatible
# view over it, and is also what obs.snapshot()["stream"] reports.
# ---------------------------------------------------------------------------
_stream_scope = obs_registry.scope("stream", defaults=dict(
    streams=0, chunks=0, rows=0, pad_rows=0, chunk_rows=0, buffers=0,
    shards=0, stages_fused=0, stages_host=0, layers=0,
    terminals=0, device_only=0,
    bytes_in=0.0, bytes_out=0.0, compiles=0,
    device_handoffs=0, handoff_bytes=0.0,
    upload_s=0.0, pull_wait_s=0.0, wall_s=0.0,
    prep_s=0.0, prep_blocked_s=0.0,
    score_stages=0, score_chunks=0,
    checkpoint_skips=0, quarantined=0,
    by_device={}, autotune={}, fallbacks=[],
))


def reset_stream_stats() -> None:
    _stream_scope.reset()


def stream_stats() -> Dict[str, Any]:
    out = _stream_scope.snapshot()
    wall = out["wall_s"]
    # overlap = share of host-side chunk prep genuinely hidden behind device
    # execution: prep_s is the work the prefetch threads did, prep_blocked_s
    # is how long the dispatch thread actually stalled waiting for them.
    # The old definition (1 - transfer/wall) read 0.002 because "upload_s"
    # included the inline host prep that serialized the whole pipeline; with
    # prefetch off, prep_blocked_s == prep_s and this still honestly reads 0.
    prep = out["prep_s"]
    if prep > 0:
        out["overlap_efficiency"] = max(
            0.0, min(1.0, 1.0 - out["prep_blocked_s"] / prep))
    else:
        out["overlap_efficiency"] = (
            max(0.0, 1.0 - (out["pull_wait_s"] + out["upload_s"]) / wall)
            if wall > 0 else 0.0)
    out["transform_rows_per_sec"] = out["rows"] / wall if wall > 0 else 0.0
    return out


obs_registry.register_provider("stream", stream_stats)


def record_fallback(reason: str, **detail: Any) -> None:
    """Delegates to the one central recorder (obs.registry.record_fallback,
    domain="stream"); ``stream_stats()["fallbacks"]`` is the audit trail."""
    obs_registry.record_fallback("stream", reason, **detail)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------
class _ProxyCol:
    """Plan-time stand-in for a device-resident intermediate: carries only
    what ``jax_out_metadata`` implementations read (.metadata/.width/.ftype)."""

    def __init__(self, ftype, metadata=None, width=None):
        self.ftype = ftype
        self.metadata = metadata
        self.width = width


@dataclass
class _StreamStage:
    stage: Any
    prep: bool                                  # per-chunk jax_host_prep
    arg_specs: Tuple[Tuple[str, str], ...]      # (kind, column name)
    out_name: str
    out_kind: str                               # "numeric" | "vector"
    ftype: Any
    metadata: Any                               # VectorMetadata (vector outs)
    terminal: bool = True


@dataclass
class StreamPlan:
    stages: List[_StreamStage]
    host_layers: List[List[Any]]                # per input layer, unfused rest
    base_numeric: List[str]
    base_vector: List[str]
    handoff: Set[str] = field(default_factory=set)
    key: Tuple = ()

    @property
    def n_stream(self) -> int:
        return len(self.stages)


def _try_plan_stage(t, ds: Dataset, internal: Dict[str, str],
                    proxies: Dict[str, Any]) -> Optional[_StreamStage]:
    """One stage's slot in the streamed program, or None -> host path.

    Stream-fusable = has ``jax_transform``, single output, and every input
    is either a base Numeric/Vector column of ``ds`` or the output of an
    earlier fused stage (device-resident).  ``jax_host_prep`` stages fuse
    only when ALL inputs are base columns — host prep needs host data, so a
    chain through a device-resident intermediate is cut here (the stage and
    its dependents run host-side after the stream, preserving DAG order).
    """
    if not (hasattr(t, "jax_transform") and getattr(t, "n_outputs", 0) == 1):
        return None
    # chunk-safety is opt-out: the fused-layer protocol is row-wise by
    # construction (every shipped jax_transform maps input row i to output
    # row i with no data-dependent shapes); a stage whose device math needs
    # the whole column at once must set jax_chunkable = False to stay on
    # the single-launch / host paths
    if not getattr(t, "jax_chunkable", True):
        return None
    names = [f.name for f in t.inputs]
    if hasattr(t, "jax_host_prep"):
        if any(nm in internal for nm in names):
            return None
        cols = [ds.columns.get(nm) for nm in names]
        if any(c is None for c in cols):
            return None
        ready = getattr(t, "jax_host_ready", None)
        if ready is not None and not ready(cols):
            return None
        prep, specs, in_cols = True, [], cols
    else:
        prep, specs, in_cols = False, [], []
        for nm in names:
            if nm in internal:
                if internal[nm] == "numeric":
                    specs += [("inv", nm), ("inm", nm)]
                else:
                    specs.append(("iv", nm))
                in_cols.append(proxies[nm])
            else:
                c = ds.columns.get(nm)
                if isinstance(c, NumericColumn):
                    specs += [("nv", nm), ("nm", nm)]
                elif isinstance(c, VectorColumn):
                    specs.append(("bv", nm))
                else:
                    return None
                in_cols.append(c)
    out_feat = t.get_outputs()[0]
    kind = ("numeric" if getattr(t, "jax_output", "vector") == "numeric"
            else "vector")
    vm = None
    if kind == "vector":
        try:
            # per-chunk metadata reuse: built ONCE here, never per chunk
            vm = t.jax_out_metadata(in_cols)
        except Exception:
            return None  # proxy lacked what this stage needs -> host path
    return _StreamStage(stage=t, prep=prep, arg_specs=tuple(specs),
                        out_name=out_feat.name, out_kind=kind,
                        ftype=out_feat.ftype, metadata=vm)


def build_plan(ds: Dataset, layers: Sequence[Sequence[Any]],
               live: Optional[Set[str]] = None,
               handoff: Optional[Set[str]] = None) -> Optional[StreamPlan]:
    """Compile-plan a run of DAG layers into one streamed program.

    ``live``: column names needed after these layers (None = keep every
    output).  Fused outputs consumed only inside the plan and not live are
    never materialized to host — the ``_dead_columns``-style liveness win.
    ``handoff``: names whose device chunks should stay resident for the
    model-selector handoff.  Returns None when fewer than two stages fuse
    (no cross-stage win; callers fall back to the per-layer paths).
    """
    internal: Dict[str, str] = {}
    proxies: Dict[str, Any] = {}
    stages: List[_StreamStage] = []
    host_layers: List[List[Any]] = []
    base_numeric: List[str] = []
    base_vector: List[str] = []
    seen: Set[str] = set()

    for layer in layers:
        host_this: List[Any] = []
        for t in layer:
            entry = _try_plan_stage(t, ds, internal, proxies)
            if entry is None:
                host_this.append(t)
                continue
            stages.append(entry)
            internal[entry.out_name] = entry.out_kind
            if entry.out_kind == "numeric":
                proxies[entry.out_name] = _ProxyCol(entry.ftype)
            else:
                vm = entry.metadata
                proxies[entry.out_name] = _ProxyCol(
                    T.OPVector, metadata=vm,
                    width=len(vm.columns) if vm is not None else None)
            for kind, nm in entry.arg_specs:
                if kind in ("nv", "nm") and nm not in seen:
                    seen.add(nm)
                    base_numeric.append(nm)
                elif kind == "bv" and nm not in seen:
                    seen.add(nm)
                    base_vector.append(nm)
        host_layers.append(host_this)

    if len(stages) < 2:
        return None

    host_inputs = {f.name for lay in host_layers for t in lay
                   for f in t.inputs}
    for e in stages:
        e.terminal = (e.out_name in host_inputs
                      or live is None or e.out_name in live)
    hand = set(handoff or ()) & {e.out_name for e in stages if e.terminal}
    key = (tuple(id(e.stage) for e in stages),
           tuple(e.arg_specs for e in stages),
           tuple(e.terminal for e in stages))
    return StreamPlan(stages=stages, host_layers=host_layers,
                      base_numeric=base_numeric, base_vector=base_vector,
                      handoff=hand, key=key)


# ---------------------------------------------------------------------------
# Jitted per-chunk program (bounded cache, one compile per plan shape)
# ---------------------------------------------------------------------------
_PROGRAMS: "OrderedDict[Tuple, Tuple[Any, List[_StreamStage]]]" = OrderedDict()
_PROGRAMS_MAX = 16
# serve replicas warm concurrently against the shared program cache
_PROGRAMS_LOCK = threading.Lock()


def _program_for(plan: StreamPlan):
    import jax

    with _PROGRAMS_LOCK:
        cached = _PROGRAMS.get(plan.key)
        if cached is not None:
            _PROGRAMS.move_to_end(plan.key)
            return cached[0]
    stages = list(plan.stages)

    def program(args):
        env: Dict[str, Any] = {}
        outs: Dict[str, Any] = {}
        for si, e in enumerate(stages):
            if e.prep:
                call = list(args[f"p{si}"])
            else:
                call = []
                for kind, nm in e.arg_specs:
                    if kind == "iv":
                        call.append(env[nm])
                    elif kind == "inv":
                        call.append(env[nm][0])
                    elif kind == "inm":
                        call.append(env[nm][1])
                    else:
                        call.append(args[f"{kind}:{nm}"])
            res = e.stage.jax_transform(*call)
            env[e.out_name] = res
            if e.terminal:
                outs[e.out_name] = res
        return outs

    # donated inputs: each chunk's upload buffers are dead after the
    # launch, so XLA may write outputs over them
    built = (jax.jit(program, donate_argnums=(0,)), stages)
    with _PROGRAMS_LOCK:
        cached = _PROGRAMS.setdefault(plan.key, built)
        while len(_PROGRAMS) > _PROGRAMS_MAX:
            _PROGRAMS.popitem(last=False)
    return cached[0]


def program_for(plan: StreamPlan):
    """The jitted per-chunk program for one plan (serve AOT entry point).

    Returned callable takes the dict built by :func:`chunk_args` and is
    safe to ``.lower()`` against device-committed arguments."""
    return _program_for(plan)


def _cache_size(jitted) -> Optional[int]:
    try:
        return int(jitted._cache_size())
    except Exception:
        return None


# ---------------------------------------------------------------------------
# Chunk building
# ---------------------------------------------------------------------------
def _slice_col(col, lo: int, hi: int):
    if isinstance(col, NumericColumn):
        return NumericColumn(col.ftype, col.values[lo:hi], col.mask[lo:hi])
    if isinstance(col, VectorColumn):
        return VectorColumn(col.ftype, col.values[lo:hi], col.metadata)
    if isinstance(col, ObjectColumn):
        return ObjectColumn(col.ftype, col.values[lo:hi])
    raise TypeError(f"cannot slice column {type(col).__name__} for streaming")


def _pad0(a: np.ndarray, pad: int) -> np.ndarray:
    """Zero-pad along axis 0 to the constant chunk shape.  Padded rows are
    masked out (numeric masks pad False) and sliced off every pulled output,
    so their values are free to be garbage — zeros keep XLA finite-safe."""
    if not pad:
        return a
    return np.concatenate(
        [a, np.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)


def _host_chunk_args(plan: StreamPlan, ds: Dataset, lo: int, hi: int,
                     C: int) -> Tuple[Dict[str, Any], float]:
    rows = hi - lo
    pad = C - rows
    args: Dict[str, Any] = {}
    nbytes = 0.0
    for nm in plan.base_numeric:
        col = ds[nm]
        v = _pad0(np.ascontiguousarray(col.values[lo:hi], np.float32), pad)
        m = _pad0(np.ascontiguousarray(col.mask[lo:hi]), pad)
        args[f"nv:{nm}"] = v
        args[f"nm:{nm}"] = m
        nbytes += v.nbytes + m.nbytes
    for nm in plan.base_vector:
        col = ds[nm]
        v = _pad0(np.ascontiguousarray(col.values[lo:hi], np.float32), pad)
        args[f"bv:{nm}"] = v
        nbytes += v.nbytes
    for si, e in enumerate(plan.stages):
        if not e.prep:
            continue
        cols = [_slice_col(ds[f.name], lo, hi) for f in e.stage.inputs]
        preps = []
        for a in e.stage.jax_host_prep(cols):
            a = np.asarray(a)
            if a.shape[:1] != (rows,):
                raise ValueError(
                    f"jax_host_prep of {e.stage} is not row-aligned "
                    f"({a.shape} for {rows} rows) — not chunk-safe")
            a = _pad0(a, pad)
            preps.append(a)
            nbytes += a.nbytes
        args[f"p{si}"] = preps
    return args, nbytes


def chunk_args(plan: StreamPlan, ds: Dataset, lo: int, hi: int,
               C: int) -> Tuple[Dict[str, Any], float]:
    """Padded host argument dict for one chunk (serve AOT entry point):
    rows [lo, hi) of ``ds`` zero-padded to the constant chunk shape ``C``.
    Returns ``(args, upload_bytes)``."""
    return _host_chunk_args(plan, ds, lo, hi, C)


def _apply_stream_poison(plan: "StreamPlan", host_args: Dict[str, Any],
                         lo: int, rows: int) -> None:
    """Chaos hook (site ``stream.upload`` with a ``poison`` rule): corrupt
    the planted rows of this chunk's upload buffers in place, BEFORE the
    quarantine scan, so the scan is exercised against real garbage.  A
    float32 column can't hold type/text garbage, so those kinds map to NaN
    (``garbage_value`` does the mapping) — the same artifact a reader-side
    coercion failure produces."""
    names = plan.base_numeric
    if not names:
        return
    for idx, kind in _inject.poison_plan("stream.upload", rows, key=lo):
        nm = names[idx % len(names)]
        g = _inject.garbage_value(kind)
        bad = np.float32(g) if isinstance(g, float) else np.float32("nan")
        host_args[f"nv:{nm}"][idx] = bad
        host_args[f"nm:{nm}"][idx] = True


def _quarantine_chunk(plan: "StreamPlan", host_args: Dict[str, Any],
                      lo: int, rows: int, pol: str) -> int:
    """``TMOG_QUARANTINE`` row policy over one chunk's upload buffers.

    A row is bad when any present (mask-True) numeric value, or any cell of
    a vector column, is non-finite.  ``strict`` raises at the first bad
    row; ``fail`` audits every bad row then raises; ``drop`` audits the
    row, then zeroes + masks it out of every upload buffer so the fused
    program treats it exactly like tail padding (numeric outputs masked
    null, vector outputs zero).  Returns the number of rows dropped."""
    bad = np.zeros(rows, bool)
    culprit: Dict[int, str] = {}
    for nm in plan.base_numeric:
        hit = host_args[f"nm:{nm}"][:rows] & \
            ~np.isfinite(host_args[f"nv:{nm}"][:rows])
        for i in np.nonzero(hit & ~bad)[0]:
            culprit[int(i)] = nm
        bad |= hit
    for nm in plan.base_vector:
        v = host_args[f"bv:{nm}"][:rows]
        hit = ~np.isfinite(v).reshape(rows, -1).all(axis=1)
        for i in np.nonzero(hit & ~bad)[0]:
            culprit[int(i)] = nm
        bad |= hit
    if not bad.any():
        return 0
    rows_bad = [int(i) for i in np.nonzero(bad)[0]]
    dls = _quar.store()
    if pol == "strict":
        i = rows_bad[0]
        dls.put("stream", "non_finite", index=lo + i, field=culprit.get(i),
                detail=f"chunk@{lo} row {i} (strict)")
        raise _quar.DataFault("non_finite", index=lo + i,
                              field=culprit.get(i),
                              detail=f"TMOG_QUARANTINE=strict, chunk@{lo}")
    for i in rows_bad:
        dls.put("stream", "non_finite", index=lo + i, field=culprit.get(i),
                detail=f"chunk@{lo} row {i}")
    if pol == "fail":
        raise _quar.DataFault(
            "non_finite", index=lo + rows_bad[0],
            field=culprit.get(rows_bad[0]),
            detail=f"{len(rows_bad)} bad row(s) in chunk@{lo}, "
                   "TMOG_QUARANTINE=fail")
    for nm in plan.base_numeric:
        host_args[f"nv:{nm}"][rows_bad] = np.float32(0.0)
        host_args[f"nm:{nm}"][rows_bad] = False
    for nm in plan.base_vector:
        host_args[f"bv:{nm}"][rows_bad] = np.float32(0.0)
    _stream_scope.inc("quarantined", len(rows_bad))
    return len(rows_bad)


# ---------------------------------------------------------------------------
# Device-view registry (model-selector handoff)
# ---------------------------------------------------------------------------
_views: Dict[int, Dict[str, Any]] = {}


def _register_view(host_arr: np.ndarray, chunks: List[Tuple[Any, int]],
                   n_rows: int) -> bool:
    """Remember the device-resident chunks behind an assembled host matrix,
    keyed (weakly) by the host array's identity — the devcache idiom."""
    total = sum(int(a.nbytes) * r // max(1, a.shape[0]) for a, r in chunks)
    if total > handoff_budget_bytes():
        record_fallback("handoff_over_budget", bytes=total)
        return False
    key = id(host_arr)
    try:
        ref = weakref.ref(host_arr, lambda _r, k=key: _views.pop(k, None))
    except TypeError:
        return False
    _views[key] = {"_ref": ref, "chunks": list(chunks), "full": None,
                   "rows": n_rows}
    return True


def device_view(host_arr) -> Optional[Any]:
    """The device-resident copy of a streamed terminal matrix, or None.
    Chunks are concatenated lazily on first use (tail padding sliced off)."""
    ent = _views.get(id(host_arr))
    if ent is None:
        return None
    if ent["full"] is None:
        import jax
        import jax.numpy as jnp

        parts = [a if int(a.shape[0]) == r else a[:r]
                 for a, r in ent["chunks"]]
        if len(parts) > 1:
            # a sharded stream leaves chunks committed to different devices;
            # concatenation needs them co-located — gather onto the first
            # chunk's device (no-op copies when already there)
            try:
                d0 = next(iter(parts[0].devices()))
                parts = [p if next(iter(p.devices())) == d0
                         else jax.device_put(p, d0) for p in parts]
            except Exception:
                pass  # uncommitted arrays (single-device path): as before
        ent["full"] = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        ent["chunks"] = []  # drop per-chunk refs; keep one buffer
    return ent["full"]


def handoff_rows(src_host, dst_host, idx) -> bool:
    """Device-side row gather: when ``src_host`` has a streamed device view,
    compute ``src[idx]`` on device and seed it into devcache under
    ``dst_host``'s identity, so the sweep's ``device_array(dst, float32)``
    resolves to the resident buffer and the host matrix never re-uploads."""
    view = device_view(src_host)
    if view is None:
        return False
    import jax.numpy as jnp

    from ..utils import devcache

    dev = jnp.take(view, jnp.asarray(np.asarray(idx)), axis=0)
    if not devcache.seed(dst_host, dev, np.float32):
        return False
    _stream_scope.inc("device_handoffs")
    _stream_scope.inc("handoff_bytes", float(dev.nbytes))
    return True


def clear_views() -> None:
    _views.clear()


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------
def execute(plan: StreamPlan, ds: Dataset) -> Dict[str, Any]:
    """Stream ``ds`` through the plan's jitted per-chunk program.

    Returns the materialized terminal columns (name -> Column).  Three-deep
    pipeline: background prefetch threads slice/pad host chunk buffers,
    the dispatch thread round-robins ``device_put`` + async launch across
    the stream devices (one jit specialization per device), and pulls block
    only when a device's in-flight window (TMOG_STREAM_BUFFERS) is full.
    """
    import jax

    C = chunk_rows()
    B = stream_buffers()
    n = len(ds)
    devs = _stream_devices()
    D = len(devs)
    dev_labels = [str(d) if d is not None else "default" for d in devs]
    perdev: Dict[str, Dict[str, float]] = {
        lbl: dict(chunks=0, rows=0, bytes_in=0.0, bytes_out=0.0,
                  upload_s=0.0, pull_wait_s=0.0) for lbl in dev_labels}
    jitted = _program_for(plan)
    cs_before = _cache_size(jitted)
    bytes_in0 = _stream_scope.get("bytes_in")
    bytes_out0 = _stream_scope.get("bytes_out")
    t_wall = time.perf_counter()

    out_vals: Dict[str, np.ndarray] = {}
    out_masks: Dict[str, np.ndarray] = {}
    hand_chunks: Dict[str, List[Tuple[Any, int]]] = \
        {nm: [] for nm in plan.handoff}
    terminals = [e for e in plan.stages if e.terminal]

    # chunk-boundary resume: with TMOG_CHECKPOINT_DIR set, each drained
    # chunk's terminal outputs persist keyed by (plan signature, chunk
    # index, the chunk's own host-arg fingerprints) — a killed transform
    # rerun restores completed chunks and executes only the remainder
    _ck = _ckpt.store()
    plan_sig = None
    if _ck.enabled:
        plan_sig = (C, n, tuple(
            (getattr(e.stage, "uid", "?"),
             getattr(e.stage, "operation_name", "?"),
             e.out_name, e.out_kind, bool(e.terminal))
            for e in plan.stages))
        # multi-host: the host range joins the signature, so a restarted
        # host finds exactly ITS OWN completed chunks and can never restore
        # another host's range (chunk offsets are host-local).  Single-host
        # keys stay byte-identical to the pre-multi-host layout.
        from ..parallel.mesh import host_count, host_index

        H = host_count()
        if H > 1:
            plan_sig = plan_sig + (("host", host_index(), H),)

    def _chunk_key(lo, host_args):
        fps = []
        for k in sorted(host_args):
            v = host_args[k]
            for a in (v if isinstance(v, (list, tuple)) else (v,)):
                fps.append(_ckpt.data_fingerprint(a))
        return _ckpt.content_key("stream_chunk", plan_sig, lo, tuple(fps))

    def _restore(lo, rows, arrays) -> bool:
        need = {f"v_{e.out_name}" for e in terminals} | {
            f"m_{e.out_name}" for e in terminals if e.out_kind == "numeric"}
        if not need.issubset(arrays):
            return False
        for e in terminals:
            hv = arrays[f"v_{e.out_name}"]
            if e.out_kind == "numeric":
                if e.out_name not in out_vals:
                    out_vals[e.out_name] = np.empty(n, hv.dtype)
                    out_masks[e.out_name] = np.empty(n, bool)
                out_masks[e.out_name][lo:lo + rows] = \
                    arrays[f"m_{e.out_name}"][:rows]
            elif e.out_name not in out_vals:
                out_vals[e.out_name] = np.empty((n, hv.shape[1]), np.float32)
            out_vals[e.out_name][lo:lo + rows] = hv[:rows]
        return True

    def drain(item) -> None:
        lo, rows, outs, ck_key, di = item
        label = dev_labels[di]
        t0 = time.perf_counter()
        saved: Dict[str, np.ndarray] = {}
        b_out0 = _stream_scope.get("bytes_out")

        def _pull():
            _inject.maybe_fail("stream.pull", key=lo)
            pulled = 0
            with trace.span("stream.chunk.pull", lo=lo, rows=rows,
                            device=label) as _psp:
                for e in terminals:
                    o = outs[e.out_name]
                    if e.out_kind == "numeric":
                        hv = np.asarray(o[0])
                        hm = np.asarray(o[1])
                        if e.out_name not in out_vals:
                            out_vals[e.out_name] = np.empty(n, hv.dtype)
                            out_masks[e.out_name] = np.empty(n, bool)
                        out_vals[e.out_name][lo:lo + rows] = hv[:rows]
                        out_masks[e.out_name][lo:lo + rows] = hm[:rows]
                        pulled += rows * (hv.itemsize + hm.itemsize)
                        _stream_scope.inc("bytes_out", float(
                            rows * (hv.itemsize + hm.itemsize)))
                        if ck_key is not None:
                            saved[f"v_{e.out_name}"] = hv[:rows]
                            saved[f"m_{e.out_name}"] = hm[:rows]
                    else:
                        hv = np.asarray(o)
                        if e.out_name not in out_vals:
                            out_vals[e.out_name] = np.empty((n, hv.shape[1]),
                                                            np.float32)
                        out_vals[e.out_name][lo:lo + rows] = hv[:rows]
                        pulled += rows * hv.shape[1] * 4
                        _stream_scope.inc("bytes_out",
                                          float(rows * hv.shape[1] * 4))
                        if ck_key is not None:
                            saved[f"v_{e.out_name}"] = hv[:rows]
                _psp.set(bytes=int(pulled))

        _retry.with_retry("stream.pull", _pull)
        if ck_key is not None:
            _ck.save("stream_chunk", ck_key, saved, meta={"lo": lo,
                                                          "rows": rows})
        dt = time.perf_counter() - t0
        _stream_scope.inc("pull_wait_s", dt)
        pd = perdev[label]
        pd["pull_wait_s"] += dt
        pd["bytes_out"] += float(_stream_scope.get("bytes_out") - b_out0)

    inflight: deque = deque()
    counts = [0] * D
    n_chunks = 0
    restored = 0
    dispatched = 0
    chunk_los = list(range(0, n, C))

    # ---- host-prep prefetch pool -------------------------------------------
    # Chunk slicing/padding used to run inline on the dispatch thread, which
    # serialized the whole pipeline (the overlap_efficiency=0.002 bug: the
    # "async" upload of chunk k+1 could not start until its host prep
    # finished, which could not start until chunk k's pull returned).  Prep
    # now runs in background threads feeding a bounded queue; chunks may
    # arrive out of order (row slices are disjoint, so assembly is
    # order-free), and with one worker the prep order is unchanged.
    task_q: "queue.Queue" = queue.Queue()
    for lo in chunk_los:
        task_q.put(lo)
    out_q: "queue.Queue" = queue.Queue(maxsize=max(2, B * D))
    stop_evt = threading.Event()

    def _prep_one(lo: int):
        hi = min(lo + C, n)
        t0 = time.perf_counter()
        with trace.span("stream.chunk.prep", lo=lo, rows=hi - lo):
            host_args, nbytes = _host_chunk_args(plan, ds, lo, hi, C)
        return lo, hi, host_args, nbytes, time.perf_counter() - t0

    def _prefetch_worker() -> None:
        while not stop_evt.is_set():
            try:
                lo = task_q.get_nowait()
            except queue.Empty:
                return
            try:
                item = ("ok",) + _prep_one(lo)
            except BaseException as e:  # noqa: BLE001 — re-raised on dispatch
                item = ("err", e)
            while not stop_evt.is_set():
                try:
                    out_q.put(item, timeout=0.05)
                    break
                except queue.Full:
                    continue
            if item[0] == "err":
                return

    workers = [threading.Thread(target=_prefetch_worker, daemon=True,
                                name=f"tmog-stream-prep-{i}")
               for i in range(min(prefetch_workers(D), len(chunk_los)))]

    def _next_prepped():
        """The next prepped chunk; the dispatch thread's stall time here is
        the overlap metric's numerator (prep_blocked_s)."""
        if not workers:  # TMOG_STREAM_PREFETCH=0: inline, fully blocking
            item = ("ok",) + _prep_one(task_q.get_nowait())
            _stream_scope.inc("prep_s", item[5])
            _stream_scope.inc("prep_blocked_s", item[5])
            return item[1:]
        t0 = time.perf_counter()
        item = out_q.get()
        _stream_scope.inc("prep_blocked_s", time.perf_counter() - t0)
        if item[0] == "err":
            raise item[1]
        _stream_scope.inc("prep_s", item[5])
        return item[1:]

    try:
        with trace.span("stream.execute", rows=n, chunk_rows=C, window=B,
                        shards=D):
            for w in workers:
                w.start()
            for _ in range(len(chunk_los)):
                lo, hi, host_args, nbytes, _pw = _next_prepped()
                rows = hi - lo
                ck_key = None
                if _ck.enabled:
                    ck_key = _chunk_key(lo, host_args)
                    hit = _ck.load("stream_chunk", ck_key)
                    if hit is not None and _restore(lo, rows, hit[0]):
                        _stream_scope.inc("checkpoint_skips")
                        restored += 1
                        continue
                # data-plane hardening: poison injection, then the
                # TMOG_QUARANTINE row scan.  Both are zero-work when chaos
                # is off and the policy is unset — the chunk buffers are
                # untouched, keeping the legacy path bit-identical.
                if _inject.active():
                    _apply_stream_poison(plan, host_args, lo, rows)
                pol = _quar.policy()
                if pol:
                    _quarantine_chunk(plan, host_args, lo, rows, pol)
                di = dispatched % D
                dev = devs[di]
                label = dev_labels[di]
                t0 = time.perf_counter()
                with trace.span("stream.chunk.upload", lo=lo, rows=rows,
                                device=label) as _usp:
                    _usp.set(bytes=int(nbytes))

                    def _go(dev=dev, host_args=host_args, lo=lo):
                        _inject.maybe_fail("stream.upload", key=lo)
                        # committed transfer: jit specializes per device, so
                        # the D-device stream compiles once per chip
                        dev_args = (jax.device_put(host_args, dev)
                                    if dev is not None
                                    else jax.device_put(host_args))
                        with warnings.catch_warnings():
                            # XLA can't reuse every donated buffer (e.g. bool
                            # masks with no same-shape output); that's
                            # expected, not actionable
                            warnings.filterwarnings(
                                "ignore",
                                message="Some donated buffers were not usable")
                            # async dispatch; donates the uploads
                            return jitted(dev_args)

                    outs = _retry.with_retry("stream.upload", _go)
                dt = time.perf_counter() - t0
                _stream_scope.inc("upload_s", dt)
                _stream_scope.inc("bytes_in", nbytes)
                _stream_scope.inc("pad_rows", C - rows)
                pd = perdev[label]
                pd["chunks"] += 1
                pd["rows"] += rows
                pd["bytes_in"] += float(nbytes)
                pd["upload_s"] += dt
                n_chunks += 1
                dispatched += 1
                for nm in plan.handoff:
                    hand_chunks[nm].append((lo, outs[nm], rows))
                inflight.append((lo, rows, outs, ck_key, di))
                counts[di] += 1
                while counts[di] > B:
                    it = inflight.popleft()
                    counts[it[4]] -= 1
                    drain(it)
            while inflight:
                it = inflight.popleft()
                counts[it[4]] -= 1
                drain(it)
    finally:
        stop_evt.set()
        try:  # unblock any worker parked on a full queue, then reap
            while True:
                out_q.get_nowait()
        except queue.Empty:
            pass
        for w in workers:
            w.join(timeout=5.0)

    cs_after = _cache_size(jitted)
    if cs_before is not None and cs_after is not None:
        _stream_scope.inc("compiles", max(0, cs_after - cs_before))
    _stream_scope.inc("streams")
    _stream_scope.inc("chunks", n_chunks)
    _stream_scope.set("chunk_rows", C)
    _stream_scope.set("buffers", B)
    _stream_scope.set("shards", D)
    bd = dict(_stream_scope.get("by_device") or {})
    for label, v in perdev.items():
        if not v["chunks"]:
            continue
        cur = dict(bd.get(label) or {})
        for k2, val in v.items():
            cur[k2] = cur.get(k2, 0) + val
        bd[label] = cur
    _stream_scope.set("by_device", bd)
    _stream_scope.inc("rows", n)
    _stream_scope.inc("terminals", len(terminals))
    _stream_scope.inc("device_only", len(plan.stages) - len(terminals))
    wall = time.perf_counter() - t_wall
    _stream_scope.inc("wall_s", wall)

    from ..utils import flops

    flops.record_streamed(_stream_scope.get("bytes_in") - bytes_in0,
                          _stream_scope.get("bytes_out") - bytes_out0,
                          n_chunks)

    new_cols: Dict[str, Any] = {}
    for e in terminals:
        if e.out_kind == "numeric":
            new_cols[e.out_name] = NumericColumn(
                e.ftype, out_vals[e.out_name], out_masks[e.out_name])
        else:
            new_cols[e.out_name] = VectorColumn(
                T.OPVector, out_vals[e.out_name], e.metadata)
    for nm, chunks in hand_chunks.items():
        if restored and chunks and nm in new_cols:
            # resumed run: restored chunks never reached the device, so the
            # chunk list is incomplete — the selector falls back to its own
            # upload instead of a torn handoff
            obs_registry.record_fallback("stream", "handoff_skipped_resume",
                                         name=nm, restored=restored)
        elif chunks and nm in new_cols:
            # prefetch may dispatch chunks out of row order; the view is a
            # row-ordered concat
            ordered = [(a, r) for _lo, a, r in
                       sorted(chunks, key=lambda c: c[0])]
            _register_view(new_cols[nm].values, ordered, n)
    return new_cols


# ---------------------------------------------------------------------------
# Sharded winner scoring (the modelSelector.transform wall)
# ---------------------------------------------------------------------------
#: jitted predict programs keyed by head-stage identity; values pin the stage
#: so the id() key can't be recycled (the _PROGRAMS idiom)
_HEAD_JITS: "OrderedDict[int, Tuple[Any, Any]]" = OrderedDict()
_HEAD_JITS_MAX = 16
_HEAD_LOCK = threading.Lock()


def _head_jit(t):
    """One jitted ``X -> (pred, raw|None, prob|None)`` program per head
    stage, via the same ``predict_program`` duck type the serving-side
    ``serve/aot.BucketScorer._head_call`` AOT-compiles per replica.  jit
    specializes per committed device, so the round-robin score pass below
    compiles once per chip.  Raises NotImplementedError for heads without a
    pure-JAX program (the tree families)."""
    import jax

    key = id(t)
    with _HEAD_LOCK:
        hit = _HEAD_JITS.get(key)
        if hit is not None:
            _HEAD_JITS.move_to_end(key)
            return hit[0]
    from ..serve.aot import head_program

    program = head_program(t)
    if program is None:
        raise NotImplementedError("head has no predict_program")
    built = (jax.jit(program), t)
    with _HEAD_LOCK:
        hit = _HEAD_JITS.setdefault(key, built)
        while len(_HEAD_JITS) > _HEAD_JITS_MAX:
            _HEAD_JITS.popitem(last=False)
    return hit[0]


def score_head_sharded(t, ds: Dataset, devs: Optional[list] = None):
    """Chunked multi-device score pass for a prediction-head stage.

    The winner model (``modelSelector.transform``) has no ``jax_transform``,
    so on the legacy path it scores the full feature matrix in one
    single-chip pass.  When the stream is sharded this routes heads exposing
    the pure-JAX ``predict_program`` contract through round-robin chunks
    across the stream devices — the same per-device in-flight window as the
    transform stream.  Returns the assembled PredictionColumn, or None when
    it can't apply (not a head, no program, single device, any failure) —
    always a recorded fallback for real heads, never an error."""
    import jax

    from ..columns import PredictionColumn

    cls = getattr(t, "predictor_class", None)
    if cls is None or getattr(t, "n_outputs", 0) != 1:
        return None
    vec = ds.columns.get(t.inputs[-1].name)
    if not isinstance(vec, VectorColumn):
        return None
    if devs is None:
        devs = _stream_devices()
    D = len(devs)
    n = len(ds)
    if D <= 1 or n == 0:
        return None
    try:
        jitted = _head_jit(t)
    except NotImplementedError:
        record_fallback("score_head_no_program", stage=type(t).__name__,
                        head=cls.__name__)
        return None
    except Exception as e:  # noqa: BLE001 — scoring must not break
        record_fallback("score_head_failed", stage=type(t).__name__,
                        error=str(e))
        return None
    C = chunk_rows()
    B = stream_buffers()
    try:
        pred: Optional[np.ndarray] = None
        raw: Optional[np.ndarray] = None
        prob: Optional[np.ndarray] = None

        def assemble(item) -> None:
            nonlocal pred, raw, prob
            lo, rows, outs = item
            p, r, q = outs
            hp = np.asarray(p)
            if pred is None:
                pred = np.empty(n, np.float64)
            pred[lo:lo + rows] = hp[:rows]
            if r is not None:
                hr = np.asarray(r)
                if raw is None:
                    raw = np.empty((n,) + hr.shape[1:], np.float64)
                raw[lo:lo + rows] = hr[:rows]
            if q is not None:
                hq = np.asarray(q)
                if prob is None:
                    prob = np.empty((n,) + hq.shape[1:], np.float64)
                prob[lo:lo + rows] = hq[:rows]

        inflight: deque = deque()
        n_chunks = 0
        with trace.span("stream.score", rows=n, chunk_rows=C, shards=D,
                        head=cls.__name__):
            for k, lo in enumerate(range(0, n, C)):
                hi = min(lo + C, n)
                rows = hi - lo
                chunk = _pad0(np.ascontiguousarray(
                    vec.values[lo:hi], np.float32), C - rows)
                dev = devs[k % D]
                label = str(dev) if dev is not None else "default"
                with trace.span("stream.score.chunk", lo=lo, rows=rows,
                                device=label):
                    xa = (jax.device_put(chunk, dev) if dev is not None
                          else jax.device_put(chunk))
                    outs = jitted(xa)  # async dispatch
                inflight.append((lo, rows, outs))
                n_chunks += 1
                while len(inflight) > B * D:
                    assemble(inflight.popleft())
            while inflight:
                assemble(inflight.popleft())
        col = PredictionColumn(T.Prediction, pred, raw, prob)
        summary = getattr(t, "summary", None)
        if summary is not None:  # the SelectedModel metadata contract
            col.metadata = {"model_selector_summary": summary.to_json()}
        _stream_scope.inc("score_stages")
        _stream_scope.inc("score_chunks", n_chunks)
        return col
    except Exception as e:  # noqa: BLE001 — fall back to transform_dataset
        record_fallback("score_head_failed", stage=type(t).__name__,
                        error=str(e))
        return None


def maybe_score_sharded(t, ds: Dataset):
    """Route one unfusable stage through the sharded score pass when a data
    mesh is active; None (with the reason recorded for real heads) keeps the
    caller's generic ``transform_dataset`` path."""
    if not enabled():
        return None
    devs = _stream_devices()
    if len(devs) <= 1:
        return None
    return score_head_sharded(t, ds, devs=devs)


class _StreamLabel:
    """Listener label for one streamed multi-layer launch."""

    def __init__(self, plan: StreamPlan):
        names = [getattr(e.stage, "operation_name", "?") for e in plan.stages]
        self.operation_name = "streamed[" + "+".join(names) + "]"
        self.uid = "streamed:" + ",".join(
            getattr(e.stage, "uid", "?") for e in plan.stages)


def apply_streamed(ds: Dataset, layers: Sequence[Sequence[Any]],
                   live: Optional[Set[str]] = None,
                   handoff: Optional[Set[str]] = None) -> Optional[Dataset]:
    """Apply a run of transformer layers via the streaming executor.

    Returns the transformed Dataset, or None when streaming does not apply
    (disabled, empty data, or fewer than two fusable stages) — callers fall
    back to the per-layer paths.  Unfused stages run host-side AFTER the
    stream in their original layer order (their stream-produced inputs are
    materialized terminals by construction).
    """
    if not enabled():
        return None
    n = len(ds)
    if n == 0:
        return None
    plan = build_plan(ds, layers, live=live, handoff=handoff)
    if plan is None:
        record_fallback("too_few_fusable_stages",
                        layers=len(layers),
                        stages=sum(len(l) for l in layers))
        return None
    from . import dag as dag_util

    _stream_scope.inc("stages_fused", plan.n_stream)
    _stream_scope.inc("stages_host", sum(len(l) for l in plan.host_layers))
    _stream_scope.inc("layers", len(layers))
    with dag_util._maybe_time(_StreamLabel(plan), "transform", n):
        new_cols = execute(plan, ds)
    ds = ds.with_columns(new_cols)
    devs = _stream_devices()
    for layer in plan.host_layers:
        if not layer:
            continue
        new: Dict[str, Any] = {}
        for t in layer:
            out_feats = t.get_outputs()
            with dag_util._maybe_time(t, "transform", n):
                # sharded winner scoring: prediction heads ride the same
                # device round-robin as the transform chunks instead of a
                # single-chip full-width pass
                col = (score_head_sharded(t, ds, devs=devs)
                       if len(devs) > 1 else None)
                if col is None:
                    col = t.transform_dataset(ds)
            if t.n_outputs == 1:
                new[out_feats[0].name] = col
            else:
                for f, c in zip(out_feats, col):
                    new[f.name] = c
        ds = ds.with_columns(new)
    return ds
