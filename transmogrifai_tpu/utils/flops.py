"""FLOPs accounting via XLA ``cost_analysis`` — the MFU instrumentation.

The judging criterion for single-chip performance is MFU (model FLOPs
utilization), so the bench needs a defensible FLOPs count for the sweep it
times.  Rather than hand-derived formulas for every kernel (fragile for the
histogram trees, whose work is scatter/cumsum-heavy), each hot jitted kernel
call-site calls :func:`record`, which AOT-lowers the SAME jitted callable at
the call's exact arguments and reads the compiled executable's
``cost_analysis()['flops']`` — XLA's own static count of the optimized HLO.

Zero overhead unless enabled (the bench enables it); each (kernel, shape
signature) is lowered once and cached, so steady-state calls add a dict
lookup.  Numbers are per-call costs summed over calls — i.e. total optimized
FLOPs dispatched to the device, the honest numerator for

    MFU = flops_total / wall_clock / peak_flops.

Caveat (stated where the bench reports it): XLA counts every op's arithmetic
— including the VPU-bound scatter/cumsum work of tree histogram building —
so tree-sweep "MFU" is utilization of peak *arithmetic* throughput, not an
MXU duty cycle.  The linear-model sweeps are matmul-dominated and their MFU
reads conventionally.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax

from ..obs import registry as obs_registry

_enabled: bool = bool(int(os.environ.get("TMOG_COUNT_FLOPS", "0") or 0))
_totals: Dict[str, float] = {"flops": 0.0, "bytes_accessed": 0.0, "calls": 0.0}
_by_fn: Dict[str, Dict[str, Any]] = {}
_by_device: Dict[str, Dict[str, Any]] = {}
#: per-axis collective traffic: axis -> {"count", "bytes", "<kind>_count"}
_collectives: Dict[str, Dict[str, float]] = {}
#: histogram-subtraction savings: sibling histograms derived as parent - child
#: rather than rebuilt.  XLA's cost_analysis already counts only the work the
#: optimized HLO actually does, so the main ``flops`` total needs no
#: adjustment — this bucket records the AVOIDED build FLOPs separately
#: (trace-time estimates: loop bodies counted once, like the collectives).
_hist_subtracted: Dict[str, float] = {"levels": 0.0, "flops_avoided": 0.0}
#: GBT boosting-chain telemetry from the trees kernels' trace events: how
#: many sequential scan launches carried a boosting chain and the longest
#: chain (scan steps) any of them dispatched — the critical-path number the
#: round-collapse attacks
_gbt_chain: Dict[str, float] = {"chains": 0.0, "steps_max": 0.0}
#: streamed transform-pipeline traffic (workflow/stream.py): bytes pushed
#: through device_put per chunk and pulled back for terminal columns, plus
#: the chunk/launch counts — the "intermediates never leave the device"
#: claim made auditable next to the FLOPs totals
_streamed: Dict[str, float] = {"bytes_in": 0.0, "bytes_out": 0.0,
                               "chunks": 0.0, "streams": 0.0}
_cost_cache: Dict[Tuple, Optional[Dict[str, float]]] = {}


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    _totals.update(flops=0.0, bytes_accessed=0.0, calls=0.0)
    _by_fn.clear()
    _by_device.clear()
    _collectives.clear()
    _hist_subtracted.update(levels=0.0, flops_avoided=0.0)
    _gbt_chain.update(chains=0.0, steps_max=0.0)
    _streamed.update(bytes_in=0.0, bytes_out=0.0, chunks=0.0, streams=0.0)


def totals() -> Dict[str, Any]:
    """{"flops", "bytes_accessed", "calls", "by_fn": {...}, "by_device": {...}}

    Each ``by_fn`` entry carries ``flops``, ``bytes`` (XLA "bytes accessed"
    — the roofline ledger's memory-traffic mirror of the FLOPs bucket),
    ``calls``, and a ``by_shape`` sub-dict mapping a compact shape signature
    -> {"flops", "bytes", "calls"}, so a kernel recorded once per shard/per
    chunk under DIFFERENT shapes (the partitioned sweep does exactly this)
    stays auditable: sum of by_shape calls == entry calls.
    ``by_device`` splits the same totals by the device label the caller
    attributed the launch to (multi-chip runs; empty on unattributed runs);
    a device that ran collective-bearing programs additionally carries a
    ``collectives`` sub-dict.  Top-level ``collectives`` maps mesh axis ->
    {"count", "bytes", "psum_count", "all_gather_count"} — the row-sharded
    sweep's communication claim, auditable per axis (bytes are trace-time
    payload sizes: loop bodies counted once, vmap batch factors excluded).
    """
    out: Dict[str, Any] = dict(_totals)
    out["by_fn"] = {
        k: {"flops": v["flops"], "bytes": v.get("bytes", 0.0),
            "calls": v["calls"],
            "by_shape": {s: dict(c) for s, c in v["by_shape"].items()}}
        for k, v in _by_fn.items()}
    out["by_device"] = {
        k: {kk: (dict(vv) if isinstance(vv, dict) else vv)
            for kk, vv in v.items()}
        for k, v in _by_device.items()}
    out["collectives"] = {k: dict(v) for k, v in _collectives.items()}
    out["hist_subtracted"] = dict(_hist_subtracted)
    out["gbt_chain"] = dict(_gbt_chain)
    out["streamed"] = dict(_streamed)
    return out


#: obs.snapshot()["flops"] is this module's totals() — the registry never
#: duplicates the buckets, it reads them through the provider
obs_registry.register_provider("flops", totals)


def record_streamed(bytes_in: float, bytes_out: float, chunks: int) -> None:
    """Accumulate ONE streamed transform run's transfer traffic
    (workflow/stream.execute calls this with the run's deltas).  No-op
    unless enabled, like every other bucket here."""
    if not _enabled:
        return
    _streamed["bytes_in"] += float(bytes_in)
    _streamed["bytes_out"] += float(bytes_out)
    _streamed["chunks"] += float(chunks)
    _streamed["streams"] += 1.0


def streamed_totals() -> Dict[str, float]:
    """{"bytes_in", "bytes_out", "chunks", "streams"}: streamed transform
    transfer traffic (same shape as totals()["streamed"])."""
    return dict(_streamed)


def record_collectives(colls, device=None) -> None:
    """Accumulate ONE launch's worth of traced mesh collectives.

    ``colls`` is the (kind, axis, bytes) list captured by
    ``parallel.mesh.trace_collectives`` around the program's lowering; the
    launcher replays it here per call so per-axis counts and bytes scale
    with launches just like FLOPs do.  No-op unless enabled."""
    if not _enabled or not colls:
        return
    for kind, axis, nbytes in colls:
        if kind == "hist_subtracted":
            # not traffic: a trees-kernel trace event carrying the avoided
            # histogram-build FLOPs of one subtracted level (see
            # parallel.mesh.record_trace_event)
            _hist_subtracted["levels"] += 1
            _hist_subtracted["flops_avoided"] += nbytes
            continue
        if kind == "gbt_chain":
            # not traffic either: a trees-kernel trace event carrying the
            # boosting scan length (post round-collapse) of one launch
            _gbt_chain["chains"] += 1
            _gbt_chain["steps_max"] = max(_gbt_chain["steps_max"],
                                          float(nbytes))
            continue
        agg = _collectives.setdefault(
            axis, {"count": 0.0, "bytes": 0.0})
        agg["count"] += 1
        agg["bytes"] += nbytes
        agg[f"{kind}_count"] = agg.get(f"{kind}_count", 0.0) + 1
        if device is not None:
            dv = _by_device.setdefault(str(device),
                                       {"flops": 0.0, "bytes": 0.0,
                                        "calls": 0.0})
            dcoll = dv.setdefault("collectives", {})
            dax = dcoll.setdefault(axis, {"count": 0.0, "bytes": 0.0})
            dax["count"] += 1
            dax["bytes"] += nbytes


def collective_totals() -> Dict[str, Dict[str, float]]:
    """Per-axis collective traffic (same shape as totals()["collectives"])."""
    return {k: dict(v) for k, v in _collectives.items()}


def hist_subtracted_totals() -> Dict[str, float]:
    """{"levels", "flops_avoided"}: histogram builds saved by subtraction."""
    return dict(_hist_subtracted)


def _signature(args, kwargs) -> Tuple:
    leaves, treedef = jax.tree.flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sig.append(("a", tuple(shape), str(getattr(leaf, "dtype", "?"))))
        else:
            sig.append(("s", repr(leaf)))
    return (str(treedef), tuple(sig))


def _shape_key(args, kwargs) -> str:
    """Compact human-auditable shape signature, e.g. "(240,20)|(240,)|s3"."""
    leaves, _ = jax.tree.flatten((args, kwargs))
    parts = []
    n_static = 0
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            parts.append("(" + ",".join(str(s) for s in shape) + ")")
        else:
            n_static += 1
    if n_static:
        parts.append(f"s{n_static}")
    return "|".join(parts)


def _accumulate(name: str, cost: Dict[str, float], shape_key: str,
                device: Optional[str]) -> None:
    _totals["flops"] += cost["flops"]
    _totals["bytes_accessed"] += cost["bytes_accessed"]
    _totals["calls"] += 1
    agg = _by_fn.setdefault(name, {"flops": 0.0, "bytes": 0.0, "calls": 0.0,
                                   "by_shape": {}})
    agg["flops"] += cost["flops"]
    agg["bytes"] = agg.get("bytes", 0.0) + cost["bytes_accessed"]
    agg["calls"] += 1
    sh = agg["by_shape"].setdefault(shape_key,
                                    {"flops": 0.0, "bytes": 0.0, "calls": 0.0})
    sh["flops"] += cost["flops"]
    sh["bytes"] = sh.get("bytes", 0.0) + cost["bytes_accessed"]
    sh["calls"] += 1
    if device is not None:
        dv = _by_device.setdefault(str(device),
                                   {"flops": 0.0, "bytes": 0.0, "calls": 0.0})
        dv["flops"] += cost["flops"]
        dv["bytes"] = dv.get("bytes", 0.0) + cost["bytes_accessed"]
        dv["calls"] += 1


def bytes_by_kernel() -> Dict[str, float]:
    """kernel name -> accumulated XLA "bytes accessed" — the per-program
    memory-traffic mirror of the per-fn FLOPs bucket (the roofline ledger's
    bytes source)."""
    return {k: float(v.get("bytes", 0.0)) for k, v in _by_fn.items()}


def bytes_by_device() -> Dict[str, float]:
    """device label -> accumulated XLA "bytes accessed" (mirror of the
    per-device FLOPs bucket)."""
    return {k: float(v.get("bytes", 0.0)) for k, v in _by_device.items()}


def _cost(fn, args, kwargs) -> Optional[Dict[str, Any]]:
    try:
        # lower inside the mesh trace collector so kernel trace events
        # (hist_subtracted savings, collectives traced outside a launcher
        # that captures them itself) ride along with the cached cost and
        # are replayed per recorded call
        from ..parallel.mesh import trace_collectives

        with trace_collectives() as colls:
            lowered = fn.lower(*args, **kwargs)
        compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                "events": tuple(c for c in colls
                                if c[0] in ("hist_subtracted", "gbt_chain"))}
    except Exception:
        return None


def cost_of(fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """One-off XLA cost of jitted ``fn`` at these args, WITHOUT accumulating
    into the running totals (bench uses this for per-family attribution)."""
    return _cost(fn, args, kwargs)


def wrap(name: str, jitted):
    """Wrap a jitted kernel so every call records its XLA cost when
    accounting is enabled.  Applied once at module bottom in ops/ — call
    sites stay untouched and always-on overhead is one ``if`` per call."""
    import functools

    @functools.wraps(jitted)
    def wrapper(*args, **kwargs):
        out = jitted(*args, **kwargs)
        if _enabled:
            record(name, jitted, *args, **kwargs)
        return out

    wrapper.__wrapped_jit__ = jitted
    return wrapper


def record(name: str, fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """Accumulate the XLA-optimized cost of ONE call of jitted ``fn`` at
    these arguments.  No-op unless enabled; per-(fn, shapes) cost is cached.
    ``fn`` must be the jit-wrapped callable itself (has ``.lower``).
    Returns the per-call cost dict ({"flops", "bytes_accessed", ...}; treat
    as read-only — it is the cache entry) so launch sites can feed the
    roofline ledger, or None when disabled/unavailable."""
    if not _enabled:
        return None
    key = (name, _signature(args, kwargs))
    if key not in _cost_cache:
        _cost_cache[key] = _cost(fn, args, kwargs)
    cost = _cost_cache[key]
    if cost is None:
        return None
    _accumulate(name, cost, _shape_key(args, kwargs), None)
    record_collectives(cost.get("events", ()))
    return cost


def record_device(name: str, device, fn, *args, **kwargs
                  ) -> Optional[Dict[str, Any]]:
    """:func:`record`, attributing the call to ``device`` in ``by_device``."""
    if not _enabled:
        return None
    key = (name, _signature(args, kwargs))
    if key not in _cost_cache:
        _cost_cache[key] = _cost(fn, args, kwargs)
    cost = _cost_cache[key]
    if cost is None:
        return None
    _accumulate(name, cost, _shape_key(args, kwargs), str(device))
    record_collectives(cost.get("events", ()), device)
    return cost


def record_compiled(name: str, compiled, args: Tuple, device=None
                    ) -> Optional[Dict[str, float]]:
    """Accumulate ONE call of an already-AOT-compiled executable.

    The multi-chip sweep compiles its per-shard programs itself (concurrent
    AOT, ops/sweep.py) — re-lowering them here just to read a cost would
    double every shard's compile, so this variant reads ``cost_analysis()``
    straight off the executable.  ``args`` are the call's dynamic arguments
    (shape-signature bookkeeping only).  Returns the per-call cost dict, or
    None when disabled/unavailable.
    """
    if not _enabled:
        return None
    try:
        ca = compiled.cost_analysis() or {}
        cost = {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    except Exception:
        return None
    _accumulate(name, cost, _shape_key(args, {}),
                None if device is None else str(device))
    return cost
