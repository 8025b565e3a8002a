"""Timeline reconstruction + bubble attribution over the span tracer.

The sweep is latency-bound, not compute-bound (MFU ~1.1% at 215.9 models/s,
BENCH_r05) — this module turns the raw span events :mod:`obs.trace` already
records into an *answer* to "where does the wall go?".  It rebuilds one
execution lane per thread (the per-shard sweep pool threads, the stream
executor, the serve dispatcher), classifies every covered microsecond into a
named bubble bucket, and charges the uncovered remainder to ``idle`` — so
each lane's buckets sum to the analysis window's wall EXACTLY, and the
aggregate (the per-lane mean) inherits that invariant.  No more guessing
which perf lever to pull first.

Buckets (:data:`BUCKETS`):

- ``host_prep``    — host-blocked preparation: array staging/device upload
  (``sweep.upload``, ``stream.chunk.upload``, ``devcache.put``), host row
  gathers (``selector.gather``, ``selector.rows``), checkpoint writes.
- ``compile``      — XLA lowering/compilation (``sweep.compile``,
  ``serve.rebuild``).
- ``dispatch``     — launch serialization: async-dispatch enqueue
  (``sweep.dispatch``) and serve queue wait (the slice of ``serve.request``
  not covered by its inner ``serve.batch``).
- ``collective``   — cross-device collective wait (``mesh.*`` spans; XLA
  hides in-program collectives, so this is only populated when an explicit
  host-visible collective span exists).
- ``gather``       — device-execution + host-pull wait: the blocking
  ``np.asarray`` that drains a shard (``sweep.gather``,
  ``stream.chunk.pull``).  On async backends device compute hides here —
  the host's view of "waiting for the accelerator".
- ``compute``      — instrumented host/device work not better classified
  (``serve.batch``, ``profile.case``, unknown span names).
- ``idle``         — the window's uncovered remainder: uninstrumented host
  glue and true idleness.  Structural wrapper spans (``sweep.launch``,
  ``sweep.shard``, ``stream.execute``, the profiling windows) never absorb
  time themselves; only their classified children do.

Overlapping spans on one lane resolve innermost-wins (the latest-started
active span owns the instant), matching Chrome-trace nesting semantics.

:func:`bubble_report` is wired into ``tools/profile_sweep.py``, ``bench.py``
and the JSONL run records; ``python -m transmogrifai_tpu.obs.timeline
trace.json`` reports over an exported Chrome trace (e.g. the tier-1 CI
artifact).
"""
from __future__ import annotations

import bisect
import heapq
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

__all__ = ["BUCKETS", "classify", "bubble_report", "critical_path",
           "format_report", "SCHEMA", "SCHEMA_VERSION"]

SCHEMA = "tmog.bubble_report"
SCHEMA_VERSION = 1

#: every bucket a report carries, in display order; per lane they sum to the
#: window wall (``idle`` is defined as the remainder).
BUCKETS = ("host_prep", "compile", "dispatch", "collective", "gather",
           "compute", "idle")

#: span name -> bucket.  Unknown names default to ``compute`` (they are
#: instrumented work); structural wrappers classify to None (excluded).
_EXACT = {
    "sweep.upload": "host_prep",
    "sweep.checkpoint": "host_prep",
    "stream.chunk.upload": "host_prep",
    "selector.split": "host_prep",
    "selector.prepare": "host_prep",
    "selector.gather": "host_prep",
    "selector.rows": "host_prep",
    "sweep.plan": "host_prep",
    "devcache.upload": "host_prep",
    "devcache.put": "host_prep",
    "sweep.compile": "compile",
    "serve.rebuild": "compile",
    "sweep.dispatch": "dispatch",
    "serve.request": "dispatch",  # queue wait; inner serve.batch wins overlap
    "sweep.gather": "gather",
    "stream.chunk.pull": "gather",
    "serve.batch": "compute",
    "serve.probe": "compute",
    "profile.case": "compute",
}

#: pure wrappers: they delimit, their children attribute.  Their own
#: uncovered interior is exactly the "uninstrumented glue" idle measures.
_STRUCTURAL = frozenset({
    "sweep.launch", "sweep.shard", "stream.execute",
    "selector.fit", "selector.validate", "stage.fit", "stage.transform",
    "profile.window", "bench.window",
})


def classify(name: str) -> Optional[str]:
    """Bucket for a span name; None for structural wrappers."""
    if name in _STRUCTURAL:
        return None
    b = _EXACT.get(name)
    if b is not None:
        return b
    if name.startswith("mesh.") or name.endswith(".collective"):
        return "collective"
    return "compute"


# ---------------------------------------------------------------------------
# event plumbing
# ---------------------------------------------------------------------------
def _complete_events(events: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if isinstance(ts, (int, float)) and isinstance(dur, (int, float)) \
                and dur >= 0:
            out.append(e)
    return out


def _resolve_window(evs: List[Dict[str, Any]],
                    window: Union[None, str, Tuple[float, float]],
                    ) -> Tuple[float, float, str]:
    """(t0_us, t1_us, label).  ``window`` names a span (last occurrence
    wins), gives explicit (t0_us, t1_us), or None = the events' hull."""
    if isinstance(window, (tuple, list)) and len(window) == 2:
        return float(window[0]), float(window[1]), "explicit"
    if isinstance(window, str):
        for e in reversed(evs):
            if e["name"] == window:
                return float(e["ts"]), float(e["ts"] + e["dur"]), window
        raise ValueError(f"no span named {window!r} in the trace buffer")
    t0 = min(e["ts"] for e in evs)
    t1 = max(e["ts"] + e["dur"] for e in evs)
    return float(t0), float(t1), "all-events"


#: a classified span clipped to the window: (start_us, end_us, bucket, name,
#: lane label)
_Clipped = Tuple[float, float, str, str, str]


def _lanes(evs: List[Dict[str, Any]], t0: float, t1: float,
           ) -> Dict[str, List[_Clipped]]:
    """Classified spans clipped to [t0, t1], grouped per (pid, tid) lane.
    Lanes whose only spans are structural are dropped (e.g. the main thread
    blocked on the shard pool — its wait is the workers' story)."""
    lanes: Dict[Tuple, Dict[str, Any]] = {}
    for e in evs:
        key = (e.get("pid"), e.get("tid"))
        ln = lanes.setdefault(key, {"spans": [], "device": ""})
        args = e.get("args") or {}
        dev = args.get("device") or args.get("column") or args.get("devices")
        if dev is not None and not ln["device"]:
            ln["device"] = str(dev)
        bucket = classify(e["name"])
        if bucket is None:
            continue
        s = max(float(e["ts"]), t0)
        en = min(float(e["ts"] + e["dur"]), t1)
        if en <= s:
            continue
        ln["spans"].append((s, en, bucket, e["name"]))
    out: Dict[str, List[_Clipped]] = {}
    for i, (key, ln) in enumerate(sorted(lanes.items(),
                                         key=lambda kv: str(kv[0]))):
        if not ln["spans"]:
            continue
        label = f"lane{i}" + (f":{ln['device']}" if ln["device"] else "")
        out[label] = [(s, en, b, nm, label) for s, en, b, nm in ln["spans"]]
    return out


def _coverage(spans: Sequence[_Clipped], t0: float, t1: float,
              ) -> Dict[str, float]:
    """Per-bucket covered microseconds in [t0, t1], innermost-wins.

    Boundary sweep with a max-start heap: at each segment the active span
    with the LATEST start owns it (the deepest nesting level under Chrome-
    trace containment; well-defined for partial overlaps too)."""
    cov = {b: 0.0 for b in BUCKETS}
    if not spans:
        cov["idle"] = t1 - t0
        return cov
    ordered = sorted(spans)
    bounds = sorted({p for s in ordered for p in (s[0], s[1])})
    heap: List[Tuple[float, float, str]] = []  # (-start, end, bucket)
    i = 0
    for j in range(len(bounds) - 1):
        a, b = bounds[j], bounds[j + 1]
        while i < len(ordered) and ordered[i][0] <= a:
            heapq.heappush(heap, (-ordered[i][0], ordered[i][1],
                                  ordered[i][2]))
            i += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            cov[heap[0][2]] += b - a
    covered = sum(cov.values())
    cov["idle"] = max(0.0, (t1 - t0) - covered)
    return cov


def critical_path(spans: Sequence[_Clipped], t0: float, t1: float,
                  max_items: int = 32) -> List[Dict[str, Any]]:
    """Backward-chained critical path through [t0, t1] across every lane.

    From the window's end, repeatedly take the span whose END is latest but
    not after the cursor, emit it, and jump the cursor to its start;
    uncovered stretches emit ``(gap)`` entries.  This is the chain of
    last-finishers — shrinking any span on it (or filling any gap) moves the
    measured wall.  Oldest-first; truncated to ``max_items`` with a summary
    tail entry."""
    path: List[Dict[str, Any]] = []
    ordered = sorted(spans, key=lambda s: s[1])
    ends = [s[1] for s in ordered]
    eps = 1e-6
    t = t1
    while t > t0 + eps:
        i = bisect.bisect_right(ends, t + eps) - 1
        if i < 0:  # nothing ends at or before the cursor: leading gap
            path.append({"name": "(gap)", "bucket": "idle", "lane": "",
                         "dur_us": t - t0})
            break
        s = ordered[i]
        if s[1] < t - eps:
            path.append({"name": "(gap)", "bucket": "idle", "lane": "",
                         "dur_us": t - s[1]})
        path.append({"name": s[3], "bucket": s[2], "lane": s[4],
                     "dur_us": s[1] - max(s[0], t0)})
        t = max(s[0], t0)
        if len(path) > 4096:  # degenerate traces must still terminate
            break
    path.reverse()
    for p in path:
        p["dur_s"] = round(p.pop("dur_us") / 1e6, 6)
    if len(path) > max_items:
        tail = path[max_items - 1:]
        path = path[:max_items - 1] + [{
            "name": f"(+{len(tail)} more)", "bucket": "", "lane": "",
            "dur_s": round(sum(p["dur_s"] for p in tail), 6)}]
    return path


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------
def bubble_report(events: Optional[Iterable[Dict[str, Any]]] = None,
                  window: Union[None, str, Tuple[float, float]] = None,
                  wall_s: Optional[float] = None,
                  max_path: int = 32) -> Dict[str, Any]:
    """Per-device timelines -> named bubble buckets + critical path.

    ``events`` defaults to the live trace ring buffer; pass an exported
    trace's ``traceEvents`` to analyze offline.  ``window`` picks the
    analysis interval (span name / explicit (t0_us, t1_us) / whole trace);
    ``wall_s`` optionally supplies an externally measured wall to report the
    window against.  Invariant: each lane's buckets (idle included) sum to
    the window wall, and ``buckets_s`` — the per-lane mean — therefore does
    too (``bucket_sum_s`` vs ``wall_s``).
    """
    if events is None:
        from . import trace as _trace
        events = _trace.events()
    evs = _complete_events(events)
    if not evs:
        raise ValueError("no complete span events to analyze "
                         "(is tracing enabled?)")
    t0, t1, wname = _resolve_window(evs, window)
    wall_us = max(t1 - t0, 1e-9)
    lanes = _lanes(evs, t0, t1)
    lane_out: Dict[str, Dict[str, Any]] = {}
    agg = {b: 0.0 for b in BUCKETS}
    all_spans: List[_Clipped] = []
    for label, spans in lanes.items():
        cov = _coverage(spans, t0, t1)
        all_spans.extend(spans)
        for b in BUCKETS:
            agg[b] += cov[b]
        lane_out[label] = {
            "spans": len(spans),
            "buckets_s": {b: round(cov[b] / 1e6, 6) for b in BUCKETS},
        }
    n_lanes = max(len(lanes), 1)
    buckets_s = {b: round(agg[b] / n_lanes / 1e6, 6) for b in BUCKETS}
    if not lanes:  # window held only structural spans: all idle
        buckets_s["idle"] = round(wall_us / 1e6, 6)
    bucket_sum = sum(buckets_s.values())
    window_wall_s = wall_us / 1e6
    path = critical_path(all_spans, t0, t1, max_items=max_path)
    bubble_s = bucket_sum - buckets_s["compute"] - buckets_s["gather"]
    report: Dict[str, Any] = {
        "schema": SCHEMA,
        "schema_version": SCHEMA_VERSION,
        "window": wname,
        "wall_s": round(window_wall_s, 6),
        "events": len(evs),
        "lanes": lane_out,
        "buckets_s": buckets_s,
        "bucket_sum_s": round(bucket_sum, 6),
        # bubble = wall not spent computing or draining results: prep,
        # dispatch, compile, collectives, idle — the attribution ROADMAP
        # item 1 starts from
        "bubble_fraction": round(max(0.0, bubble_s) / window_wall_s, 4),
        "critical_path": path,
        "critical_path_coverage": round(
            sum(p["dur_s"] for p in path if p["name"] != "(gap)")
            / window_wall_s, 4) if path else 0.0,
    }
    if wall_s is not None:
        report["measured_wall_s"] = round(float(wall_s), 6)
        report["window_vs_measured"] = round(window_wall_s / max(
            float(wall_s), 1e-9), 4)
    return report


def format_report(report: Dict[str, Any], width: int = 46) -> str:
    """Human-readable rendering (profile_sweep/bench print this)."""
    wall = max(report["wall_s"], 1e-9)
    lines = [f"bubble report  window={report['window']} "
             f"wall={report['wall_s']:.4f}s lanes={len(report['lanes'])} "
             f"events={report['events']}"]
    for b in BUCKETS:
        v = report["buckets_s"].get(b, 0.0)
        bar = "#" * int(round(width * v / wall))
        lines.append(f"  {b:10s} {v:10.4f}s {100 * v / wall:5.1f}%  {bar}")
    lines.append(f"  {'sum':10s} {report['bucket_sum_s']:10.4f}s "
                 f"(vs wall {report['wall_s']:.4f}s)  "
                 f"bubble_fraction={report['bubble_fraction']:.3f}")
    cp = report.get("critical_path") or []
    if cp:
        lines.append("  critical path "
                     f"({report['critical_path_coverage'] * 100:.0f}% of wall):")
        for p in cp:
            lines.append(f"    {p['dur_s']:9.4f}s  {p['name']}"
                         + (f" [{p['lane']}]" if p.get("lane") else ""))
    return "\n".join(lines)


def _main(argv: Optional[List[str]] = None) -> int:
    """``python -m transmogrifai_tpu.obs.timeline trace.json [--out r.json]``
    — bubble-report an exported Chrome trace (the CI trace artifact)."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=_main.__doc__)
    ap.add_argument("trace", help="Chrome trace-event JSON (obs.trace export)")
    ap.add_argument("--window", default=None,
                    help="span name to analyze (default: whole trace)")
    ap.add_argument("--out", default="",
                    help="also write the report as JSON here")
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    report = bubble_report(events=events, window=args.window)
    print(format_report(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"bubble report -> {args.out}")
    return 0


if __name__ == "__main__":  # pragma: no cover - thin CLI
    raise SystemExit(_main())
