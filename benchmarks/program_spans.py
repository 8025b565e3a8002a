"""The program's own spans and scopes in the profiler's trace: which span
the host was in while the device sat idle, and which named scope each device
op belongs to.

``transmogrifai_tpu/obs/trace.py`` enters a ``jax.profiler.TraceAnnotation``
for every span, so a traced step's ``.xplane.pb`` holds them on the host
plane, on the device ops' clock, their attributes as event stats; and
``ops/sweep.py`` / ``ops/metrics.py`` wrap each model family and each part of
the metric pass in a ``jax.named_scope``, which the compiler carries into
every op's name path.  ``trace_reduce.read_xplane`` keeps the spans' names
and times alone (no stats) and no name path, so this module reads the same
file again.

On a TPU v5e trace the name path is the ``tf_op`` stat of the op's EVENT
METADATA (``jit(_run_metrics)/metrics.binary/jit(_binary_grid_metrics)/
vmap(vmap(metrics.sort))/gather:``, ``jit(_run_scores)/scores.forest/while/
body/trees.hist/while/body/dot_general:``), not of the event: the event's
name is its HLO line without
``metadata={...}`` and its own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier`` alone.
``jax.profiler.ProfileData`` shows event stats only, so the file is decoded
here from the protobuf wire format (``xplane.proto``: a dozen fields), with
no dependency beyond the standard library.  A ``while`` op carries no
``tf_op``; the ops of its body do.

Two parts, as in ``trace_reduce``.  **Reading** (``read_xplane``) gives plain
intervals: the host spans ``(name, start, end, stats)`` under the program's
roots, and per device the ops ``(scope path, start, end)``.  **Arithmetic**
(``idle_by_span``, ``idle_by_phase``, ``self_seconds``, ``scope_seconds``)
works on those alone and is tested on synthetic intervals of known answer.

A program without the spans (the parent of the PR that added them) reads as
empty: every metric built on this module is then silent, none raises.

Times are seconds on the trace's own clock.
"""
from __future__ import annotations

import json
import os
import re
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce

Interval = Tuple[float, float]
#: (name, start, end, stats)
Span = Tuple[str, float, float, Dict[str, Any]]
#: (scope path, start, end)
Op = Tuple[str, float, float]

HERE = os.path.dirname(os.path.abspath(__file__))
#: where ``run.py`` has the profiler write (still on disk when readers run)
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".bench_trace")

NO_SPAN = "(no span)"

#: the phases of one selector fit that feed the device: it idles until the
#: sweep's first program starts
FEED = ("selector.split", "selector.prepare", "selector.gather",
        "sweep.plan", "sweep.dispatch")
#: the phases after the sweep's programs were dispatched: the pull (which
#: also covers the gaps between the running programs' ops), the winner's
#: refit, its train and holdout evaluation
REFIT_EVAL = ("sweep.gather", "selector.refit", "selector.evaluate")

#: a named scope as ``ops/sweep.py``, ``ops/metrics.py`` and ``ops/trees.py``
#: write them
SCOPE = re.compile(r"\b(?:scores|metrics|trees)\.[a-z_]+")
#: the stat of an op's event metadata that holds its name path
SCOPE_STAT = "tf_op"


# ---------------------------------------------------------------------------
# reading: xplane.proto's wire format
#   XSpace.planes=1
#   XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map)
#   XLine.name=2 .timestamp_ns=3 .events=4
#   XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3 .stats=4
#   XEventMetadata.name=2 .stats=5;  XStatMetadata.name=2
#   XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7
# ---------------------------------------------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        yield key >> 3, v


def _map_entry(buf) -> Tuple[int, Any]:
    key, value = 0, memoryview(b"")
    for num, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _stats(bufs, stat_names: Dict[int, str]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for buf in bufs:
        name, value = "", None
        for num, v in _fields(buf):
            if num == 1:
                name = stat_names.get(v, str(v))
            elif num == 2:
                value = struct.unpack("<d", v)[0]
            elif num in (3, 4):
                value = v - (1 << 64) if num == 4 and v >= 1 << 63 else v
            elif num == 5:
                value = bytes(v).decode("utf-8", "replace")
            elif num == 6:
                value = bytes(v)
            elif num == 7:
                value = stat_names.get(v, str(v))
        out[name] = value
    return out


def _plane(buf) -> Dict[str, Any]:
    """``{"name", "lines": [(name, timestamp_ns, [event buffers])],
    "event_names": {id: name}, "event_stats": {id: [stat buffers]},
    "stat_names": {id: name}}``."""
    out: Dict[str, Any] = {"name": "", "lines": [], "event_names": {},
                           "event_stats": {}, "stat_names": {}}
    for num, v in _fields(buf):
        if num == 2:
            out["name"] = bytes(v).decode()
        elif num == 3:
            name, t0, events = "", 0, []
            for n2, v2 in _fields(v):
                if n2 == 2:
                    name = bytes(v2).decode()
                elif n2 == 3:
                    t0 = v2
                elif n2 == 4:
                    events.append(v2)
            out["lines"].append((name, t0, events))
        elif num in (4, 5):
            key, value = _map_entry(v)
            name, stats = "", []
            for n2, v2 in _fields(value):
                if n2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5 and num == 4:
                    stats.append(v2)
            if num == 4:
                out["event_names"][key], out["event_stats"][key] = name, stats
            else:
                out["stat_names"][key] = name
    return out


def _events(line) -> Any:
    """``(metadata id, start s, end s, [stat buffers])`` of a line's
    events, on the clock ``ProfileData`` reports (``start_ns / 1e9``)."""
    _, t0_ns, events = line
    for buf in events:
        mid = off = dur = 0
        stats = []
        for num, v in _fields(buf):
            if num == 1:
                mid = v
            elif num == 2:
                off = v
            elif num == 3:
                dur = v
            elif num == 4:
                stats.append(v)
        start = (t0_ns * 1000 + off) / 1000.0
        yield mid, start / 1e9, (start + dur / 1000.0) / 1e9, stats


def read_xplane(path: str) -> Dict[str, Any]:
    """``{"spans": [Span], "ops": {device id: [Op]}}``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    spans: List[Span] = []
    ops: Dict[int, List[Op]] = {}
    for num, buf in _fields(space):
        if num != 1:
            continue
        plane = _plane(buf)
        m = trace_reduce.DEVICE_PLANE.match(plane["name"])
        names, stat_names = plane["event_names"], plane["stat_names"]
        if m:
            paths: Dict[int, str] = {}   # one decode per op, not per event
            for line in plane["lines"]:
                if line[0] != trace_reduce.OPS_LINE:
                    continue
                dev = ops.setdefault(int(m.group(1)), [])
                for mid, a, b, _ in _events(line):
                    if mid not in paths:
                        paths[mid] = str(_stats(
                            plane["event_stats"].get(mid, ()),
                            stat_names).get(SCOPE_STAT, ""))
                    dev.append((paths[mid], a, b))
        elif plane["name"].startswith("/host"):
            for line in plane["lines"]:
                spans.extend(
                    (names[mid], a, b, _stats(stats, stat_names))
                    for mid, a, b, stats in _events(line)
                    if names.get(mid, "").startswith(trace_reduce.SPAN_PREFIX))
    return {"spans": spans, "ops": ops}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def _innermost(piece: Interval, spans: Sequence[Span]) -> str:
    """The shortest span that covers the piece."""
    best, best_len = NO_SPAN, float("inf")
    for name, a, b, _ in spans:
        if a <= piece[0] and piece[1] <= b and (b - a) < best_len:
            best, best_len = name, b - a
    return best


def idle_by_span(ops: Sequence[Tuple[Any, float, float]], window: Interval,
                 spans: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds of the window by host span: every gap of the device
    (``trace_reduce.gaps``) is cut at the span edges inside it and each piece
    goes to the innermost span covering it, ``"(no span)"`` otherwise.  The
    values sum to the gaps' total."""
    out: Dict[str, float] = {}
    for lo, hi in trace_reduce.gaps(ops, window):
        cuts = sorted({lo, hi, *(t for _, a, b, _ in spans for t in (a, b)
                                 if lo < t < hi)})
        for piece in zip(cuts, cuts[1:]):
            name = _innermost(piece, spans)
            out[name] = out.get(name, 0.0) + (piece[1] - piece[0])
    return out


def idle_by_phase(ops: Sequence[Tuple[Any, float, float]], window: Interval,
                  spans: Sequence[Span], phases: Sequence[str]
                  ) -> Dict[str, float]:
    """``idle_by_span`` over the spans named in ``phases`` alone: a piece
    goes to the innermost covering span that is a phase (a gap under
    ``devcache.upload`` under ``sweep.plan`` counts for ``sweep.plan``),
    ``"(no span)"`` when none is."""
    return idle_by_span(ops, window, [s for s in spans if s[0] in phases])


def self_seconds(spans: Sequence[Span]) -> Dict[str, float]:
    """Per span name: duration minus what the spans inside it cover (a span
    is inside another when its interval is; of two equal intervals the later
    in the list is the child)."""
    out: Dict[str, float] = {}
    for i, (name, a, b, _) in enumerate(spans):
        inside = [(c, d) for j, (_, c, d, _) in enumerate(spans)
                  if j != i and a <= c and d <= b
                  and ((c, d) != (a, b) or j > i)]
        cover = trace_reduce.length(trace_reduce.union(inside))
        out[name] = out.get(name, 0.0) + (b - a) - cover
    return out


def scope_seconds(ops: Sequence[Op], window: Interval, pattern: str
                  ) -> Optional[float]:
    """Seconds of the window in which an op whose scope path matches
    ``pattern`` ran (a loop and the ops of its body count once); None when
    no op in the window matches.  ``scope_pattern`` makes the pattern of one
    named scope."""
    rx = re.compile(pattern)
    hit = trace_reduce.clip(((a, b) for path, a, b in ops if rx.search(path)),
                            window)
    return trace_reduce.length(trace_reduce.union(hit)) if hit else None


def scope_pattern(scope: str) -> str:
    return re.escape(scope) + r"\b"


def in_window(spans: Sequence[Span], window: Interval) -> List[Span]:
    """The spans that start inside the window (the window is itself a span:
    a microsecond of room for its own start)."""
    return [s for s in spans if window[0] - 1e-6 <= s[1] < window[1]]


def table(ops: Sequence[Op], window: Interval, spans: Sequence[Span]
          ) -> Dict[str, Any]:
    """The fact line's content: per span name (of the window's spans) its
    count, wall, self time and the idle seconds given to it; per named scope
    its device seconds."""
    idle, selfs = idle_by_span(ops, window, spans), self_seconds(spans)
    by_name: Dict[str, Dict[str, float]] = {}
    for name, a, b, _ in spans:
        row = by_name.setdefault(name, {"n": 0, "wall_s": 0.0})
        row["n"] += 1
        row["wall_s"] += b - a
    for name, row in by_name.items():
        row["self_s"] = selfs[name]
        row["device_idle_s"] = idle.get(name, 0.0)
    if NO_SPAN in idle:
        by_name[NO_SPAN] = {"n": 0, "wall_s": 0.0, "self_s": 0.0,
                            "device_idle_s": idle[NO_SPAN]}
    scopes = sorted({m for path, _, _ in ops for m in SCOPE.findall(path)})
    return {"spans": by_name,
            "scopes": {s: scope_seconds(ops, window, scope_pattern(s))
                       for s in scopes}}


# ---------------------------------------------------------------------------
# for the readers under layers/
# ---------------------------------------------------------------------------
def load(r) -> Dict[str, Any]:
    """The traced step's spans, ops and window: parsed once per run, kept on
    the ``Run`` object, and printed as the ``program_spans`` fact line."""
    got = getattr(r, "program_spans", None)
    if got is not None:
        return got
    try:
        read = read_xplane(trace_reduce.find_xplane(TRACE_DIR))
    except FileNotFoundError:
        read = {"spans": [], "ops": {}}
    window = r.trace["window"]
    ops = read["ops"][min(read["ops"])] if read["ops"] else []
    got = r.program_spans = {"window": window, "ops": ops,
                             "spans": in_window(read["spans"], window)}
    print(json.dumps({"phase": "program_spans", **table(ops, window,
                                                        got["spans"])},
                     default=float), flush=True)
    return got


def phase_idle(r) -> Optional[Dict[str, float]]:
    """``{"feed", "refit_eval", "unattributed"}``: the step's idle seconds
    split three ways, summing to all of them; None when the trace holds no
    span of either phase list (a program that writes none)."""
    p = load(r)
    phases = FEED + REFIT_EVAL
    if not any(s[0] in phases for s in p["spans"]):
        return None
    idle = idle_by_phase(p["ops"], p["window"], p["spans"], phases)
    return {"feed": sum(idle.get(n, 0.0) for n in FEED),
            "refit_eval": sum(idle.get(n, 0.0) for n in REFIT_EVAL),
            "unattributed": idle.get(NO_SPAN, 0.0)}


def scope_device_seconds(r, scope: str) -> Optional[float]:
    p = load(r)
    return scope_seconds(p["ops"], p["window"], scope_pattern(scope))


def span_stat_sum(r, name: str, stat: str) -> Optional[float]:
    """Sum of one stat over the step's spans of that name; None when there
    is no such span."""
    vals = [s[3].get(stat, 0) for s in load(r)["spans"] if s[0] == name]
    return float(sum(vals)) if vals else None
