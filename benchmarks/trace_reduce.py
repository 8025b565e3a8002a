"""From a profiler trace to device busy time, idle share, per-program
device time and named idle gaps.

Two parts.  **Reading** (``read_xplane``) turns ``jax.profiler``'s
``.xplane.pb`` into plain intervals: per device the op events and the
program (module) events, and the host's annotation spans.  **Arithmetic**
(everything else) works on those intervals alone, so it is tested on the
CPU with synthetic intervals of known answer; a CPU trace has no device
plane, so none is recorded in the sandbox.

Times are seconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Named = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: roots of the host spans kept, here and by ``program_spans``: the
#: benchmark's own (run.py, entries), the outer cover of every idle gap, and
#: the program's (``obs/trace.py`` call sites), so that a gap is named by what
#: the program was doing and not by ``bench.step`` alone
SPAN_PREFIX = ("bench.", "selector.", "sweep.", "devcache.", "stage.",
               "stream.", "serve.")
OP_NAME_CHARS = 120


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------
def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str) -> Dict[str, Any]:
    """``{"devices": {id: {"ops": [Named], "modules": [Named]}},
    "host_spans": [Named], "inventory": {plane: {line: events}}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Named]]] = {}
    host_spans: List[Named] = []
    inventory: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        inv = inventory.setdefault(plane.name, {})
        for line in plane.lines:
            events = list(line.events)
            inv[line.name] = inv.get(line.name, 0) + len(events)
            if m and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                dev = devices.setdefault(int(m.group(1)),
                                         {"ops": [], "modules": []})
                dev[key].extend((e.name, e.start_ns / 1e9, e.end_ns / 1e9)
                                for e in events)
            elif not m and plane.name.startswith("/host"):
                host_spans.extend((e.name, e.start_ns / 1e9, e.end_ns / 1e9)
                                  for e in events
                                  if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host_spans": host_spans,
            "inventory": inventory}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------
def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted cover of the intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_seconds(events: Sequence[Named], window: Interval) -> float:
    """Seconds of the window in which at least one op ran."""
    return length(union(clip(((a, b) for _, a, b in events), window)))


def gaps(events: Sequence[Named], window: Interval) -> List[Interval]:
    """The window's intervals in which no op ran, longest first."""
    cover = union(clip(((a, b) for _, a, b in events), window))
    out, at = [], window[0]
    for a, b in cover:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if window[1] > at:
        out.append((at, window[1]))
    return sorted(out, key=lambda g: g[0] - g[1])


def name_gap(gap: Interval, spans: Sequence[Named]) -> str:
    """The innermost (shortest) host span that covers most of the gap, or
    ``"(no span)"``."""
    best, best_len = "(no span)", float("inf")
    g = gap[1] - gap[0]
    for name, a, b in spans:
        cover = min(b, gap[1]) - max(a, gap[0])
        if cover >= 0.5 * g and (b - a) < best_len:
            best, best_len = name, b - a
    return best


def top_ops(events: Sequence[Named], window: Interval, k: int = 10
            ) -> List[List[Any]]:
    """``[[name, seconds], ...]``: the ops that took most device time."""
    tot: Dict[str, float] = {}
    for name, a, b in events:
        d = min(b, window[1]) - max(a, window[0])
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d
    # an op's name is its whole HLO line; the head identifies it
    return [[n[:OP_NAME_CHARS], s]
            for n, s in sorted(tot.items(), key=lambda t: -t[1])[:k]]


def idle_gap_table(events: Sequence[Named], window: Interval,
                   spans: Sequence[Named], k: int = 10) -> List[List[Any]]:
    """``[[span name, seconds], ...]``: idle seconds summed by the host span
    covering each gap (``name_gap``), largest first.  A tree step leaves
    hundreds of thousands of gaps between its ops and nearly all lie between
    two neighbouring span edges, where every span covers all of a gap or none
    of it: those stretches are named once."""
    edges = sorted({t for _, a, b in spans for t in (a, b)})
    stretch: Dict[int, str] = {}
    tot: Dict[str, float] = {}
    for g in gaps(events, window):
        i = bisect.bisect_right(edges, g[0])
        if i < len(edges) and edges[i] < g[1]:     # a span edge inside the gap
            n = name_gap(g, spans)
        else:
            n = stretch.get(i) or stretch.setdefault(i, name_gap(g, spans))
        tot[n] = tot.get(n, 0.0) + (g[1] - g[0])
    return [[n, s] for n, s in sorted(tot.items(), key=lambda t: -t[1])[:k]]


def program_seconds(modules: Sequence[Named], window: Interval,
                    pattern: str) -> Optional[float]:
    """Device seconds of the programs whose name matches ``pattern``; None
    when no program in the window does."""
    rx = re.compile(pattern)
    hit = [(a, b) for n, a, b in modules if rx.search(n)]
    hit = clip(hit, window)
    return length(union(hit)) if hit else None


def span_window(spans: Sequence[Named], name: str) -> Optional[Interval]:
    """The first host span of that name."""
    for n, a, b in sorted(spans, key=lambda s: s[1]):
        if n == name:
            return (a, b)
    return None


def summarize(read: Dict[str, Any], window_span: str,
              fallback_window_s: Optional[float] = None) -> Dict[str, Any]:
    """Busy seconds averaged over the devices, the window's length, the
    breakdown and per-device detail for the layer readers."""
    devices = read["devices"]
    if not devices:
        raise ValueError("the trace has no device plane: "
                         f"{list(read['inventory'])}")
    window = span_window(read["host_spans"], window_span)
    if window is None:
        starts = [a for d in devices.values() for _, a, _ in d["ops"]]
        if not starts or fallback_window_s is None:
            raise ValueError(f"no host span {window_span!r} and no device op")
        window = (min(starts), min(starts) + fallback_window_s)
    busy = [busy_seconds(d["ops"], window) for d in devices.values()]
    first = devices[min(devices)]
    return {
        "window": window,
        "window_s": window[1] - window[0],
        "busy_s": sum(busy) / len(busy),
        "modules": first["modules"],
        "breakdown": {
            "device_ops": top_ops(first["ops"], window),
            "idle_gaps": idle_gap_table(first["ops"], window,
                                        read["host_spans"])},
    }
