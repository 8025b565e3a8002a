"""Span tracer: one ``span()``, two sinks.

The repo's hot paths (selector fit phases, per-shard sweep compile/dispatch/
gather, devcache uploads, stream chunk upload/compute/pull, GBT boosting
chains, serve request->batch->swap, DAG stages) are instrumented with
:func:`span` context managers.  Every span goes to:

1. **The profiler's trace.**  ``span()`` always enters a
   ``jax.profiler.TraceAnnotation`` of the same name and attributes.  While a
   ``jax.profiler`` session is active (``benchmarks/run.py --trace 1``,
   TensorBoard, an operator's capture) the span is written into the
   ``.xplane.pb`` host plane ON THE CLOCK THE DEVICE OPS ARE ON, its
   attributes as event stats, so an idle gap of the device can be given to
   the span the host was in (``benchmarks/program_spans.py``).  With no
   session the annotation does nothing: no formatting, no buffer append,
   about a microsecond per enter/exit (not zero).  A stat is fixed when the
   span is entered: an attribute added later by :meth:`set` reaches only the
   second sink.
2. **The tracer's own ring buffer**, when ON (``TMOG_TRACE=path.json``, or
   :func:`enable` in tests): a Chrome trace-event "complete" event
   (``ph: "X"``) per span, bounded at ``TMOG_TRACE_BUF`` events (default
   65536 — oldest drop).  :func:`export` writes the Perfetto-loadable
   ``{"traceEvents": [...]}`` JSON; with ``TMOG_TRACE`` set the file is also
   written at interpreter exit.  Buffer timestamps are microseconds since
   one process-wide ``time.monotonic`` origin, shared by all threads
   (``serve/`` captures :func:`now` at enqueue and hands both ends to
   :func:`complete`, which is therefore buffer-only: the profiler cannot be
   given a span after the fact).

Buffered spans carry, under ``args``, the three fields that make a trace a
tree: ``id``, ``parent`` (the span open on this thread when this one was
entered — a thread-local stack; a pool thread joins its submitter's tree
with ``attach(current())``, or ``bind(fn)`` at the submit) and ``req`` (one identifier per
:func:`request`, opened by the outermost of ``OpWorkflow.train`` /
``ModelSelector.fit`` and inherited by everything under it).  The serve path
keeps the request ids it passes as attributes.
"""
from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["enabled", "enable", "disable", "span", "timed", "instant",
           "complete", "request", "current", "attach", "bind", "now",
           "export", "reset", "events", "DEFAULT_BUF_EVENTS"]

DEFAULT_BUF_EVENTS = 65536

_enabled: bool = False
_path: Optional[str] = None
_buf: Deque[Dict[str, Any]] = deque(maxlen=DEFAULT_BUF_EVENTS)
#: one origin for every thread: ts fields are microseconds since this
_origin: float = time.monotonic()
_atexit_registered = False
_ids = itertools.count(1)
_reqs = itertools.count(1)
#: per thread, the open spans' ``(id, req)``, innermost last
_tls = threading.local()

#: what :func:`current` hands to :func:`attach`: ``(span id, request id)``
Handle = Tuple[Optional[int], Optional[int]]


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def now() -> float:
    """The buffer's clock (``time.monotonic`` seconds).  Callers that span
    across queues capture ``now()`` at entry and pass it to :func:`complete`."""
    return time.monotonic()


def enabled() -> bool:
    return _enabled


def _buf_events() -> int:
    v = os.environ.get("TMOG_TRACE_BUF", "").strip()
    try:
        return max(1, int(float(v))) if v else DEFAULT_BUF_EVENTS
    except ValueError:
        return DEFAULT_BUF_EVENTS


def enable(path: Optional[str] = None, buf_events: Optional[int] = None) -> None:
    """Turn tracing on, ringing at ``buf_events`` (default TMOG_TRACE_BUF).

    ``path`` (or ``TMOG_TRACE``) is where :func:`export` writes by default;
    tests may pass ``path=None`` and export explicitly."""
    global _enabled, _path, _buf, _atexit_registered
    _path = path or os.environ.get("TMOG_TRACE") or _path
    _buf = deque(_buf, maxlen=buf_events or _buf_events())
    _enabled = True
    if _path and not _atexit_registered:
        atexit.register(_export_atexit)
        _atexit_registered = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    _buf.clear()


def events() -> list:
    """A snapshot copy of the buffered events (the timeline/bubble
    profiler's input; same dicts :func:`export` would write)."""
    return list(_buf)


class _Annotation(TraceAnnotation):
    """What :func:`span` returns while the buffer is off: the profiler's
    annotation alone, with the surface of :class:`_Span`."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass


class _Span:
    """A span that reads the clock itself (``seconds`` after exit) and
    records into the buffer when it was on at entry."""

    __slots__ = ("name", "attrs", "t0", "t1", "_ann", "_link")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self._ann = TraceAnnotation(name, **attrs)
        self._link: Optional[Tuple[int, Optional[int], Optional[int]]] = None

    def __enter__(self):
        if _enabled:
            st = _stack()
            parent, req = st[-1] if st else (None, None)
            sid = next(_ids)
            st.append((sid, req))
            self._link = (sid, parent, req)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes discovered mid-span (e.g. a chosen bucket);
        they reach the buffer only — the profiler's stats are fixed at
        entry."""
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._ann.__exit__(*exc)
        if self._link is not None:
            sid, parent, req = self._link
            _stack().pop()
            args = self.attrs
            args.setdefault("id", sid)
            args.setdefault("parent", parent)
            args.setdefault("req", req)
            _buf.append({
                "name": self.name, "ph": "X", "cat": "tmog",
                "ts": (self.t0 - _origin) * 1e6,
                "dur": (self.t1 - self.t0) * 1e6,
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": args,
            })
        return False


def span(name: str, **attrs):
    """Context manager for one nested span: a profiler annotation always,
    a buffered event as well while the buffer is on."""
    if _enabled:
        return _Span(name, attrs)
    return _Annotation(name, **attrs)


def timed(name: str, **attrs) -> _Span:
    """:func:`span` for a caller that needs the duration whether or not the
    buffer is on (``OpListener.time_stage``): ``.seconds`` after exit is the
    span's own two clock reads."""
    return _Span(name, attrs)


def current() -> Optional[Handle]:
    """The innermost span open on this thread, for :func:`attach` in a
    thread this one starts; None while the buffer is off."""
    if not _enabled:
        return None
    st = _stack()
    return st[-1] if st else None


@contextlib.contextmanager
def attach(handle: Optional[Handle]):
    """Make ``handle`` (another thread's :func:`current`) the parent of the
    spans this thread opens inside the block."""
    if handle is None:
        yield
        return
    st = _stack()
    st.append(handle)
    try:
        yield
    finally:
        st.pop()


def bind(fn):
    """``fn`` for a pool or hedge thread: the spans it opens there get this
    thread's innermost open span as their parent (``sweep.shard`` under
    ``sweep.launch``).  ``fn`` itself while the buffer is off."""
    handle = current()
    if handle is None:
        return fn

    def bound(*args, **kwargs):
        with attach(handle):
            return fn(*args, **kwargs)

    return bound


def request():
    """Open a request: spans entered inside share one new ``req``.  Nested
    inside another request it does nothing, so the outermost caller wins."""
    top = current()
    if not _enabled or (top is not None and top[1] is not None):
        return contextlib.nullcontext()
    return attach((top[0] if top else None, next(_reqs)))


def instant(name: str, **attrs) -> None:
    """A zero-duration marker event (``ph: "i"``), buffer-only."""
    if not _enabled:
        return
    st = _stack()
    if st:
        attrs.setdefault("parent", st[-1][0])
        attrs.setdefault("req", st[-1][1])
    _buf.append({
        "name": name, "ph": "i", "cat": "tmog", "s": "t",
        "ts": (time.monotonic() - _origin) * 1e6,
        "pid": os.getpid(), "tid": threading.get_ident(),
        "args": attrs,
    })


def complete(name: str, t_start: float, t_end: float, **attrs) -> None:
    """Record a span whose endpoints were captured elsewhere (both from
    :func:`now`) — the serve path spans enqueue->response across threads.
    Buffer-only: it has no id, parent or ``req`` beyond its attributes, and
    the profiler's trace never sees it."""
    if not _enabled:
        return
    _buf.append({
        "name": name, "ph": "X", "cat": "tmog",
        "ts": (t_start - _origin) * 1e6,
        "dur": max(0.0, (t_end - t_start)) * 1e6,
        "pid": os.getpid(), "tid": threading.get_ident(),
        "args": attrs,
    })


def export(path: Optional[str] = None) -> Optional[str]:
    """Write the buffered events as Chrome trace-event JSON; returns the
    path written (None if no path is known).  Safe to call repeatedly."""
    path = path or _path
    if not path:
        return None
    events = list(_buf)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
    return path


def _export_atexit() -> None:
    try:
        if _enabled:
            export()
    except Exception:
        pass


# env activation: TMOG_TRACE=path.json turns tracing on at import
if os.environ.get("TMOG_TRACE", "").strip():
    enable(os.environ["TMOG_TRACE"].strip())
