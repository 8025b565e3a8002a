"""Unified observability core: span tracing, one metrics registry, per-run
telemetry records.

Three pieces, one import point:

- :mod:`~transmogrifai_tpu.obs.trace` — thread-safe nested span tracer.
  Every span is a ``jax.profiler.TraceAnnotation`` (it lands in any active
  profiler capture, on the device ops' clock; about a microsecond a span
  with no capture) and, with ``TMOG_TRACE=path.json``, also an event with
  ``id`` / ``parent`` / ``req`` in a bounded ring buffer (``TMOG_TRACE_BUF``)
  exported as Chrome-trace-event JSON (loads in Perfetto).
- :mod:`~transmogrifai_tpu.obs.registry` — named counters/gauges/histograms
  plus scoped sinks.  The legacy surfaces (``ops/sweep.run_stats``,
  ``workflow/stream.stream_stats``, ``utils/flops`` buckets,
  ``serve.ServeMetrics``) are backward-compatible views over it.
- :mod:`~transmogrifai_tpu.obs.record` — schema-versioned JSONL rows
  snapshotting the registry + run context: the training-row format for the
  ROADMAP learned TPU cost model.

``obs.snapshot()`` returns the union: a superset of what ``run_stats() +
stream_stats() + flops.totals() + ServeMetrics.snapshot()`` used to give,
under the keys ``sweep`` / ``stream`` / ``flops`` / ``serve``.
"""
from __future__ import annotations

from typing import Any, Dict

from . import record, registry, regress, slo, timeline, trace
from . import ledger
from .ledger import LaunchLedger, classify_launch, ledger_report
from .record import write_record
from .registry import (REGISTRY, SCHEMA_VERSION, prometheus_text,
                       record_fallback, register_provider, scope)
from .slo import SLOMonitor
from .timeline import bubble_report, format_report
from .trace import complete, instant, span

__all__ = ["trace", "registry", "record", "timeline", "slo", "regress",
           "ledger", "snapshot", "write_record", "span", "instant",
           "complete", "scope", "register_provider", "record_fallback",
           "prometheus_text", "REGISTRY", "SCHEMA_VERSION", "SLOMonitor",
           "bubble_report", "format_report", "LaunchLedger",
           "classify_launch", "ledger_report"]


def snapshot() -> Dict[str, Any]:
    """One call, every telemetry surface.

    Imports the legacy sink modules lazily so their registry scopes and
    providers exist even if nothing else touched them this run — the
    acceptance contract is that this dict is a superset of
    ``run_stats() + stream_stats() + flops.totals() +
    ServeMetrics.snapshot()``.
    """
    for mod in ("transmogrifai_tpu.ops.sweep",
                "transmogrifai_tpu.workflow.stream",
                "transmogrifai_tpu.utils.flops",
                "transmogrifai_tpu.serve.metrics",
                "transmogrifai_tpu.serve.compile_cache",
                "transmogrifai_tpu.resilience",
                "transmogrifai_tpu.continual.controller"):
        try:
            __import__(mod)
        except Exception:  # a broken optional subsystem must not block obs
            pass
    return registry.snapshot()
