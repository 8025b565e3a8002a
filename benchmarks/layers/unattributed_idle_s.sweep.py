"""The rest of the traced step's device-idle seconds: the gaps no span of
``program_spans.FEED`` or ``REFIT_EVAL`` covers (only ``selector.fit``,
``selector.validate``, ``sweep.launch``, ``stage.fit``, the benchmark's
``bench.step``, or nothing): what the spans still do not explain.  With the
two metrics beside it, it sums to ``device_idle_pct.sweep`` x the window."""
from benchmarks import program_spans


def read(r):
    idle = program_spans.phase_idle(r)
    return idle and idle["unattributed"]
