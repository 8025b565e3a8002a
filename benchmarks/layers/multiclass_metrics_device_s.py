"""Device seconds of the traced step under the named scope
``metrics.multiclass`` (the arg-max metric pass over the [F, C, n, k] score
block, ``ops/metrics._multiclass_grid_metrics``: weighted F1 / Precision /
Recall / Error of every fold and candidate): the union of the device ops
whose name path holds the scope.  Silent when no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "metrics.multiclass")
