"""Device seconds of the traced step under the named scope ``metrics.rank``
(AuROC's midranks in ``ops/metrics._binary_one``: since PR 28 the tie bounds
from one neighbour compare, a ``cummax`` and a reversed ``cummin``; the two
scans lose the scope on the TPU, so this reads the compare and the midrank
arithmetic alone): the union of the device ops whose name path holds the
scope, a loop and its body counted once.  Silent when no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "metrics.rank")
