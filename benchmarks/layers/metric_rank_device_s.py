"""Device seconds of the traced step under the named scope ``metrics.rank`` (the
``searchsorted`` pair behind AuROC's midranks, in
``ops/metrics._binary_one``): the union of the device ops whose name path
holds the scope, a loop and its body counted once.  Silent when no op carries
it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "metrics.rank")
