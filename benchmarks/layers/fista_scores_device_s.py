"""Device seconds of the traced step under the named scope ``scores.fista`` (the
FISTA logistic fits and their scoring, in ``ops/sweep._frag_scores``): the
union of the device ops whose name path holds the scope, a loop and its body
counted once.  Silent when no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "scores.fista")
