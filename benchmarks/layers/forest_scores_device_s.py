"""Device seconds of the traced step under the named scope ``scores.forest``
(every random-forest depth group of the fused sweep: draws, level histograms,
splits, routing, leaf reads, in ``ops/sweep._frag_scores``): the union of the
device ops whose name path holds the scope, a loop and its body counted once.
Silent when no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "scores.forest")
