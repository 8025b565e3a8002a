"""Device seconds of the traced step under the named scope ``trees.split``
(cumulative sums over bins, gains, arg-max, the beam's gain ranking, node
records and the next level's parent histograms, in
``ops/trees._grow_level_batch``): the union of the device ops whose name path
holds the scope.  A scan lowered to ``reduce-window`` loses its scope on the
TPU (``PERF.md`` section 7), so the cumulative sums themselves may be missing
from it.  Silent when no op carries the scope."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "trees.split")
