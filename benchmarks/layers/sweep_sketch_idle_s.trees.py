"""Device-idle seconds of the traced step under ``sweep.sketch``: the host
quantile sketch of the sweep's rows (``ops/trees.sketch_edges``: the row
subsample and ``np.quantile``), inside ``sweep.quantize``.  A part of
``sweep_quantize_idle_s.trees``."""
from benchmarks import host_spans


def read(r):
    return host_spans.idle_under(r, ("sweep.sketch",))
