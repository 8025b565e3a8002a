"""Device seconds of the traced step under the named scope ``scores.softmax``
(the multinomial logistic candidates of the fused sweep: the proximal
iterations of ``ops/linear.fit_softmax_grid_folds`` and the [F, G, n, k]
scoring product, in ``ops/sweep._frag_scores``): the union of the device ops
whose name path holds the scope.  Silent when no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "scores.softmax")
