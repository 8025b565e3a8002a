"""Device-idle seconds of the traced step under ``selector.rows``: the
selector's numpy row gathers (the training rows, the refit's and the train
evaluation's ``Xtr[ridx]``, the holdout rows).  A part of
``sweep_feed_idle_s.sweep`` (the training rows, under ``selector.gather``)
and of ``refit_eval_idle_s.sweep`` (the other three)."""
from benchmarks import host_spans


def read(r):
    return host_spans.idle_under(r, (host_spans.ROWS,))
