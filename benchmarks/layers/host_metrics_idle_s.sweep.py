"""Device-idle seconds of the traced step under ``selector.metrics``: the
host evaluators over the refit winner's train and holdout predictions.  A
part of ``refit_eval_idle_s.sweep``."""
from benchmarks import host_spans


def read(r):
    return host_spans.idle_under(r, ("selector.metrics",))
