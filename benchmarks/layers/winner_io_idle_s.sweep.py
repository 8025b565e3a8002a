"""Device-idle seconds of the traced step under ``selector.winner_fit``
(the refit's ``fit_arrays``) and ``selector.winner_predict`` (each
``predict_arrays`` and the pull of its outputs), with their uploads
(``devcache.put``) inside: the winner's host-side time around its device
calls.  A part of ``refit_eval_idle_s.sweep``."""
from benchmarks import host_spans


def read(r):
    return host_spans.idle_under(
        r, ("selector.winner_fit", "selector.winner_predict"))
