"""Device-idle seconds of the traced step after the sweep was dispatched:
the gaps given to ``sweep.gather`` (the pull, which also covers the gaps
between the running programs' ops), ``selector.refit`` or
``selector.evaluate`` (``program_spans.REFIT_EVAL``)."""
from benchmarks import program_spans


def read(r):
    idle = program_spans.phase_idle(r)
    return idle and idle["refit_eval"]
