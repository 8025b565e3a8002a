"""Device-idle seconds of the traced step while the host was feeding the
sweep: the gaps given to ``selector.split``, ``.prepare``, ``.gather``,
``sweep.plan`` or ``sweep.dispatch`` (``program_spans.FEED``), each gap cut
at span edges and given to the innermost of these spans covering it."""
from benchmarks import program_spans


def read(r):
    idle = program_spans.phase_idle(r)
    return idle and idle["feed"]
