"""The host's own row-gather rate in the traced step, GB/s: the ``bytes``
of the ``selector.rows`` spans over their summed wall.  Host memory bounds
it, so a run on a slow host reads low here whatever the program does."""
from benchmarks import host_spans


def read(r):
    return host_spans.gather_gbps(r)
