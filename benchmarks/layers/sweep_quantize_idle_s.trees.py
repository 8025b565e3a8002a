"""Device-idle seconds of the traced step under the host span
``sweep.quantize`` (``impl/sweep_fragments._xb_index``: the quantile sketch
of the sweep's rows on the host, their binning, the pull and the upload back,
inside ``sweep.plan``): each gap cut at the span's edges.  A part of
``sweep_feed_idle_s.sweep``.  Silent when the step holds no such span."""
from benchmarks import program_spans

SPAN = "sweep.quantize"


def read(r):
    p = program_spans.load(r)
    if not any(s[0] == SPAN for s in p["spans"]):
        return None
    return program_spans.idle_by_phase(p["ops"], p["window"], p["spans"],
                                       (SPAN,)).get(SPAN, 0.0)
