"""Bytes one selector fit sends host -> device past the device cache
(``utils/devcache.put``: the estimators' ``fit_arrays`` / ``predict_arrays``,
the binning's upload, the sweep's fold weights and blob): the sum of the
``bytes`` stat of the traced step's ``devcache.put`` spans.  The cached
uploads are ``sweep_h2d_bytes.sweep``'s."""
from benchmarks import program_spans


def read(r):
    return program_spans.span_stat_sum(r, "devcache.put", "bytes")
