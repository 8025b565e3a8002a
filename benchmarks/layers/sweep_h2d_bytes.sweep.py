"""Bytes one selector fit sends host -> device through
``utils/devcache.device_array``: the sum of the ``bytes`` stat of the traced
step's ``devcache.upload`` spans (entered on a cache miss only)."""
from benchmarks import program_spans


def read(r):
    return program_spans.span_stat_sum(r, "devcache.upload", "bytes")
