"""Device seconds of the traced step under the named scope ``scores.gbt`` (the
boosted groups of the fused sweep: the scan over rounds, each round's level
histograms, splits, routing and margin update, in
``ops/sweep._frag_scores``): the union of the device ops whose name path
holds the scope, a loop and its body counted once.  Silent when no op carries
it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "scores.gbt")
