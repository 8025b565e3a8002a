"""Device seconds of the traced step under the named scope ``trees.hist`` (the
level-histogram build of ``ops/trees._grow_level_batch``: the scan over row
blocks that makes both one-hots and accumulates the GEMM, the sibling
subtraction), forests, boosting and a tree winner's refit together: the union
of the device ops whose name path holds the scope.  Silent when no op carries
it (a program before the scope existed)."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "trees.hist")
