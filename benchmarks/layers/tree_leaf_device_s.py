"""Device seconds of the traced step under the named scope ``trees.leaves``
(``ops/trees.read_leaves``: every grown tree's leaf value for each of its
training rows, read by selection at the end of a forest chunk and of a boosted
round): the union of the device ops whose name path holds the scope.  Silent
when no op carries it: a program before PR 34 read its leaves by
``take_along_axis`` under no scope of their own, where their seconds are
``forest_scores_device_s`` + ``gbt_scores_device_s`` less ``tree_hist_``,
``tree_split_`` and ``tree_route_device_s``."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "trees.leaves")
