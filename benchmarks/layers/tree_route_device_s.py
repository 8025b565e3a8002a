"""Device seconds of the traced step under the named scope ``trees.route``
(the second block scan of a level in ``ops/trees._grow_level_batch``: each
row's next frontier slot and pool node, selected a row block at a time),
forests, boosting and a tree winner's refit together: the union of the device
ops whose name path holds the scope.  The scope is as old as the trees cell
(PR 29), so this reads on every program that can run a tree cell.  Silent when
no op carries it."""
from benchmarks import program_spans


def read(r):
    return program_spans.scope_device_seconds(r, "trees.route")
