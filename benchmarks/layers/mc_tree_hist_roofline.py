"""The level-histogram build's share of its roofline in the traced step of a
k-class grid: the least time the chip could take for the accumulates the
histograms require and for the bytes they stream and write
(``multiclass_ops_count``: ``k + 1`` adds per training row, kept feature and
level; the binned matrix once a level per forest depth; each histogram's
``k + 1`` planes written once — never the one-hot contraction's FLOPs), over
the device seconds under the scope ``trees.hist``.  Silent when no op carries
the scope."""
from benchmarks import multiclass_ops_count, ops_count, program_spans


def read(r):
    dev_s = program_spans.scope_device_seconds(r, "trees.hist")
    if not dev_s:
        return None
    work = multiclass_ops_count.of_run(r)
    need = ops_count.roofline_seconds(
        {"flops": work["hist_flops"], "bytes": work["hist_bytes"]}, r.peaks)
    return 100.0 * need["seconds"] / dev_s
