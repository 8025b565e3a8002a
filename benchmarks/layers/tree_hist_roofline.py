"""The level-histogram build's share of its roofline in the traced step: the
least time the chip could take for the accumulates the histograms require
and for the bytes they stream and write (``trees_ops_count``: one add of g
and of h per training row, kept feature and level; the binned matrix once a
level per group; each histogram written once — never the one-hot
contraction's FLOPs), over ``tree_hist_device_s``.  Silent when no op carries
the ``trees.hist`` scope."""
from benchmarks import ops_count, program_spans, trees_ops_count


def read(r):
    dev_s = program_spans.scope_device_seconds(r, "trees.hist")
    if not dev_s:
        return None
    work = trees_ops_count.of_run(r)
    need = ops_count.roofline_seconds(
        {"flops": work["hist_flops"], "bytes": work["hist_bytes"]}, r.peaks)
    return 100.0 * need["seconds"] / dev_s
