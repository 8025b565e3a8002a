"""The fused sweep programs' share of their roofline in the traced step of a
k-class grid: the least time the chip could take for one selector fit's
required FLOPs and bytes (``multiclass_ops_count.sweep_step``: softmax fits,
k-channel tree histograms, split scans, the winner's refit), over the device
time of the programs of ``ops/sweep.py`` (``_run``, ``_run_scores``,
``_run_metrics``) in the trace.  Silent when the trace names no such
program."""
from benchmarks import multiclass_ops_count, ops_count, trace_reduce

#: jit names of ops/sweep.py's programs as the trace's module line shows them
PROGRAMS = r"jit__run(_scores|_metrics)?\b"


def read(r):
    t = r.trace
    dev_s = trace_reduce.program_seconds(t["modules"], t["window"], PROGRAMS)
    if not dev_s:
        return None
    work = multiclass_ops_count.of_run(r)
    return 100.0 * ops_count.roofline_seconds(work, r.peaks)["seconds"] / dev_s
