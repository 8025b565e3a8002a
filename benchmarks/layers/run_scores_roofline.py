"""The training program's share of its roofline in the traced step: the
least time the chip could take for the required FLOPs and bytes of the 320
CV fits and their scoring (``ops_count.sweep_step`` less the winner's
refit), over the device time of ``ops/sweep.py``'s ``_run_scores`` in the
trace.  Silent when the sweep did not run as the split pair
(``_run_scores`` + ``_run_metrics``)."""
from benchmarks import ops_count, trace_reduce

PROGRAM = r"jit__run_scores\b"


def read(r):
    t = r.trace
    dev_s = trace_reduce.program_seconds(t["modules"], t["window"], PROGRAM)
    if not dev_s:
        return None
    work = ops_count.sweep_step(r.cfg, r.shapes["sweep_rows"], r.shapes["width"],
                                refit=False)
    return 100.0 * ops_count.roofline_seconds(work, r.peaks)["seconds"] / dev_s
