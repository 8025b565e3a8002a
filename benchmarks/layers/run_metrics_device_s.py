"""Device seconds of ``ops/sweep.py``'s ``_run_metrics`` (the [F, C, n]
sort, rank and cumulative-sum pass behind AuROC / AuPR) in the traced step.
Silent when the sweep ran as one fused ``_run``."""
from benchmarks import trace_reduce

PROGRAM = r"jit__run_metrics\b"


def read(r):
    t = r.trace
    return trace_reduce.program_seconds(t["modules"], t["window"], PROGRAM)
