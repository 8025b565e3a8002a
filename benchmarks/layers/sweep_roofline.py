"""The fused sweep programs' share of their roofline in the traced step:
the least time the chip could take for one selector fit's required FLOPs
and bytes (``ops_count``), over the device time of the programs of
``ops/sweep.py`` (``_run``, ``_run_scores``, ``_run_metrics``) in the trace.
Silent when the trace names no such program."""
from benchmarks import ops_count, trace_reduce

#: jit names of ops/sweep.py's programs as the trace's module line shows them
PROGRAMS = r"jit__run(_scores|_metrics)?\b"


def read(r):
    t = r.trace
    dev_s = trace_reduce.program_seconds(t["modules"], t["window"], PROGRAMS)
    if not dev_s:
        return None
    sh = r.shapes
    work = ops_count.sweep_step(r.cfg, sh["sweep_rows"], sh["width"],
                                sh["winner_family"], sh["holdout_rows"])
    return 100.0 * ops_count.roofline_seconds(work, r.peaks)["seconds"] / dev_s
