"""``ops.sweep.run_stats()["score_block_bytes"]`` of the last step's CV
launch: bytes of the [F, C, n, k] float32 score block the scoring half hands
the metric pass.  Silent where the program keeps no such counter."""
from benchmarks import program


def read(r):
    n = program.sweep_record().get("score_block_bytes")
    return float(n) if n else None
