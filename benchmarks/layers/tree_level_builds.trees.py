"""``ops.sweep.run_stats()["tree_level_builds"]`` of the last step's CV
launch: levels x trees grown, one level histogram each.  Silent where the
program keeps no such counter."""
from benchmarks import program


def read(r):
    n = program.sweep_record().get("tree_level_builds")
    return float(n) if n else None
