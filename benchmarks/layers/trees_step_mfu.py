"""The whole step's share of the chip's bf16 peak, for a grid with tree
families: FLOPs one selector fit requires (``trees_ops_count.sweep_step``,
from shapes: accumulates and split scans, not the one-hot contraction) x
steps over the window's length."""
from benchmarks import trees_ops_count


def read(r):
    work = trees_ops_count.of_run(r)
    return 100.0 * work["flops"] * r.n_steps / r.window_s / r.peaks["bf16_flops_per_s"]
