"""The whole step's share of the chip's bf16 peak, for a k-class grid: FLOPs
one selector fit requires (``multiclass_ops_count.sweep_step``, from shapes:
softmax iterations, k-channel accumulates and split scans, not the one-hot
contraction) x steps over the window's length."""
from benchmarks import multiclass_ops_count


def read(r):
    work = multiclass_ops_count.of_run(r)
    return 100.0 * work["flops"] * r.n_steps / r.window_s / r.peaks["bf16_flops_per_s"]
