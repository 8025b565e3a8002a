"""The whole step's share of the chip's bf16 peak: FLOPs one selector fit
requires (``ops_count.sweep_step``, from shapes) x steps over the window's
length."""
from benchmarks import ops_count


def read(r):
    sh = r.shapes
    work = ops_count.sweep_step(r.cfg, sh["sweep_rows"], sh["width"],
                                sh["winner_family"], sh["holdout_rows"])
    return 100.0 * work["flops"] * r.n_steps / r.window_s / r.peaks["bf16_flops_per_s"]
