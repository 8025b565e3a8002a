"""``ops.sweep.run_stats()["launches"]`` of the CV sweep, per step."""


def read(r):
    n = r.counts.get("sweep_launches")
    return n / r.n_steps if n and r.n_steps else None
