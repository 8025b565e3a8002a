"""Share of the traced step in which no op ran on the device: 1 - the union
of the device-op intervals over the traced window."""


def read(r):
    t = r.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
