"""CV fits completed in the window over the window's whole length: a stall
anywhere lengthens the window and so lowers the rate."""


def read(r):
    return r.work_per_step * r.n_steps / r.window_s
