"""Process start to window start: imports, the table, the workflow, the
prepared columns and the warm-up of every program the window drives."""


def read(r):
    return r.setup_s
