"""Host-clock wall of the selector stage's ``fit`` (the benchmark's own
``OpListener`` span around it), mean seconds per step of the window."""


def read(r):
    return r.stage_wall("modelSelector.fit")
