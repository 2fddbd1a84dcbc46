"""Seconds from the process's start to the window's: imports, weights,
the trainer, compiles or cache loads, and the warm-up work."""


def read(r):
    return r.setup_s
