"""Seconds from process start to the start of the window: imports, chip
initialisation, building from the seed, warm-up and compiles."""


def read(run):
    return run.setup_s
