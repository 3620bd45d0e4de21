"""Seconds from the process's start to the window's opening: weights,
images, measured profiling, mapping, fusion, compiles and warm-up."""


def read(run):
    return run.setup_s
