"""Seconds from process start to the start of the window: imports,
weights, compiling (or loading from the cache) and warming the cell's
shapes, and the pre-window ramp."""


def read(run, name):
    return run.setup_s
