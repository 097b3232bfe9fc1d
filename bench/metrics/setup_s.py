"""Seconds from process start to the window's start: imports, weights,
engine, compilation (or loading it from the cache) and warm-up."""


def read(run):
    return run.setup_s
