"""Seconds from the process's start to the window's open: imports, the
TPU check, weights made from the seed, compilation or its cache, the
warm-up of the cell's executables, and for a backlog the filled slots."""


def read(run):
    return run.setup_s
