"""Median time of one CF-head scoring call (factor lookups through the
hot-row cache and sharded tables, fusion, ranking), host span, ms."""


def read(run):
    return run.pctl("cf.score", 50)
