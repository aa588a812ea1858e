"""Median time of one train step: the interval between two steps' ends
as the host waits for them in order (traced runs only), ms."""


def read(run):
    return run.pctl("train.step", 50)
