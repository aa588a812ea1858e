"""Median time of one decode step over all slots, host span ended by
block_until_ready, ms."""


def read(run):
    return run.pctl("model.decode_step", 50)
