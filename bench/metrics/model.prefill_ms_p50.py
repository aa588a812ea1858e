"""Median time of one prefill call into the model (one prompt into one
slot), host span ended by block_until_ready, ms."""


def read(run):
    return run.pctl("model.prefill", 50)
