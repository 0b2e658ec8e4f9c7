"""``mfu.decode`` in the batch-generation cell, where it moves the
throughput: least compute time of the mean decode execution's model
work over the mean device time of a decode execution."""

from bench.reduce import step_mfu


def read(rec):
    return step_mfu(rec, "decode")
