"""Least compute time of the model work of the mean decode execution
(weight matmuls at the int8 peak, attention at each row's true context
at the bf16 peak) over the mean device time of a decode execution."""

from bench.reduce import step_mfu


def read(rec):
    return step_mfu(rec, "decode")
