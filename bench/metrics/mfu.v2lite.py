"""``mfu.offline`` in the DeepSeek-V2-Lite batch-generation cell: least
compute time of the mean decode execution's model work (its held
experts' routed rows from the program's counters) over the mean device
time of a decode execution."""

from bench.reduce import step_mfu


def read(rec):
    return step_mfu(rec, "decode")
