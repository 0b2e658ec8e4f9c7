"""Share of the traced window in which no operation ran on the device,
in the batch-generation cell."""

from bench.reduce import idle_share


def read(rec):
    return idle_share(rec)
