"""Share of the traced window in which no operation ran on the device."""

from bench.reduce import idle_share


def read(rec):
    return idle_share(rec)
