"""Output tokens landed on the host in the window, per second."""


def read(rec):
    c = rec["counts"]
    return c["tokens"] / c["window_s"] if "tokens" in c else None
