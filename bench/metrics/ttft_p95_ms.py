"""95th percentile (nearest rank), over every request due in the window,
of first-token time minus due time; an unanswered request counts as
waiting until the drain limit."""

from bench.reduce import nearest_rank


def read(rec):
    xs = rec["counts"].get("ttft_s")
    return 1e3 * nearest_rank(xs, 95) if xs else None
