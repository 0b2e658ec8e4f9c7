"""95th percentile (nearest rank) of each request's time per output
token after the first, (t_complete - t_first_token) / (n_out - 1); an
unanswered request counts as missing it."""

from bench.reduce import nearest_rank


def read(rec):
    xs = rec["counts"].get("tpot_s")
    return 1e3 * nearest_rank(xs, 95) if xs else None
