"""The generator: the same seed gives the same traffic, and every seed the
same multiset of lengths and gaps, in another order."""

import numpy as np

from bench import traffic

SEEDS = (0, 7, 2**31 + 5, -3)


def test_open_loop_same_seed_same_traffic():
    mix = traffic.load_mix("chat")
    a = traffic.open_loop(mix, 2**31 + 5, 10.0, 49152)
    b = traffic.open_loop(mix, 2**31 + 5, 10.0, 49152)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))


def test_open_loop_seeds_share_the_work():
    mix = traffic.load_mix("chat")
    runs = [traffic.open_loop(mix, s, 40.0, 49152) for s in SEEDS]
    n = round(mix["rate_per_s"] * 40.0)
    for reqs in runs:
        assert len(reqs) == n
        due = [r.due_s for r in reqs]
        assert due[0] == 0.0 and due == sorted(due) and due[-1] < 40.0
        lens = [len(r.prompt) for r in reqs]
        assert min(lens) >= mix["prompt"]["min"]
        assert max(lens) <= mix["prompt"]["max"]
    key = lambda reqs: (sorted(len(r.prompt) for r in reqs),  # noqa: E731
                        sorted(r.max_new_tokens for r in reqs),
                        sorted(np.round(np.diff(
                            [r.due_s for r in reqs] + [40.0]), 9)))
    assert all(key(r) == key(runs[0]) for r in runs[1:])
    assert [len(r.prompt) for r in runs[0]] != [len(r.prompt)
                                                for r in runs[1]]


def test_closed_loop_cycles_a_stratified_set():
    mix = traffic.load_mix("offline")
    a = traffic.ClosedLoop(mix, 1, 49152).take(2 * mix["cycle"])
    b = traffic.ClosedLoop(mix, 2, 49152).take(mix["cycle"])
    first = sorted(len(r.prompt) for r in a[:mix["cycle"]])
    assert first == sorted(len(r.prompt) for r in a[mix["cycle"]:])
    assert first == sorted(len(r.prompt) for r in b)
    b = mix["block"]
    block = sorted(traffic.quantiles(mix["prompt"], b))
    assert first == sorted(block * (mix["cycle"] // b))
    for k in range(0, len(a), b):  # each block is the same stratified set
        assert sorted(len(r.prompt) for r in a[k:k + b]) == block
        assert sorted(r.max_new_tokens for r in a[k:k + b]) == sorted(
            traffic.quantiles(mix["output"], b))
    assert min(first) >= mix["prompt"]["min"]
    assert max(first) <= mix["prompt"]["max"]


def test_image_pool():
    mix = dict(traffic.load_mix("images-224-b8"), pool_batches=2)
    a = traffic.image_pool(mix, 2**31 + 9)
    assert a.shape == (2, 8, 224, 224, 3) and a.dtype == np.float32
    assert (a == traffic.image_pool(mix, 2**31 + 9)).all()
    assert not (a == traffic.image_pool(mix, 3)).all()


def test_lognormal_quantiles():
    spec = {"dist": "lognormal", "median": 160, "sigma": 0.8, "min": 16,
            "max": 768}
    q = traffic.quantiles(spec, 201)
    assert q[100] == 160 and q.min() >= 16 and q.max() <= 768
