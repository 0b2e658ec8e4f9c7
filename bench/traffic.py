"""The one traffic generator: reads a mix's parameters, draws from the seed.

A mix is ``bench/traffic/<name>.json``.  Its ``kind`` says what it
generates:

* ``batches``: a pool of ``pool_batches`` input batches of shape
  ``[batch, *shape]``, standard normal, served in a closed loop with at
  most ``in_flight`` batches outstanding.
* ``open_loop``: requests due on a schedule at ``rate_per_s``, whether or
  not earlier ones have finished (independent users).
* ``closed_loop``: a backlog of at least ``backlog`` requests is kept
  queued from the start of the window (batch generation); the stream
  repeats a cycle of ``cycle`` requests made of blocks of ``block``.

Lengths are given as ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}``.  Every seed gets the
same multiset of lengths and gaps between arrivals, in another order:
the values are the distribution's quantiles at (i + 1/2)/n, shuffled by
the seed.  So two seeds differ in order and token ids, not in the
amount of work, and their runs can be compared.
"""

from __future__ import annotations

import dataclasses
import json
import os
import statistics

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str, directory: str = TRAFFIC_DIR) -> dict:
    with open(os.path.join(directory, f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("kind") not in ("batches", "open_loop", "closed_loop"):
        raise ValueError(f"traffic {name}: unknown kind {mix.get('kind')!r}")
    return mix


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the seed (any whole number
    that fits 64 bits, negative ones included)."""
    return np.random.default_rng([seed & (2**64 - 1), stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 1/2)/n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        vals = spec["min"] + np.floor(u * (spec["max"] - spec["min"] + 1))
    elif spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        vals = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Req:
    """One generated request: when it is due (seconds from the window's
    start; None in a closed loop), its prompt and its output budget."""

    due_s: float | None
    prompt: np.ndarray
    max_new_tokens: int


def image_pool(mix: dict, seed: int) -> np.ndarray:
    """``[pool_batches, batch, *shape]`` float32 inputs."""
    shape = (mix["pool_batches"], mix["batch"], *mix["shape"])
    return rng_for(seed, 0).standard_normal(shape, np.float32)


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              rate_per_s: float | None = None) -> list[Req]:
    """The requests due in a window of ``seconds``: round(rate * seconds)
    of them, the gaps between them the exponential distribution's
    quantiles (a Poisson process), shuffled."""
    rate = rate_per_s if rate_per_s is not None else mix["rate_per_s"]
    n = max(1, round(rate * seconds))
    rng = rng_for(seed, 1)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u) / rate)
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    prompts = rng.permutation(quantiles(mix["prompt"], n))
    outputs = rng.permutation(quantiles(mix["output"], n))
    return [Req(float(t), rng.integers(0, vocab, int(p)).astype(np.int32),
                int(o)) for t, p, o in zip(due, prompts, outputs)]


class ClosedLoop:
    """An endless, seeded request stream: ``cycle`` length pairs,
    repeated; token ids fresh per request.  The cycle is made of blocks
    of ``block`` requests (the whole cycle by default), each the same
    stratified set shuffled anew, so that every seed's first requests
    carry the same lengths and a window's work does not hang on the
    order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.vocab = vocab
        self.rng = rng_for(seed, 2)
        n = mix.get("cycle", 256)
        b = mix.get("block", n)
        if n % b:
            raise ValueError(f"cycle {n} is not a whole number of "
                             f"blocks of {b}")

        def blocks(spec):
            q = quantiles(spec, b)
            return np.concatenate([self.rng.permutation(q)
                                   for _ in range(n // b)])
        self.prompts, self.outputs = blocks(mix["prompt"]), \
            blocks(mix["output"])
        self.i = 0

    def take(self, n: int) -> list[Req]:
        out = []
        for _ in range(n):
            j = self.i % len(self.prompts)
            out.append(Req(None, self.rng.integers(
                0, self.vocab, int(self.prompts[j])).astype(np.int32),
                int(self.outputs[j])))
            self.i += 1
        return out

