"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and deals out requests from a seed.

A mix is a closed loop of ``clients`` clients; each sends its next
request as soon as the previous one finished.  Requests come in rounds of
``clients``: the sizes of round ``j`` (prompt and output lengths) are
drawn from the mix's own ``shape_seed``, so every run sees the same sizes,
and ``--seed`` only decides which client gets which size of a round and
the token ids of every prompt.  Runs with different seeds therefore do
the same amount of work, in another order and on other tokens.  Round 0,
the fill that set-up prefills in one go, keeps its drawn order for every
seed: that order sets the set-up's prefill chunks, and with them the
device's peak memory and which decode steps code pages, so it is part of
the work and not of its order.

Length distributions (``prompt_len`` / ``output_len``):

  {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
  {"dist": "uniform", "min": a, "max": b}

both in whole tokens, clipped to ``[a, b]``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

LOOPS = ("closed",)


@dataclass(frozen=True)
class Req:
    """One request of the mix: who sends it and what it asks for."""

    client: int
    index: int          # the client's ``index``-th request
    prompt: tuple       # token ids
    max_new: int


def seed_words(seed: int) -> list:
    """A non-negative seed of any size as 32-bit words (numpy's
    ``SeedSequence`` takes them as entropy)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def draw_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = rng.standard_normal(n)
        x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    elif spec["dist"] == "uniform":
        x = rng.integers(lo, hi + 1, size=n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def longest(traffic: dict) -> int:
    """Longest sequence (prompt + output) the mix can ask for."""
    return int(traffic["prompt_len"]["max"]) + int(traffic["output_len"]["max"])


class Mix:
    """Deals the requests of one traffic mix for one ``--seed``."""

    def __init__(self, traffic: dict, seed: int, vocab_size: int):
        if traffic["loop"] not in LOOPS:
            raise ValueError(f"unknown loop {traffic['loop']!r}; "
                             f"known: {LOOPS}")
        self.traffic = traffic
        self.clients = int(traffic["clients"])
        self.seed = seed
        self.vocab_size = vocab_size
        self._rounds: dict = {}

    @property
    def max_len(self) -> int:
        return longest(self.traffic)

    def round_sizes(self, j: int) -> list:
        """The (prompt_len, max_new) pairs of round ``j`` in client order:
        sizes from ``shape_seed``; their order as drawn in round 0, from
        ``--seed`` in every later round."""
        if j not in self._rounds:
            t = self.traffic
            g = np.random.default_rng([int(t["shape_seed"]), j])
            plen = draw_lengths(t["prompt_len"], g, self.clients)
            nout = draw_lengths(t["output_len"], g, self.clients)
            order = np.arange(self.clients) if j == 0 else \
                np.random.default_rng(
                    seed_words(self.seed) + [j, 0x5EED]).permutation(
                        self.clients)
            self._rounds[j] = [(int(plen[k]), int(nout[k])) for k in order]
        return self._rounds[j]

    def request(self, client: int, index: int) -> Req:
        """Client ``client``'s ``index``-th request."""
        plen, nout = self.round_sizes(index)[client]
        g = np.random.default_rng(seed_words(self.seed) + [client, index])
        prompt = g.integers(0, self.vocab_size, size=plen)
        return Req(client=client, index=index,
                   prompt=tuple(int(x) for x in prompt), max_new=nout)
