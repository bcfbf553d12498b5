"""Closed-loop chat traffic from a mix file (``bench/traffic/<mix>.json``).

A mix states ``clients``; each client sends its next request as soon as its
last one ends.  Prompt and output lengths follow clipped lognormals
(``median``, ``sigma``, ``min``, ``max``).  So that every seed serves the
same work in another order, the lengths are not drawn at random: round ``j``
of the clients (the j-th request of each) takes the ``clients`` quantile
points ``(i + 0.5) / clients`` of each distribution, dealt out to the
clients by a permutation drawn from ``(seed, j)``, and prompts and outputs
are dealt by permutations of their own.  A mix with ``"order": "fixed"``
draws the permutations from ``j`` alone, so every seed serves the same
lengths in the same order: a window that holds a few rounds then sees the
same work whatever the seed.  Token ids are drawn from ``(seed, client,
j)``.  Request ``(client, j)`` depends on nothing else, so a run that goes
further serves a longer prefix of the same sequence.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Tuple

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a lognormal
    with ``dist["median"]`` and ``dist["sigma"]``, clipped to
    ``[dist["min"], dist["max"]]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(raw, dist["min"], dist["max"]).astype(np.int64)


def _seed_words(seed: int):
    return [int(seed) >> 32, int(seed) & 0xFFFFFFFF]


class ClosedLoop:
    """The request sequence of every client of one mix, from one seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.clients = int(mix["clients"])
        self.vocab = int(vocab)
        self.seed = _seed_words(seed)
        self.deal_seed = [] if mix.get("order") == "fixed" else self.seed
        self.prompts = quantile_lengths(mix["prompt"], self.clients)
        self.outputs = quantile_lengths(mix["output"], self.clients)
        self._rounds = {}

    def _deal(self, j: int) -> Tuple[np.ndarray, np.ndarray]:
        if j not in self._rounds:
            rng = np.random.default_rng(self.deal_seed + [0, j])
            self._rounds[j] = (rng.permutation(self.clients),
                               rng.permutation(self.clients))
        return self._rounds[j]

    def request(self, client: int, j: int) -> Tuple[np.ndarray, int]:
        """``(prompt ids int32, output length)`` of the j-th request of
        ``client``."""
        pp, po = self._deal(j)
        plen = int(self.prompts[pp[client]])
        rng = np.random.default_rng(self.seed + [1, client, j])
        prompt = rng.integers(0, self.vocab, plen).astype(np.int32)
        return prompt, int(self.outputs[po[client]])

    def max_cache_rows(self, chunk: int) -> int:
        """Cache rows the longest request needs, its prompt padded to a
        whole number of ``chunk``-token prefill chunks."""
        p, o = int(self.prompts.max()), int(self.outputs.max())
        return max(p + o, math.ceil(p / chunk) * chunk)
