"""The one traffic generator: requests from a mix's parameter file and a seed.

A mix (``bench/traffic/<name>.json``) gives length distributions, the arrival
process and the engine settings it is served with.  Every seed gets the same
multiset of sizes and inter-arrival gaps, in another order: lengths and gaps
are dealt from decks of ``deck`` stratified quantiles of their distribution,
each deck shuffled by the seed.  ``"deck": "window"`` makes one deck of the
requests an open loop sends in its window (rate x seconds), so every seed
sends the same sizes in the window.  So two seeds offer the same work, and
the spread between runs of different seeds is the system's, not the draw's.
Token ids are uniform over the vocabulary, drawn from the seed.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()

#: sub-streams of one seed
_SIZES, _TOKENS, _WARM = 1, 2, 3


def quantile(spec: dict, u: float) -> int:
    """Length at quantile ``u`` of a length distribution spec."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"] + 1)
    elif dist == "fixed":
        x = spec["value"]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo, hi = spec.get("min", 1), spec.get("max", x)
    return int(min(max(math.floor(x), lo), hi))


def mean_len(spec: dict) -> float:
    """Mean of one deck of ``spec`` (what a request costs on average)."""
    n = 256
    return sum(quantile(spec, (i + 0.5) / n) for i in range(n)) / n


class Traffic:
    """Request ``i`` of a mix under a seed: ``request(i)`` gives
    ``(prompt, max_new_tokens, due_s)``; ``due_s`` is the arrival time in
    seconds from the window's start (open loop) or None (closed loop)."""

    def __init__(self, mix: dict, seed: int, vocab: int,
                 rate: float | None = None, seconds: float | None = None):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.open = mix["loop"] == "open"
        if self.open:
            if not rate or rate <= 0:
                raise ValueError("an open-loop mix needs a positive rate")
            if mix.get("arrivals", "poisson") != "poisson":
                raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        self.rate = rate
        deck = mix.get("deck", 64)
        if deck == "window":
            if not self.open or not seconds:
                raise ValueError("a window-sized deck needs an open loop and seconds")
            deck = max(round(rate * seconds), 1)
        self.deck = int(deck)
        n = self.deck
        us = [(i + 0.5) / n for i in range(n)]
        self._prompt_deck = np.array([quantile(mix["prompt_len"], u) for u in us])
        self._out_deck = np.array([quantile(mix["output_len"], u) for u in us])
        gaps = np.array([-math.log(1.0 - u) for u in us])
        self._gap_deck = gaps / gaps.mean()     # each deck spans n / rate s
        if mix.get("deck") == "window":         # ... or exactly the window
            self._gap_deck *= rate * seconds / n
        self._decks: dict = {}

    def _rng(self, *stream) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def _deal(self, c: int):
        if c not in self._decks:
            rng = self._rng(_SIZES, c)
            prompts = rng.permutation(self._prompt_deck)
            outs = rng.permutation(self._out_deck)
            gaps = rng.permutation(self._gap_deck)
            starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) + c * self.deck
            self._decks[c] = (prompts, outs, starts)
        return self._decks[c]

    def request(self, i: int):
        prompts, outs, starts = self._deal(i // self.deck)
        k = i % self.deck
        n_prompt = int(prompts[k])
        tokens = self._rng(_TOKENS, i).integers(
            0, self.vocab, n_prompt, dtype=np.int32
        )
        due = float(starts[k]) / self.rate if self.open else None
        return tokens, int(outs[k]), due

    def warmup(self, k: int, length: int, gen: int):
        """Warm-up request ``k``: ``length`` prompt tokens, ``gen`` outputs."""
        tokens = self._rng(_WARM, k).integers(0, self.vocab, length, dtype=np.int32)
        return tokens, int(gen)
