"""Seeded traffic: request sizes, arrival times and prompt tokens.

A traffic file (``bench/traffic/<name>.json``) gives the parameters; this
module is the one generator that reads them. Sizes and gaps are drawn by
stratified quantiles and then shuffled by the seed, so every seed serves
the same multiset of prompt lengths, output lengths and inter-arrival
gaps in another order: the seed changes the order and the token ids, not
the amount of work. Prompt token ids are uniform over the vocabulary.

Two arrival kinds:

- ``poisson``: open loop at ``rate`` requests/s; ``rate * seconds``
  requests are due inside the window, the first at its start.
- ``closed``: ``clients`` clients, each sending its next request the
  moment its previous one completes. Sizes come in stratified blocks of
  ``clients`` requests.

Prompt lengths are rounded up to the prompt's ``grid``; output lengths
are whole tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class Job:
    """One request as the generator makes it: due offset from the window's
    start (open loop) and its sizes. Token ids are drawn when it is sent."""
    due: float
    isl: int
    osl: int


def stratified_lengths(spec: Dict, n: int, grid: int = 1) -> np.ndarray:
    """n lengths at the midpoint quantiles of a lognormal, clipped to
    [min, max] and rounded up to ``grid`` (sorted; shuffle to use)."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(x, spec["min"], spec["max"])
    return (np.ceil(x / grid) * grid).astype(np.int64)


def stratified_gaps(rate: float, n: int) -> np.ndarray:
    """n exponential inter-arrival gaps at the midpoint quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def grid_lengths(spec: Dict, grid: int = 1) -> List[int]:
    """Every length the spec can produce on ``grid``."""
    lo = -(-int(spec["min"]) // grid) * grid
    hi = -(-int(spec["max"]) // grid) * grid
    return list(range(lo, hi + 1, grid))


def prompt_lengths(spec: Dict, n: int) -> np.ndarray:
    return stratified_lengths(spec, n, int(spec["grid"]))


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream ``stream`` of ``seed`` (any size of int)."""
    return np.random.default_rng([stream, seed % (1 << 64)])


def open_loop_jobs(traffic: Dict, seconds: float, seed: int) -> List[Job]:
    """The open-loop schedule: ``rate * seconds`` jobs due in the window."""
    rate = float(traffic["arrivals"]["rate"])
    n = max(1, int(round(rate * seconds)))
    rng = rng_for(seed, 1)
    gaps = rng.permutation(stratified_gaps(rate, n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    isl = rng.permutation(prompt_lengths(traffic["prompt"], n))
    osl = rng.permutation(stratified_lengths(traffic["output"], n))
    return [Job(float(d), int(i), int(o))
            for d, i, o in zip(due, isl, osl) if d < seconds]


class ClosedLoopSizes:
    """Sizes for a closed loop, in shuffled stratified blocks."""

    def __init__(self, traffic: Dict, seed: int):
        self.block = int(traffic["arrivals"]["clients"])
        self.prompt, self.output = traffic["prompt"], traffic["output"]
        self.rng = rng_for(seed, 1)
        self._queue: List[Job] = []

    def next(self) -> Job:
        if not self._queue:
            n = self.block
            isl = self.rng.permutation(prompt_lengths(self.prompt, n))
            osl = self.rng.permutation(stratified_lengths(self.output, n))
            self._queue = [Job(0.0, int(i), int(o))
                           for i, o in zip(isl, osl)][::-1]
        return self._queue.pop()


class Tokens:
    """Prompt token ids, uniform over the vocabulary, from the seed."""

    def __init__(self, vocab: int, seed: int):
        self.vocab = vocab
        self.rng = rng_for(seed, 2)

    def prompt(self, isl: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=isl, dtype=np.int32)
