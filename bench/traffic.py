"""Traffic arithmetic: Zipf adapter popularity and stratified lengths.

One general generator reads every mix file. Everything is drawn from the
run's ``--seed``; the same seed gives the same requests in the same order.

Lengths are drawn in stratified blocks: each block of ``strata``
consecutive draws takes one value from each of ``strata`` equal-probability
slices of the distribution. The values of each block are the same for
every seed (they come from a generator seeded by the block's place alone);
the seed sets their order, the prompt tokens and the adapters. So every
seed serves the same set of sizes in another order, and runs with
different seeds differ in which request comes when, not in how much work
the traffic holds.

Where a window serves too few requests for the orders to average out (a
closed loop whose requests outlast the window, so that only a few of a
block's prompts are prefilled inside it), the mix sets ``"length_order":
"fixed"``: the order, and the shares of the requests in progress, are then
the same for every seed too, and the seed draws the tokens and adapters.

The Zipf weights and the clipped-length idea follow
``repro/serving/loadgen.py``; the arithmetic is copied here so that the
benchmark's traffic does not change when the program's generator does.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


def zipf_probs(n: int, s: float) -> np.ndarray:
    """P(adapter rank i) ~ 1 / (i + 1)^s, normalised."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def quantile(spec: dict, u: float) -> int:
    """The length at probability ``u`` (0 < u < 1) of a length spec:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * NormalDist().inv_cdf(u))
        return int(min(max(round(x), lo), hi))
    if spec["dist"] == "uniform":
        return int(min(lo + int(u * (hi - lo + 1)), hi))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def strata_points(n: int, fixed: np.random.Generator,
                  rng: np.random.Generator) -> np.ndarray:
    """One point from each of ``n`` equal slices of (0, 1): the points
    from ``fixed``, their order from ``rng``."""
    u = (np.arange(n) + fixed.random(n)) / n
    return np.clip(u, 1e-9, 1 - 1e-9)[rng.permutation(n)]


class Stratified:
    """An endless stream of lengths, one per stratum in each block."""

    def __init__(self, spec: dict, rng: np.random.Generator, strata: int,
                 stream: int):
        self.spec, self.rng, self.strata = spec, rng, int(strata)
        self.fixed = np.random.default_rng([stream, 0x51CE])
        self._buf: List[int] = []

    def next(self) -> int:
        if not self._buf:
            u = strata_points(self.strata, self.fixed, self.rng)
            self._buf = [quantile(self.spec, float(x)) for x in u[::-1]]
        return self._buf.pop()


@dataclass
class Request:
    prompt: np.ndarray          # int32 token ids
    max_tokens: int
    adapter: int                # adapter index


@dataclass
class Group:
    """Requests served together under one switched-in adapter."""
    adapter: int
    prompts: np.ndarray         # (batch, length) int32
    max_tokens: int


class Traffic:
    """The generator every mix file drives. ``mix`` keys used here:
    ``adapters``, ``zipf_s``, ``prompt``, ``output``,
    ``strata``, ``length_order``, and for grouped mixes ``group`` and
    ``buckets``."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab = mix, int(vocab)
        self.rng = np.random.default_rng([int(seed), 0x7AFF1C])
        fixed = mix.get("length_order", "seed") == "fixed"

        def order(stream: int) -> np.random.Generator:
            return (np.random.default_rng([stream, 0x0DE2]) if fixed
                    else self.rng)

        strata = mix.get("strata", 16)
        self.prompt_len = Stratified(mix["prompt"], order(1), strata, 1)
        self.output_len = Stratified(mix["output"], order(2), strata, 2)
        self.share_order = order(3)
        self.probs = zipf_probs(mix["adapters"], mix["zipf_s"])

    def _adapter(self) -> int:
        return int(self.rng.choice(len(self.probs), p=self.probs))

    def _tokens(self, *shape) -> np.ndarray:
        return self.rng.integers(0, self.vocab, shape, dtype=np.int32)

    def request(self) -> Request:
        return Request(self._tokens(self.prompt_len.next()),
                       self.output_len.next(), self._adapter())

    def in_progress(self, n: int) -> List[float]:
        """Shares of output still to come for ``n`` requests that stand for
        ones already in progress when a closed loop is observed: one from
        each of ``n`` equal slices of (0, 1), the same for every seed, in
        the mix's length order."""
        return list(strata_points(n, np.random.default_rng([3, 0x51CE]),
                                  self.share_order))

    def group(self, active: Optional[int]) -> Group:
        """The next group: a Zipf adapter other than ``active`` (so every
        group switches), prompts padded up to the smallest bucket that
        holds the drawn length, one output length for the group."""
        a = self._adapter()
        while a == active and len(self.probs) > 1:
            a = self._adapter()
        n = self.prompt_len.next()
        length = min(b for b in self.mix["buckets"] if b >= n)
        return Group(a, self._tokens(self.mix["group"], length),
                     self.output_len.next())
