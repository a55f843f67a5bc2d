"""The one traffic generator: reads a mix file (``mixes/<name>.json``) and
draws requests from ``--seed``.

Every length set is the set of quantiles ``(i + 0.5) / n`` of the mix's
distribution, rounded and clipped, and the seed only shuffles it; the same
holds for the gaps between arrivals. So every seed carries the same work
and the seed changes only order, pairing, gaps and token ids.

Mix keys:

- ``loop``: ``"backlog"`` (a standing queue of ``backlog`` requests,
  refilled as they finish, drawn in blocks of ``block`` requests, each
  block one shuffled quantile set) or ``"open"`` (Poisson arrivals at
  ``rate_rps``; ``lead_s`` seconds of arrivals before the window bring the
  engine to its steady state, the window gets exactly
  ``round(rate_rps * seconds)`` arrivals, and ``drain_s`` bounds the wait
  for the last of them);
- ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, Iterator, List

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    prompt: List[int]
    max_new: int
    due: float = 0.0        # seconds after the window opens (open loop)
    measured: bool = True   # lead-in requests only bring on steady state


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(seed) % 2 ** 64, stream]))


def quantile_set(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def exp_gaps(n: int) -> np.ndarray:
    """Exponential gaps at the quantiles (i + 0.5) / n, mean 1."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q)


def arrivals(gaps: np.ndarray, span: float) -> np.ndarray:
    """Arrival offsets in [0, span): the first at 0, the gaps after it
    in proportion to ``gaps[1:]``."""
    c = np.cumsum(gaps)
    return span * (c - gaps) / c[-1]


def _requests(rng, n: int, mix, vocab: int, rid0: int) -> List[Planned]:
    p = rng.permutation(quantile_set(mix["prompt"], n))
    o = rng.permutation(quantile_set(mix["output"], n))
    return [Planned(rid=rid0 + i,
                    prompt=rng.integers(0, vocab, int(p[i])).tolist(),
                    max_new=int(o[i]))
            for i in range(n)]


def backlog(mix: Dict[str, Any], seed: int, vocab: int
            ) -> Iterator[Planned]:
    """Endless requests in blocks, each block one shuffled quantile set."""
    block = int(mix["block"])
    for b in range(10 ** 9):
        yield from _requests(rng_for(seed, b), block, mix, vocab, b * block)


def open_loop(mix: Dict[str, Any], seed: int, vocab: int,
              seconds: float) -> List[Planned]:
    """Lead-in arrivals (offsets in [-lead_s, 0)) then the window's
    ``round(rate * seconds)`` arrivals (offsets in [0, seconds))."""
    rate = float(mix["rate_rps"])
    out: List[Planned] = []
    for stream, span, shift, measured in (
            (1, mix["lead_s"], -mix["lead_s"], False),
            (2, seconds, 0.0, True)):
        n = int(round(rate * span))
        if n == 0:
            continue
        rng = rng_for(seed, stream)
        reqs = _requests(rng, n, mix, vocab, len(out))
        due = arrivals(rng.permutation(exp_gaps(n)), span) + shift
        for r, d in zip(reqs, due):
            r.due, r.measured = float(d), measured
        out += reqs
    return out


def prefill_pages(mix: Dict[str, Any], page: int) -> List[int]:
    """Page counts of every prefill shape the mix's prompts can take."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return list(range(math.ceil(lo / page), math.ceil(hi / page) + 1))
