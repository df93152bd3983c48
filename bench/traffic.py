"""Seeded request schedules from a traffic mix file.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps in each segment of the schedule (a chat cell's
pre-roll, window and tail; a batch cell's one queue): lengths are the
mix's lognormal read at evenly spaced quantiles, gaps the exponential's
quantiles at the cell's rate.  The seed only permutes them within a
segment and draws the token ids.  So two seeds offer a chat window the
same work in another order, and the spread between seeds is the system's
and not the generator's.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Mapping, Sequence, Tuple

import numpy as np

__all__ = ["Req", "lognormal_quantiles", "exp_quantiles", "schedule",
           "segment"]


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    arrival: float          # seconds, relative to the start of the load
    prompt: Sequence[int]
    max_new: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 1/2) / n`` of a lognormal,
    rounded and clipped to ``[lo, hi]``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def exp_quantiles(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of an exponential."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _lengths(spec: Mapping, n: int) -> np.ndarray:
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def schedule(mix: Mapping, segments: Sequence[Tuple[int, float]], seed: int,
             vocab: int, rate: float | None = None) -> List[Req]:
    """Requests of ``mix`` in ``segments`` of ``(count, seconds)``.  Each
    segment draws its own multiset of lengths at ``count`` quantiles, so a
    segment, such as the measured window, offers the same work whatever the
    seed.  ``arrival == "poisson"`` spreads a segment's requests over its
    seconds by the exponential's quantiles at ``rate``, scaled to fill it
    exactly, the first at the segment's start; ``arrival == "offline"``
    queues them all at 0."""
    if mix["arrival"] not in ("poisson", "offline"):
        raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
    if mix["arrival"] == "poisson" and (not rate or rate <= 0):
        raise ValueError("a poisson mix needs a positive rate")
    rng = np.random.default_rng(seed)
    prompts: List[int] = []
    outputs: List[int] = []
    arrivals: List[float] = []
    start = 0.0
    for n, seconds in segments:
        prompts += rng.permutation(_lengths(mix["prompt"], n)).tolist()
        outputs += rng.permutation(_lengths(mix["output"], n)).tolist()
        if mix["arrival"] == "poisson":
            gaps = rng.permutation(exp_quantiles(n, rate))
            gaps *= seconds / gaps.sum()
            arrivals += (start + np.cumsum(gaps) - gaps).tolist()
            start += seconds
        else:
            arrivals += [0.0] * n
    return [Req(rid=i, arrival=float(arrivals[i]),
                prompt=rng.integers(0, vocab, int(prompts[i])).tolist(),
                max_new=int(outputs[i]))
            for i in range(len(prompts))]


def segment(seconds: float, rate: float) -> Tuple[int, float]:
    """A poisson segment of ``seconds`` at ``rate``: at least one request."""
    return max(1, round(seconds * rate)), seconds
