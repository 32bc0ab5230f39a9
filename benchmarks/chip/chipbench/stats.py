"""Arithmetic of the yardstick: percentiles, open-loop arrivals, intervals.

Kept with the benchmark so that no later change to the program moves it.
``percentile`` is the linear interpolation of ``numpy.percentile`` (the
two-branch lerp of ``repro.fleet.metrics``); ``poisson_arrivals`` follows
the idea of ``repro.sim.arrivals.Poisson``, with one change that keeps
runs comparable: every seed gets the same multiset of gaps, in its own
order.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(values, p: float) -> float:
    """The ``p``-th percentile of ``values``, linear between order stats."""
    arr = np.sort(np.asarray(values, dtype=np.float64))
    n = arr.size
    if n == 0:
        raise ValueError("percentile of no values")
    pos = (p / 100.0) * (n - 1)
    i = int(math.floor(pos))
    t = pos - i
    a = float(arr[i])
    if t == 0.0:
        return a
    b = float(arr[min(i + 1, n - 1)])
    return a + (b - a) * t if t < 0.5 else b - (b - a) * (1.0 - t)


def poisson_arrivals(rate: float, seconds: float, gap_seed: int,
                     seed: int) -> np.ndarray:
    """Arrival offsets in [0, seconds) of ``round(rate * seconds)`` queries.

    The gaps are exponential draws from ``gap_seed``, scaled so that they
    fill the window; ``seed`` only permutes them.  A permutation of
    independent exponential gaps is again a Poisson process, and every
    seed offers exactly the same load.
    """
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be > 0: {rate}, {seconds}")
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(gap_seed).exponential(1.0, size=n + 1)
    gaps *= seconds / gaps.sum()
    gaps = gaps[np.random.default_rng(seed).permutation(n + 1)]
    return np.cumsum(gaps)[:n]


def merged(intervals) -> list[tuple[float, float]]:
    """``intervals`` merged into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]

