"""Stratified draws: the same set of values for every seed, in an order
that the seed decides.

A run's work then does not depend on its seed, only the order in which
it arrives does; runs with different seeds differ no more than two runs
of one seed would.  Each set is the distribution's quantiles at the
midpoints of ``n`` equal slices of probability, so it matches the
declared distribution as closely as ``n`` values can.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _mids(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(n: int, median: float, sigma: float, lo: int,
                  hi: int) -> np.ndarray:
    """``n`` integer lengths: lognormal quantiles clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf(p) for p in _mids(n)])
    vals = np.rint(median * np.exp(sigma * z)).astype(np.int64)
    return np.clip(vals, lo, hi)


def uniform_int_set(n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` integers spread evenly over [lo, hi]."""
    return np.rint(lo + _mids(n) * (hi - lo)).astype(np.int64)


def exponential_set(n: int, rate: float) -> np.ndarray:
    """``n`` gaps: exponential quantiles with mean ``1 / rate``."""
    return -np.log1p(-_mids(n)) / rate


def rng(seed: int, *tags: int) -> np.random.Generator:
    """A generator keyed by the run's seed and the tags of one draw."""
    return np.random.default_rng([int(seed)] + [int(t) for t in tags])


def shuffled(values: np.ndarray, seed: int, tag: int) -> np.ndarray:
    return rng(seed, tag).permutation(values)


def stratified(values: np.ndarray, seed: int, tag: int,
               block: int) -> np.ndarray:
    """``values`` in an order the seed draws, stratified in blocks: the
    sorted values fall into ``block`` strata of equal count, and every
    run of ``block`` consecutive outputs holds one value of each stratum
    (so the largest values never bunch).  ``len(values)`` must be a
    multiple of ``block``."""
    n = len(values)
    if n % block:
        raise ValueError(f"{n} values do not fill blocks of {block}")
    g = rng(seed, tag)
    strata = np.sort(np.asarray(values)).reshape(block, n // block)
    strata = np.stack([g.permutation(row) for row in strata])
    cols = [strata[g.permutation(block), j] for j in range(n // block)]
    return np.concatenate(cols)


def odd(n: int) -> int:
    """``n`` or ``n + 1``, whichever is odd: a length that fills no
    whole number of pages of any power-of-two page size."""
    return n if n % 2 else n + 1
