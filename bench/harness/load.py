"""Open-loop arrivals and latency arithmetic.

`arrival_pattern` follows `repro.serve.slo.poisson_arrivals`
(exponential gaps, summed), with two changes that keep the run's seed
from changing the work.  The gaps are the n stratified quantiles of the
exponential distribution, in one order fixed by the traffic file's
pattern seed, rather than n independent draws.  The run's seed only
rotates that sequence (and whatever rides with it, such as each job's
size): every seed offers the same gaps and the same clumps, starting at
another point.

`quantile` is `repro.serve.slo.latency_quantiles`' arithmetic (numpy's
linear interpolation) for one quantile, over every job, with a job that
never completed counted as +inf.
"""
from __future__ import annotations

import math

import numpy as np


def stratified_exponential_gaps(n: int, rate_hz: float) -> np.ndarray:
    """The (i + 1/2)/n quantiles of Exp(rate), i = 0..n-1, ascending."""
    u = (np.arange(n, dtype=np.float64) + 0.5) / n
    return -np.log1p(-u) / float(rate_hz)


def arrival_pattern(rate_hz: float, seconds: float, pattern_seed: int,
                    sizes) -> tuple[np.ndarray, np.ndarray]:
    """(gaps, job sizes) of the n = rate·seconds jobs of a window: the
    stratified gaps and the sizes (`sizes` repeated in equal shares),
    each in an order drawn from `pattern_seed`."""
    n = int(math.floor(rate_hz * seconds))
    rng = np.random.default_rng(pattern_seed)
    gaps = rng.permutation(stratified_exponential_gaps(n, rate_hz))
    return gaps, rng.permutation(np.resize(np.asarray(sizes), n))


def rotated_arrivals(gaps, sizes, seconds: float, seed: int):
    """Due times in [0, seconds) and sizes of the pattern rotated to
    start at job `seed mod n`."""
    shift = int(seed) % len(gaps)
    due = np.cumsum(np.roll(gaps, -shift))
    keep = due < seconds
    return due[keep], np.roll(sizes, -shift)[keep]


def quantile(values, q: float) -> float:
    """The q-quantile of `values` (inf entries allowed)."""
    vals = np.asarray(list(values), dtype=np.float64)
    if vals.size == 0:
        raise ValueError("no values to take a quantile over")
    vals = np.sort(vals)
    pos = q * (vals.size - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or vals[hi] == vals[lo]:
        return float(vals[lo])
    if math.isinf(vals[hi]):
        return math.inf
    return float(vals[lo] + (pos - lo) * (vals[hi] - vals[lo]))
