"""Open-loop arrival schedules from a traffic file and a seed, and the
percentile arithmetic, as plain data and numpy (jax-free).  The idea is
``knn_tpu/loadgen``'s (arrival times fixed in advance, latency timed
from the DUE time); this is the benchmark's own copy, which later PRs
cannot change.

Every seed gets the same WORK in another ORDER: the request sizes are a
fixed multiset in the mix's proportions and the gaps between arrivals
are a fixed set (the quantiles of the exponential distribution, so the
arrivals are Poisson in shape), and ``--seed`` shuffles both.  A free
draw of sizes and gaps per seed would change how many of the rare long
requests a window holds, and with it the work, from seed to seed.
"""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import numpy as np


def _sizes(mix: Sequence[dict], n: int) -> np.ndarray:
    """``n`` request sizes in the mix's proportions (largest-remainder
    rounding, every size with a positive weight at least once)."""
    w = np.array([float(m["weight"]) for m in mix])
    if (w <= 0).any():
        raise ValueError("mix weights must be > 0")
    if n < len(mix):
        raise ValueError(
            f"{n} requests cannot hold each of the mix's {len(mix)} sizes")
    exact = w / w.sum() * n
    counts = np.maximum(np.floor(exact).astype(int), 1)
    order = np.argsort(-(exact - np.floor(exact)), kind="stable")
    i = 0
    while counts.sum() < n:
        counts[order[i % len(mix)]] += 1
        i += 1
    while counts.sum() > n:
        counts[int(np.argmax(counts))] -= 1
    return np.repeat([int(m["rows"]) for m in mix], counts)


def _gaps(n: int, rate: float) -> np.ndarray:
    """``n`` exponential gaps as the distribution's own quantiles,
    scaled so that they sum to exactly ``n / rate`` seconds."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (n / rate / g.sum())


def schedule(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> List[Tuple[float, int]]:
    """``[(due_s, rows), ...]`` over ``seconds``, ordered by due time:
    ``round(rate_rps * seconds)`` requests whose sizes follow ``mix``
    (``[{"rows", "weight"}, ...]``), in the order ``rng`` shuffles them
    into."""
    rate = float(traffic["rate_rps"])
    n = int(round(rate * seconds))
    sizes = _sizes(traffic["mix"], n)
    gaps = _gaps(n, rate)
    rng.shuffle(sizes)
    rng.shuffle(gaps)
    # request i arrives when the gaps before it have passed: the first
    # at 0, the last one gap before the window's end
    due = np.cumsum(gaps) - gaps
    return [(float(t), int(r)) for t, r in zip(due, sizes)]


def percentile(samples: Sequence[float], p: float) -> float:
    """The ``p``-th percentile (0...100) by linear interpolation between
    closest ranks; a sample that is infinite (a failed request) counts
    as slower than any limit.  Raises on no samples: a percentile of
    nothing is an error, not a NaN."""
    a = np.sort(np.asarray(samples, np.float64))
    if a.size == 0:
        raise ValueError("percentile of no samples")
    pos = (a.size - 1) * p / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or a[lo] == a[hi]:  # also: inf - inf would be NaN
        return float(a[lo])
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))
