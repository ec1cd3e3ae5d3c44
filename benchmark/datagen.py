"""Rows and queries from ``--seed``, in the distribution a
configuration's ``rows`` entry names.  The same seed gives the same
values whatever the number of threads: every chunk of rows has its own
generator, seeded ``[seed, stream, chunk]``.

Drawn straight in the type they are held in (uint8 for byte corpora,
float32 otherwise): the float64 draw ``bench.py`` makes is an eighth of
a minute of set-up at these sizes.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 65_536
DISTS = ("uint8", "uniform")
#: streams, so rows, queries and samples never share a generator
STREAM_ROWS, STREAM_QUERIES, STREAM_SAMPLE, STREAM_TRAFFIC = 0, 1, 2, 3


def rng_for(seed: int, stream: int, chunk: int = 0) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.random.default_rng([int(seed), int(stream), int(chunk)])


def draw(spec: dict, n: int, dim: int, seed: int, stream: int) -> np.ndarray:
    """[n, dim] float32: ``{"dist": "uint8"}`` is whole numbers 0...255
    (SIFT-style byte descriptors), ``{"dist": "uniform", "high": h}`` is
    uniform on [0, h)."""
    dist = spec["dist"]
    if dist not in DISTS:
        raise ValueError(f"rows.dist {dist!r} not in {DISTS}")
    high = np.float32(spec.get("high", 1.0))
    out = np.empty((n, dim), np.float32)

    def fill(c: int) -> None:
        lo = c * CHUNK_ROWS
        hi = min(lo + CHUNK_ROWS, n)
        rng = rng_for(seed, stream, c)
        if dist == "uint8":
            out[lo:hi] = rng.integers(0, 256, size=(hi - lo, dim),
                                      dtype=np.uint8)
        else:
            rng.random(out=out[lo:hi], dtype=np.float32)
            if high != 1.0:
                out[lo:hi] *= high

    chunks = range(-(-n // CHUNK_ROWS))
    workers = max(1, min(8, (os.cpu_count() or 2) - 1, len(chunks)))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, chunks))  # list(): raise what a chunk raised
    return out
