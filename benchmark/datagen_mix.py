"""Structured rows and off-distribution queries from ``--seed``: the
distributions a configuration's ``rows`` and ``queries`` entries name
beyond ``datagen.py``'s uniform noise.  One generator a chunk of 65,536
rows, seeded ``[seed, stream, chunk]`` as ``datagen.py`` does, so the
same seed gives the same values whatever the number of threads; float32
throughout.

``{"dist": "zipf_gauss_mix", "clusters": C, "zipf_s": z, "noise": a,
"scale_sigma": s}`` (rows): centres ``c_j`` are N(0, I/dim) (unit length
on average), drawn from the seed; each row draws its cluster
independently with weight proportional to ``1 / (j+1)^z``, so cluster
sizes are heavy-tailed and row order says nothing of the cluster; a row
is ``r * (c_j + a * g)`` with ``g`` N(0, I/dim) and ``r``
log-normal(0, s): norms spread, and inner product does not rank as
cosine does.

``{"dist": "offset_mix", "of": "rows", "keep": b, "offset": o, "noise":
a}`` (queries): a query draws a cluster uniformly (not by size) and is
``b * c_j + o * h_j + a * g``, where ``h_j`` is a second set of C
N(0, I/dim) directions drawn from the queries' own stream: related to
the rows (it shares their centres) and off the set they live on.

Any other ``dist`` is ``datagen.draw``'s.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

import datagen
from datagen import CHUNK_ROWS, rng_for

DISTS = ("zipf_gauss_mix", "offset_mix")
#: the centres' own stream (datagen's are 0...3)
STREAM_CENTRES = 4
#: the chunk number of a stream's per-cluster directions: no data chunk
#: reaches it
DIRECTIONS_CHUNK = 2 ** 31


def directions(seed: int, stream: int, chunk: int, clusters: int, dim: int
               ) -> np.ndarray:
    """[clusters, dim] float32 N(0, I/dim) from ``[seed, stream, chunk]``."""
    g = rng_for(seed, stream, chunk).standard_normal(
        (clusters, dim), dtype=np.float32)
    g *= np.float32(dim ** -0.5)
    return g


def centres(seed: int, clusters: int, dim: int) -> np.ndarray:
    """The rows' cluster centres, shared by rows and queries."""
    return directions(seed, STREAM_CENTRES, 0, clusters, dim)


def _in_chunks(n: int, fill) -> None:
    chunks = range(-(-n // CHUNK_ROWS))
    workers = max(1, min(8, (os.cpu_count() or 2) - 1, len(chunks)))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, chunks))  # list(): raise what a chunk raised


def draw(spec: dict, n: int, dim: int, seed: int, stream: int,
         of: Optional[dict] = None) -> np.ndarray:
    """[n, dim] float32 in the distribution ``spec`` names; ``of`` is the
    rows' spec where ``spec`` refers to it (``"of": "rows"``)."""
    dist = spec["dist"]
    if dist not in DISTS:
        return datagen.draw(spec, n, dim, seed, stream)
    if dist == "offset_mix" and (spec.get("of") != "rows" or of is None
                                 or of["dist"] != "zipf_gauss_mix"):
        raise ValueError(
            f"offset_mix is drawn around the centres of zipf_gauss_mix "
            f"rows; got of={spec.get('of')!r} and rows spec {of!r}")
    clusters = int((of if dist == "offset_mix" else spec)["clusters"])
    cen = centres(seed, clusters, dim)
    noise = np.float32(spec["noise"] * dim ** -0.5)
    out = np.empty((n, dim), np.float32)
    if dist == "zipf_gauss_mix":
        w = 1.0 / np.arange(1, clusters + 1) ** float(spec["zipf_s"])
        cdf = np.cumsum(w / w.sum())
        sigma = float(spec["scale_sigma"])

        def fill(c: int) -> None:
            lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
            rng, block = rng_for(seed, stream, c), out[lo:hi]
            j = np.minimum(np.searchsorted(cdf, rng.random(hi - lo)),
                           clusters - 1)
            scale = np.exp(sigma * rng.standard_normal(hi - lo)
                           ).astype(np.float32)
            rng.standard_normal(out=block, dtype=np.float32)
            block *= noise
            block += cen[j]
            block *= scale[:, None]
    else:
        keep, offset = np.float32(spec["keep"]), np.float32(spec["offset"])
        off = directions(seed, stream, DIRECTIONS_CHUNK, clusters, dim)
        off *= offset
        off += keep * cen

        def fill(c: int) -> None:
            lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
            rng, block = rng_for(seed, stream, c), out[lo:hi]
            j = rng.integers(0, clusters, size=hi - lo)
            rng.standard_normal(out=block, dtype=np.float32)
            block *= noise
            block += off[j]

    _in_chunks(n, fill)
    return out
