"""Rows of a k-NN GRAPH build from ``--seed``: a descriptor set whose
every row will be a query of the set itself, with exact copies in it.

``{"dist": "unit_zipf_gauss_mix", "clusters": C, "zipf_s": z, "noise":
a, "copies": c}``: ``datagen_mix.py``'s ``zipf_gauss_mix`` (C Gaussian
clusters with Zipf(z) sizes, in-cluster noise ``a`` of a centre's
length, no spread of scale) with every row then scaled to UNIT length in
float32 (the source's descriptors are PCA outputs, l2-normalised), and
a share ``c`` of the rows made EXACT COPIES of another row, in pairs: a
copy and its source are two rows at distance 0 that differ in nothing
but their ids, as a web-scale image set holds the same image twice.
Which rows are copies, and of which rows, is drawn from the seed (a
stream of its own), so the same seed gives the same rows and the same
pairs.  No row is in two pairs.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import datagen_mix
from datagen import CHUNK_ROWS, rng_for

DIST = "unit_zipf_gauss_mix"
#: the pairs' own stream (datagen's are 0...3, datagen_mix's centres 4)
STREAM_PAIRS = 5


def draw_pairs(n: int, share: float, seed: int) -> np.ndarray:
    """``[pairs, 2]`` int64 ``(source, copy)``: ``round(share * n)``
    pairs of distinct rows, no row in two of them."""
    count = int(round(share * n))
    ids = rng_for(seed, STREAM_PAIRS).permutation(n)[: 2 * count]
    return np.stack([ids[count:], ids[:count]], axis=1).astype(np.int64)


def draw_rows(spec: dict, n: int, dim: int, seed: int, stream: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """``(rows [n, dim] float32 of unit length, pairs [p, 2])``: row
    ``pairs[j, 1]`` is an exact copy of row ``pairs[j, 0]``."""
    if spec["dist"] != DIST:
        raise ValueError(f"rows.dist {spec['dist']!r} is not {DIST!r}")
    rows = datagen_mix.draw(
        {"dist": "zipf_gauss_mix", "clusters": spec["clusters"],
         "zipf_s": spec["zipf_s"], "noise": spec["noise"],
         "scale_sigma": 0.0}, n, dim, seed, stream)

    def unit(c: int) -> None:
        block = rows[c * CHUNK_ROWS:(c + 1) * CHUNK_ROWS]
        block /= np.sqrt(np.einsum("nd,nd->n", block, block))[:, None]

    datagen_mix._in_chunks(n, unit)
    pairs = draw_pairs(n, float(spec["copies"]), seed)
    rows[pairs[:, 1]] = rows[pairs[:, 0]]
    return rows, pairs


def check_rows(pairs: np.ndarray, lo: int, hi: int, n_check: int, seed: int,
               stream: int, copied_share: float = 0.5) -> np.ndarray:
    """``n_check`` distinct rows of ``lo .. hi`` whose answers a run
    compares, ascending, drawn from the seed: the share
    ``copied_share`` of them rows that HAVE an exact copy (a pair's
    source or its copy, wherever the other lies), the rest rows that
    have none."""
    in_pair = np.unique(pairs)
    in_pair = in_pair[(in_pair >= lo) & (in_pair < hi)]
    others = np.setdiff1d(np.arange(lo, hi), in_pair, assume_unique=True)
    rng = rng_for(seed, stream)
    copied = min(int(round(n_check * copied_share)), in_pair.size)
    return np.sort(np.concatenate([
        rng.choice(in_pair, size=copied, replace=False),
        rng.choice(others, size=n_check - copied, replace=False)]))
