"""Near-duplicate byte rows and their range-search queries from
``--seed``: the distribution a configuration's ``rows`` entry names as
``near_dup_bytes`` (copy-detection embeddings: SimSearchNet++-style
256-wide uint8 vectors in which some images are edited copies of
others).  One generator a chunk of 65,536 rows, seeded ``[seed, stream,
chunk]`` as ``datagen.py`` does, so the same seed gives the same values
whatever the number of threads; whole numbers 0...255 held as float32.

``{"dist": "near_dup_bytes", "clusters": C, "zipf_s": z, "centre": m,
"centre_spread": a, "noise": s, "copy_share": c, "family_max": F,
"copy_sigma": [lo, hi]}``:

- the background is ``datagen_mix``'s mixture mapped to bytes: centres
  ``m + a * g`` (``g`` N(0, I), drawn from the seed), each row draws its
  cluster with weight ``1 / (j+1)^z`` and is ``centre + s * noise``,
  rounded and clipped to 0...255.  Unrelated rows of one cluster lie
  about ``2 s^2 dim`` apart (squared), far outside any copy radius, and
  rows of different clusters farther, by how far their centres are;
- about a share ``c`` of the rows belong to FAMILIES: an original (a
  background row) and its near-copies.  Family sizes are drawn
  ``2 * (F/2)^u``, ``u`` uniform: Zipf(1) from 2 to ``F`` (cut to an
  eighth of the rows where the corpus is small).  A copy is its
  original plus whole-number noise whose per-column spread is drawn per
  copy, uniform on ``[lo, hi]``: copy-to-original squared distances of
  about ``dim * lo^2`` to ``dim * hi^2``, copy-to-copy up to twice that.
  Family members lie at random positions (a seeded permutation), so a
  row's family says nothing of its position.

Queries (``draw_queries``) come in three kinds, in the fixed numbers a
traffic file's ``shares`` gives for EVERY batch, shuffled within it:
``unrelated`` (fresh background draws), ``small_family`` (a fresh copy
of the original of a family of at most ``small_max`` members) and
``heavy_family`` (the same from a family of more than ``heavy_min``
members; from the largest family where none is that large, as on a tiny
corpus).  A kind's families are drawn one from each of as many equal
strata of its pool, sorted by size, as the batch has queries of the
kind: seeds then differ in rows and not in how long a batch's lists
are.  With ``boundary_pairs`` p, the first 2 p ``small_family`` queries
of every batch are no noisy copies but lie at an EXACT distance from
their family's original (a placed row): p at squared distance
``radius_sq`` (labelled ``AT_RADIUS``: the original is in the answer,
by an inclusive boundary alone) and p at ``radius_sq + 1``
(``PAST_RADIUS``: it is not), by whole-number steps of at most 128 a
column (:func:`boundary_steps`), so that a run's sample can hold the
boundary the configuration guarantees.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

import datagen_mix
from datagen import CHUNK_ROWS, STREAM_QUERIES, rng_for

DIST = "near_dup_bytes"
#: the families' own streams (datagen's are 0...3, datagen_mix's 4)
STREAM_FAMILIES, STREAM_ORIGINALS = 5, 6
#: query kinds, as ``draw_queries`` labels them
KINDS = ("unrelated", "small_family", "heavy_family")
#: labels of the ``small_family`` queries built at an exact distance from
#: their family's original (``boundary_pairs``)
AT_RADIUS, PAST_RADIUS = 3, 4


def _centres(spec: dict, seed: int, dim: int) -> np.ndarray:
    """[clusters, dim] float32 byte-space cluster centres."""
    g = datagen_mix.centres(seed, int(spec["clusters"]), dim)
    g *= np.float32(spec["centre_spread"] * dim ** 0.5)
    g += np.float32(spec["centre"])
    return g


def _cluster_cdf(spec: dict) -> np.ndarray:
    w = 1.0 / np.arange(1, int(spec["clusters"]) + 1) ** float(spec["zipf_s"])
    return np.cumsum(w / w.sum())


def _to_bytes(block: np.ndarray) -> None:
    np.rint(block, out=block)
    np.clip(block, 0.0, 255.0, out=block)


def family_sizes(spec: dict, n: int, seed: int) -> np.ndarray:
    """Members (original included) of each family, largest first, drawn
    until they hold ``copy_share`` of the ``n`` rows."""
    rng = rng_for(seed, STREAM_FAMILIES)
    target = int(float(spec["copy_share"]) * n)
    biggest = max(2, min(int(spec["family_max"]), n // 8))
    sizes, total = [], 0
    while total < target:
        s = min(int(2.0 * (biggest / 2.0) ** rng.random()), target - total)
        if s < 2:
            break
        sizes.append(s)
        total += s
    return np.sort(np.asarray(sizes, np.int64))[::-1]


def _originals(spec: dict, sizes: np.ndarray, seed: int, dim: int,
               cen: np.ndarray) -> np.ndarray:
    """[families, dim] float32: each family's original, a background
    row of its own draw."""
    rng = rng_for(seed, STREAM_ORIGINALS)
    j = np.minimum(np.searchsorted(_cluster_cdf(spec),
                                   rng.random(sizes.size)), len(cen) - 1)
    out = rng.standard_normal((sizes.size, dim), dtype=np.float32)
    out *= np.float32(spec["noise"])
    out += cen[j]
    _to_bytes(out)
    return out


def layout(spec: dict, n: int, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(sizes, family_of)``: the families' sizes, and for every row
    its family (-1: background) with the ORIGINAL marked by ``-2 - f``
    (a member at distance 0 from itself)."""
    sizes = family_sizes(spec, n, seed)
    members = rng_for(seed, STREAM_FAMILIES, 1).permutation(n)[:sizes.sum()]
    family_of = np.full(n, -1, np.int32)
    fam = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    family_of[members] = fam
    first = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    family_of[members[first]] = -2 - np.arange(sizes.size, dtype=np.int32)
    return sizes, family_of


def draw(spec: dict, n: int, dim: int, seed: int, stream: int) -> np.ndarray:
    """[n, dim] float32 rows in the distribution ``spec`` names."""
    if spec["dist"] != DIST:
        return datagen_mix.draw(spec, n, dim, seed, stream)
    cen, cdf = _centres(spec, seed, dim), _cluster_cdf(spec)
    sizes, family_of = layout(spec, n, seed)
    orig = _originals(spec, sizes, seed, dim, cen)
    noise = np.float32(spec["noise"])
    lo_s, hi_s = (float(x) for x in spec["copy_sigma"])
    out = np.empty((n, dim), np.float32)

    def fill(c: int) -> None:
        lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
        rng, block, fam = rng_for(seed, stream, c), out[lo:hi], family_of[lo:hi]
        j = np.minimum(np.searchsorted(cdf, rng.random(hi - lo)),
                       len(cen) - 1)
        sigma = rng.uniform(lo_s, hi_s, hi - lo).astype(np.float32)
        rng.standard_normal(out=block, dtype=np.float32)
        copy, first = np.flatnonzero(fam >= 0), np.flatnonzero(fam <= -2)
        scale = np.full(hi - lo, noise, np.float32)
        scale[copy] = sigma[copy]
        block *= scale[:, None]
        base = cen[j]
        base[copy] = orig[fam[copy]]
        block += base
        _to_bytes(block)
        block[first] = orig[-2 - fam[first]]

    datagen_mix._in_chunks(n, fill)
    return out


def boundary_steps(radius_sq: int) -> np.ndarray:
    """Whole numbers of at most 128 whose squares add up to
    ``radius_sq``, largest first: 96,237 = 5 x 128^2 + 119^2 + 12^2 +
    3^2 + 1 + 1 + 1.  A byte can always be moved by such a step one way
    or the other and stay a byte."""
    steps, left = [], int(radius_sq)
    while left:
        steps.append(min(128, math.isqrt(left)))
        left -= steps[-1] ** 2
    return np.asarray(steps, np.float32)


def _at_distance(row: np.ndarray, steps: np.ndarray) -> np.ndarray:
    """``row`` (bytes) with its first columns moved by ``steps``, each
    up where that stays a byte and down where not."""
    out = row.copy()
    head = out[:steps.size]
    head += np.where(head + steps <= 255.0, steps, -steps)
    return out


def draw_queries(spec: dict, n: int, dim: int, seed: int, batch_rows: int,
                 n_batches: int, shares: Dict[str, int], small_max: int,
                 heavy_min: int, radius_sq: Optional[int] = None,
                 boundary_pairs: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """``(queries [n_batches * batch_rows, dim] float32, kind [...]
    int8)``: every batch holds exactly ``shares`` of each kind (an index
    into KINDS), at shuffled positions; of its ``small_family`` queries
    ``boundary_pairs`` are labelled ``AT_RADIUS`` and as many
    ``PAST_RADIUS`` instead (module docstring)."""
    if sum(int(shares[k]) for k in KINDS) != batch_rows:
        raise ValueError(f"shares {shares} do not add up to a batch of "
                         f"{batch_rows} rows")
    pairs = int(boundary_pairs)
    if pairs:
        steps_at = boundary_steps(int(radius_sq))
        steps_past = np.append(steps_at, np.float32(1.0))
        if steps_past.size > dim or 2 * pairs > int(shares["small_family"]):
            raise ValueError(
                f"{pairs} boundary pairs at squared radius {radius_sq} need "
                f"{steps_past.size} columns of {dim} and {2 * pairs} "
                f"small_family queries of {shares['small_family']}")
    cen, cdf = _centres(spec, seed, dim), _cluster_cdf(spec)
    sizes = family_sizes(spec, n, seed)
    orig = _originals(spec, sizes, seed, dim, cen)
    pools: List[np.ndarray] = [
        np.empty(0, np.int64),
        np.flatnonzero(sizes <= small_max),
        np.flatnonzero(sizes > heavy_min)]
    if pools[1].size == 0:  # sizes are sorted largest first
        pools[1] = np.asarray([sizes.size - 1])
    if pools[2].size == 0:
        pools[2] = np.asarray([0])
    noise = np.float32(spec["noise"])
    lo_s, hi_s = (float(x) for x in spec["copy_sigma"])
    out = np.empty((n_batches * batch_rows, dim), np.float32)
    kinds = np.empty(n_batches * batch_rows, np.int8)
    for b in range(n_batches):
        rng = rng_for(seed, STREAM_QUERIES, b)
        block = out[b * batch_rows:(b + 1) * batch_rows]
        kind = np.repeat(np.arange(len(KINDS), dtype=np.int8),
                         [int(shares[k]) for k in KINDS])
        rng.shuffle(kind)
        kinds[b * batch_rows:(b + 1) * batch_rows] = kind
        j = np.minimum(np.searchsorted(cdf, rng.random(batch_rows)),
                       len(cen) - 1)
        base = cen[j]
        scale = np.full(batch_rows, noise, np.float32)
        for code in (1, 2):
            at = np.flatnonzero(kind == code)
            # one family from each of as many equal strata of the pool
            # (sorted by size) as there are queries: every batch then
            # holds the same spread of list lengths, whatever the seed
            pick = (np.arange(at.size) + rng.random(at.size)) \
                * (pools[code].size / at.size)
            base[at] = orig[pools[code][pick.astype(np.int64)]]
            scale[at] = rng.uniform(lo_s, hi_s, at.size)
        rng.standard_normal(out=block, dtype=np.float32)
        block *= scale[:, None]
        block += base
        _to_bytes(block)
        edge = np.flatnonzero(kind == 1)[:2 * pairs]
        for e, pos in enumerate(edge):
            block[pos] = _at_distance(
                base[pos], steps_at if e < pairs else steps_past)
            kinds[b * batch_rows + pos] = AT_RADIUS if e < pairs \
                else PAST_RADIUS
    return out, kinds
