"""Labelled rows and queries from ``--seed``: the distribution a
classification configuration's ``rows`` entry names.  One generator a
chunk of 65,536 rows, seeded ``[seed, stream, chunk]`` as ``datagen.py``
does, so the same seed gives the same values whatever the number of
threads; float32 throughout.  ``datagen_mix``'s centres and chunking are
reused by import.

``{"dist": "class_gauss_mix", "classes": C, "groups": G, "spread": b,
"noise": a, "scale_sigma": s, "size_low": l, "size_high": h,
"size_of_rows_n": n0, "size_of_classes": C0}``: G group
directions ``u_g`` and C class directions ``h_c``, each N(0, I/dim) (unit
length on average), class c in group ``c mod G``; a class's centre is
``u_g + b * h_c``, so the classes of one group lie near one another and a
query's wrong neighbours come from its class's siblings, not from
anywhere.  A row of class c is ``r * (centre_c + a * g)`` with ``g`` N(0,
I/dim) and ``r`` log-normal(0, s): norms spread, so cosine, inner
product and squared L2 rank differently.

Class sizes (:func:`class_sizes`): ``size_low`` and ``size_high`` are
the source's range at its own size (``size_of_rows_n`` rows in
``size_of_classes`` classes), scaled by the rows a class has here.  Every class has the most rows, less a deficit that classes drawn in
a seeded order take, each uniformly up to what the range allows, until
the sizes sum to n: most classes full, some tens short, as the source's
split is.  Row order says nothing of the class (a seeded permutation).

Queries: a class drawn uniformly for each, then a fresh row of that
class's law from the queries' stream (held-out images; every class asked
alike, as the source's validation split is).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

import datagen_mix
from datagen import CHUNK_ROWS, rng_for

DIST = "class_gauss_mix"
#: the group directions' stream (datagen's are 0...3, datagen_mix's
#: centres 4) and the labels' own
STREAM_GROUPS, STREAM_LABELS = 5, 6


def class_sizes(spec: dict, n: int, seed: int) -> np.ndarray:
    """[C] int64 rows a class, summing to ``n``, each in ``[low, high]``
    scaled from the entry's ``size_low`` / ``size_high`` by the rows a
    class here over the rows a class in the source."""
    classes = int(spec["classes"])
    scale = (n / classes) / (float(spec["size_of_rows_n"])
                             / float(spec["size_of_classes"]))
    high = max(1, int(np.ceil(spec["size_high"] * scale)))
    low = min(high, max(0, int(np.floor(spec["size_low"] * scale))))
    sizes = np.full(classes, high, np.int64)
    deficit = high * classes - n
    if deficit < 0 or deficit > classes * (high - low):
        raise ValueError(
            f"{n} rows do not fit {classes} classes of {low} to {high} rows")
    rng = rng_for(seed, STREAM_LABELS, 1)
    while deficit:
        for c in rng.permutation(classes):
            room = int(sizes[c] - low)
            if not room or not deficit:
                continue
            take = min(deficit, int(rng.integers(1, room + 1)))
            sizes[c] -= take
            deficit -= take
    return sizes


def row_labels(spec: dict, n: int, seed: int) -> np.ndarray:
    """[n] int32: each class as many times as :func:`class_sizes` says,
    in a seeded order."""
    sizes = class_sizes(spec, n, seed)
    labels = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    return rng_for(seed, STREAM_LABELS, 0).permutation(labels)


def class_centres(spec: dict, dim: int, seed: int) -> np.ndarray:
    """[C, dim] float32: ``u_(c mod G) + spread * h_c``."""
    classes, groups = int(spec["classes"]), int(spec["groups"])
    cen = datagen_mix.centres(seed, classes, dim)
    cen *= np.float32(spec["spread"])
    cen += datagen_mix.directions(seed, STREAM_GROUPS, 0, groups, dim)[
        np.arange(classes) % groups]
    return cen


def _fill(spec: dict, labels: np.ndarray, dim: int, seed: int, stream: int
          ) -> np.ndarray:
    n = labels.shape[0]
    cen = class_centres(spec, dim, seed)
    noise = np.float32(spec["noise"] * dim ** -0.5)
    sigma = float(spec["scale_sigma"])
    out = np.empty((n, dim), np.float32)

    def fill(c: int) -> None:
        lo, hi = c * CHUNK_ROWS, min((c + 1) * CHUNK_ROWS, n)
        rng, block = rng_for(seed, stream, c), out[lo:hi]
        scale = np.exp(sigma * rng.standard_normal(hi - lo)
                       ).astype(np.float32)
        rng.standard_normal(out=block, dtype=np.float32)
        block *= noise
        block += cen[labels[lo:hi]]
        block *= scale[:, None]

    datagen_mix._in_chunks(n, fill)
    return out


def _check(spec: dict) -> None:
    if spec.get("dist") != DIST:
        raise ValueError(f"rows.dist {spec.get('dist')!r} is not {DIST!r}")


def draw_rows(spec: dict, n: int, dim: int, seed: int, stream: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(rows [n, dim] float32, labels [n] int32)."""
    _check(spec)
    labels = row_labels(spec, n, seed)
    return _fill(spec, labels, dim, seed, stream), labels


def draw_queries(spec: dict, n: int, dim: int, seed: int, stream: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(queries [n, dim] float32, the class each was drawn from [n]
    int32): classes uniform, rows of the class's own law."""
    _check(spec)
    labels = rng_for(seed, STREAM_LABELS, 2).integers(
        0, int(spec["classes"]), size=n).astype(np.int32)
    return _fill(spec, labels, dim, seed, stream), labels
