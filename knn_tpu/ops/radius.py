"""Fixed-radius neighbor search — the radius counterpart of top-k.

Beyond the reference (which only does top-K, knn_mpi.cpp:315-338), but a
standard neighbor-API surface its users expect.  Variable-length results
are TPU-hostile (dynamic shapes defeat XLA), so the formulation is
bounded-width:

- the result rows are the lexicographic nearest-``max_neighbors`` prefix
  (ops.topk semantics — ties to the lower index), masked to the radius:
  entries beyond it carry ``+inf`` distance and index ``SENTINEL_IDX``;
  in-radius entries form a contiguous ascending-distance prefix;
- a second matmul-bound tiled pass (:func:`count_within`) counts ALL
  rows inside the radius with the same float32 distance arithmetic as
  the selection, so truncation (``counts > max_neighbors``) is always
  visible to the caller — never a silently incomplete result.

Radius units follow each metric's RANKING space returned by
ops.distance.pairwise_distance: the l2 family takes a true Euclidean
radius (thresholded against squared distances internally), l1 a raw
Manhattan radius, cosine a cosine-distance (1 - similarity) radius.
``dot`` has no radius semantics (scores are unbounded similarities) and
is rejected.  Membership of points within float32 rounding of the
boundary follows the f32 arithmetic.

The UNBOUNDED, float64-exact counterpart is
``ShardedKNN.range_search_certified`` (parallel.sharded): complete
variable-length result lists whose membership is decided in float64.
Its device side lives here too: :func:`within_words` (one pass over the
rows that counts, and marks as bits, every row at or under a per-query
float32 threshold) and :func:`compact_words` (the marked words
compacted to the one width :func:`range_width` gives), with
:func:`decode_words` their host inverse.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from knn_tpu.ops.distance import pairwise_distance
from knn_tpu.ops.topk import knn_search_tiled

#: masked index value for beyond-radius slots (sklearn-style -1; the
#: int32-max sentinel of ops.topk marks *padding*, a different thing)
SENTINEL_IDX = -1

#: ``jax.named_scope`` of the range completion's device program
#: (:func:`within_words` then :func:`compact_words`), beside the
#: certified program's scopes (ops.pallas_knn)
SCOPE_RANGE_COMPLETE = "knn.range_complete"
#: queries one completion launch holds: the truncated queries of a call
#: are gathered into sub-batches of this many rows (one compiled shape)
RANGE_SUB_BATCH = 64
#: rows one word of :func:`within_words` marks
WORD_BITS = 32


def _dispatch_metric(metric: str) -> str:
    """Canonical dispatch name for a radius-API metric.  ``'cityblock'``
    is accepted by :func:`radius_threshold` (eager validation) but not by
    ops.distance.pairwise_distance, so it is normalized to ``'l1'`` HERE,
    before any dispatch — validation and execution must agree on the
    metric vocabulary (ADVICE r5)."""
    m = metric.lower()
    return "l1" if m == "cityblock" else m


def radius_threshold(radius: float, metric: str) -> float:
    """The ranking-space threshold for a user-units ``radius``."""
    m = metric.lower()
    if m in ("l2", "sql2", "euclidean"):
        if radius < 0:
            raise ValueError(f"radius must be >= 0, got {radius}")
        return float(radius) ** 2  # ranking space is squared L2
    if m in ("l1", "manhattan", "cityblock", "cosine"):
        if radius < 0:
            raise ValueError(f"radius must be >= 0, got {radius}")
        return float(radius)
    raise ValueError(
        f"radius semantics undefined for metric {metric!r} "
        "(dot similarities are unbounded)"
    )


@functools.partial(
    jax.jit, static_argnames=("metric", "tile", "compute_dtype")
)
def count_within(
    db: jax.Array,
    queries: jax.Array,
    threshold,
    metric: str = "l2",
    *,
    tile: int = 131072,
    compute_dtype=None,
    n_valid=None,
) -> jax.Array:
    """Per query, how many db rows lie at ranking-space distance
    ``<= threshold`` — one tiled matmul-bound pass, no selection.

    [Q] int32.  ``threshold`` is scalar or [Q] (already in ranking
    space — callers convert via :func:`radius_threshold`).  Same
    distance arithmetic as the selection path, so the count and the
    mask agree including float32 boundary behavior.  ``n_valid`` masks
    trailing padding rows (the db-shard contract of ops.topk).

    Deliberately separate from ops.certified.count_below despite the
    similar tiling: count_below's arithmetic (expanded-square minus
    query norm, strict ``<``) is PINNED by the certificate's f32 error
    model (certification_tolerance) and must not drift, while this pass
    is metric-general with ``<=`` and follows pairwise_distance."""
    metric = _dispatch_metric(metric)
    n = db.shape[0]
    tile = min(tile, n)
    limit = n if n_valid is None else jnp.minimum(n, n_valid)
    n_tiles = -(-n // tile)
    padded = n_tiles * tile
    if padded != n:
        db = jnp.pad(db, ((0, padded - n), (0, 0)))
    tiles = db.reshape(n_tiles, tile, db.shape[-1])
    thr = jnp.asarray(threshold, jnp.float32)
    thr_col = thr[..., None] if thr.ndim else thr

    def step(acc, args):
        tile_idx, t = args
        d = pairwise_distance(queries, t, metric, compute_dtype=compute_dtype)
        gidx = tile_idx * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        ok = (d <= thr_col) & (gidx < limit)
        return acc + jnp.sum(ok, axis=-1, dtype=jnp.int32), None

    counts, _ = lax.scan(
        step,
        jnp.zeros(queries.shape[0], jnp.int32),
        (jnp.arange(n_tiles, dtype=jnp.int32), tiles),
    )
    return counts


def check_truncation(counts, max_neighbors: int, action_hint: str) -> None:
    """Raise when any query's in-radius set exceeds ``max_neighbors`` —
    the ONE home of the strict-mode truncation contract, shared by the
    radius estimators and the graph exports."""
    counts = np.asarray(counts)
    over = counts > max_neighbors
    if over.any():
        raise ValueError(
            f"{int(over.sum())} queries have more than "
            f"max_neighbors={max_neighbors} in-radius neighbors "
            f"(max {int(counts.max())}); raise max_neighbors, shrink the "
            f"radius, or pass strict=False to {action_hint}"
        )


def radius_search(
    queries: jax.Array,
    db: jax.Array,
    radius: float,
    *,
    max_neighbors: int,
    metric: str = "l2",
    train_tile: Optional[int] = None,
    compute_dtype=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All neighbors within ``radius``, up to ``max_neighbors`` per query.

    Returns ``(dists [Q, M], idx [Q, M], counts [Q])`` with
    ``M = min(max_neighbors, n_db)``: the nearest-M prefix masked to the
    radius (beyond-radius slots: ``+inf`` / ``SENTINEL_IDX``), plus the
    EXACT within-radius count per query.  ``counts[q] > M`` means query
    ``q``'s result is truncated to its M nearest — detectable, never
    silent.  Distances are in ranking space (squared for the l2 family;
    callers wanting Euclidean values apply ops.distance.metric_values).
    """
    thr = radius_threshold(radius, metric)  # eager validation (aliases ok)
    metric = _dispatch_metric(metric)  # execution vocabulary
    m = min(int(max_neighbors), db.shape[0])
    if m < 1:
        raise ValueError(f"max_neighbors must be >= 1, got {max_neighbors}")
    d, i = knn_search_tiled(
        queries, db, m, metric,
        train_tile=train_tile, compute_dtype=compute_dtype,
    )
    counts = count_within(
        db, queries, thr, metric,
        tile=min(train_tile or 131072, db.shape[0]),
        compute_dtype=compute_dtype,
    )
    within = d <= thr
    return (
        jnp.where(within, d, jnp.inf),
        jnp.where(within, i, SENTINEL_IDX),
        counts,
    )


# --- range completion: every row at or under a threshold, unbounded --------
def range_width(k: int) -> int:
    """The width (marked words a query) the completion collects at, read
    off the placement's ``k``: sixteen times the first power of two at
    or over ``2 k`` (a query reaches completion only with more than
    ``k`` results), 4,096 at k = 100.  ONE width, so one program: a
    wider collect costs little (the compaction sorts every word
    whatever the width), and a query that marks more words than this
    is finished on the host."""
    return 16 << max(0, (2 * int(k) - 1).bit_length())


def words_geometry(n_rows: int, tile: int) -> Tuple[int, int, int]:
    """``(rows, tile, n_tiles)`` of :func:`within_words` over a shard of
    ``n_rows`` rows: the rows as the pass sees them (padded up to one
    word where fewer), the row tile (a whole number of words, at most
    the rows) and how many tiles.  The last tile is the last ``tile``
    rows, overlapping its neighbour where the rows are no multiple of
    the tile; an overlapped row is marked once (by the tile that owns
    it)."""
    rows = max(int(n_rows), WORD_BITS)
    tile = max(WORD_BITS, min(int(tile), rows) // WORD_BITS * WORD_BITS)
    return rows, tile, -(-rows // tile)


def within_words(
    db: jax.Array,
    queries: jax.Array,
    thresholds: jax.Array,
    *,
    tile: int = 131072,
    n_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """One pass over ``db`` that, per query, counts the rows at squared
    L2 distance ``<= thresholds`` and marks them: ``(counts [Q] int32,
    words [Q, n_tiles * tile // 32] uint32)``.

    The distance arithmetic is ops.certified.count_below's (float32
    expanded square, ``Precision.HIGHEST``), whose error
    ``certification_tolerance`` bounds: a threshold of ``r + tol`` marks
    a superset of ``{t: d64(q, t) <= r}``.  No padded copy of the rows
    is made: tile ``i`` is the rows from ``min(i * tile, rows - tile)``
    (:func:`words_geometry`).  Bit ``b`` of word ``w`` of tile ``i``
    stands for row ``start_i + b * (tile // 32) + w``: a layout whose
    minor dimension stays ``tile // 32`` wide, not 32
    (:func:`decode_words` is the inverse).  Rows at index >= ``n_valid``
    (may be traced) are padding and never marked."""
    n = db.shape[0]
    rows, tile, n_tiles = words_geometry(n, tile)
    if rows != n:
        db = jnp.pad(db, ((0, rows - n), (0, 0)))
    limit = n if n_valid is None else jnp.minimum(n, n_valid)
    per_word = tile // WORD_BITS
    q32 = queries.astype(jnp.float32)
    q_norm = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    thr = thresholds[:, None].astype(jnp.float32)
    shifts = lax.broadcasted_iota(jnp.uint32, (1, WORD_BITS, 1), 1)

    def step(acc, tile_idx):
        start = jnp.minimum(tile_idx * tile, rows - tile)
        t32 = lax.dynamic_slice_in_dim(db, start, tile).astype(jnp.float32)
        t_norm = jnp.sum(t32 * t32, axis=-1)[None, :]
        qt = lax.dot_general(
            q32, t32, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )
        d = jnp.maximum(q_norm + t_norm - 2.0 * qt, 0.0)
        col = start + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        hit = (d <= thr) & (col >= tile_idx * tile) & (col < limit)
        bits = hit.reshape(-1, WORD_BITS, per_word).astype(jnp.uint32)
        # distinct bits: the sum is the bitwise or
        words = jnp.sum(bits << shifts, axis=1, dtype=jnp.uint32)
        return acc + jnp.sum(hit, axis=-1, dtype=jnp.int32), words

    with jax.named_scope(SCOPE_RANGE_COMPLETE):
        counts, words = lax.scan(
            step, jnp.zeros(queries.shape[0], jnp.int32),
            jnp.arange(n_tiles, dtype=jnp.int32))
        # [n_tiles, Q, per_word] -> [Q, n_tiles * per_word]
        words = jnp.moveaxis(words, 0, 1).reshape(queries.shape[0], -1)
    return counts, words


def compact_words(words: jax.Array, width: int) -> jax.Array:
    """The non-zero words of :func:`within_words` compacted to ``width``
    a query: ``[Q, 2, min(width, columns)]`` uint32, the word's column
    and its bits.  Every array here is uint32 like the words, a type
    the certified call's other programs hold nowhere but in the first
    pass's packed certificate bits: a device trace tells the
    completion's operations by it (an HLO line carries no name a
    program could choose).  A query marks at most as many words
    as rows, so a ``width`` at or over its count loses nothing; slots
    past a query's last non-zero word hold zero bits."""
    width = min(int(width), words.shape[1])
    with jax.named_scope(SCOPE_RANGE_COMPLETE):
        # the words themselves are the keys: any marked word is over 0
        bits, pos = lax.top_k(words, width)
        return jnp.stack([pos.astype(jnp.uint32), bits], axis=1)


def decode_words(compact: np.ndarray, n_rows: int, tile: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Host inverse of :func:`compact_words` over one shard of
    ``n_rows`` rows: ``(query positions, local row indices)`` of every
    marked row, flat, in no particular order."""
    rows, tile, _ = words_geometry(n_rows, tile)
    per_word = tile // WORD_BITS
    pos = compact[:, 0, :].astype(np.int64)
    bits = compact[:, 1, :]
    qi, slot = np.nonzero(bits)
    word, col = bits[qi, slot], pos[qi, slot]
    # marked rows are sparse (most words hold one): peel the lowest set
    # bit off every word still non-zero, as often as the fullest word
    # has bits
    hits, bs, at = [], [], np.arange(word.size)
    while word.size:
        low = word & (~word + np.uint32(1))
        hits.append(at)
        bs.append(np.log2(low).astype(np.int64))  # exact: a power of two
        word = word ^ low
        left = word != 0
        word, at = word[left], at[left]
    hit = np.concatenate(hits) if hits else np.empty(0, np.int64)
    b = np.concatenate(bs) if bs else np.empty(0, np.int64)
    tile_idx, w = np.divmod(col[hit], per_word)
    start = np.minimum(tile_idx * tile, rows - tile)
    return qi[hit], start + b * per_word + w
