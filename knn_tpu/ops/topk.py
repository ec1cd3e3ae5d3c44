"""Top-k neighbor selection: exact ``lax.top_k`` plus a tiled streaming merge.

The reference selects neighbors by fully sorting all N_train candidate
distances per query with ``std::sort`` (knn_mpi.cpp:323,366) — O(N log N)
for a top-K=50 select.  The TPU-native replacement is ``lax.top_k`` over the
distance matrix, and for databases too large to materialize a full |Q|x|T|
distance matrix in HBM, a ``lax.scan`` over train tiles that carries a
running top-k (the TPU-KNN-paper-style streaming merge; SURVEY.md §7 step 5).

The Pallas coarse path has its own in-kernel alternative to the scan
merge here: ``ops.pallas_knn``'s ``kernel="streaming"`` carries the
running per-bin candidate list across train tiles inside ONE kernel
launch (double-buffered HBM->VMEM streaming) instead of round-tripping
per-tile partials to this module's merge — the lexicographic
(distance, index) contract below is shared by both.

Tie-breaking: the reference's ``std::sort`` with ``Comp`` (knn_mpi.cpp:24-31)
leaves the order of equal distances unspecified.  We define it: ties go to
the **lower train index** — i.e. the k-nearest set is the lexicographic
smallest k pairs ``(distance, index)``.  ``lax.top_k`` over an index-ordered
distance row produces exactly this, and :func:`merge_topk` preserves it by
merging with a two-key ``lax.sort`` over ``(distance, index)``.  Because the
lexicographic merge is associative and commutative, every execution
strategy — single-shot, tiled scan, all-gather merge, ring merge across a
device mesh (parallel.sharded) — returns the identical result.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from knn_tpu.ops.distance import pairwise_distance


def topk_smallest(dists: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """(values, indices) of the k smallest entries along the last axis,
    sorted ascending; ties broken toward the lower index."""
    neg, idx = lax.top_k(-dists, k)
    return -neg, idx


def topk_pairs(d: jax.Array, i: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Lexicographic-smallest k ``(distance, index)`` pairs along the last
    axis, sorted ascending.  A two-key ``lax.sort`` — value ties resolve to
    the lower index by construction, not by input position, so the result
    is independent of candidate order."""
    sd, si = lax.sort((d, i), dimension=-1, num_keys=2)
    return sd[..., :k], si[..., :k]


def merge_topk(
    best_d: jax.Array,
    best_i: jax.Array,
    new_d: jax.Array,
    new_i: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Merge a running top-k with new candidates along the last axis.

    Inputs are [..., k] and [..., m]; output is the combined lexicographic
    top-k.  Associative and commutative (see module docstring), so tiled,
    ring, and all-gather merges all agree bitwise.
    """
    d = jnp.concatenate([best_d, new_d], axis=-1)
    i = jnp.concatenate([best_i, new_i], axis=-1)
    return topk_pairs(d, i, k)


def knn_search(
    queries: jax.Array,
    train: jax.Array,
    k: int,
    metric: str = "l2",
    *,
    compute_dtype=None,
    n_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact KNN with the full distance matrix materialized: [Q, k] dists+idx.

    Use when |Q|x|T| fits in HBM; otherwise :func:`knn_search_tiled`.
    ``n_valid`` (may be traced): train rows at index >= n_valid are padding —
    their distance is forced to +inf *before* selection so they can never
    displace a real neighbor (the db-shard padding contract of
    parallel.sharded).
    """
    d = pairwise_distance(queries, train, metric, compute_dtype=compute_dtype)
    if n_valid is not None:
        cols = lax.broadcasted_iota(jnp.int32, (1, train.shape[0]), 1)
        d = jnp.where(cols < n_valid, d, jnp.inf)
    return topk_smallest(d, k)


def knn_search_tiled(
    queries: jax.Array,
    train: jax.Array,
    k: int,
    metric: str = "l2",
    *,
    train_tile: Optional[int] = None,
    compute_dtype=None,
    n_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact KNN streaming over train tiles with a running top-k merge.

    HBM cost is O(Q*train_tile) per step instead of O(Q*T).  The rows are
    scanned WHERE THEY LIE: every step reads one ``[train_tile, D]`` window
    of ``train`` as given, and a T not divisible by ``train_tile``
    (the reference's divisibility ``MPI_Abort``, knn_mpi.cpp:127-129) ends
    in a whole tile that stops at the last row: its start is clamped to
    ``T - train_tile``, and the rows it shares with the tile before, already
    scored there, are masked out by their global index before the tile's
    top-k.  Padding the operand to whole tiles instead is wrong on a placed
    corpus: the pad is a copy of ALL rows, read and written in every call
    (12.8 ms a batch of the certified repair's re-select at 1M x 1,024
    float32 on a v5e, and the largest temporary of any loaded program),
    to append less than one tile of zeros that the mask then discards.

    ``n_valid`` additionally marks trailing train rows as padding (see
    :func:`knn_search`).  A masked row comes out as ``(+inf, int32 max)``,
    never under a real row's index.  Every finite ``(distance, index)``
    pair equals :func:`knn_search`'s, lower-index tie-breaks included.
    """
    n_train = train.shape[0]
    if k > n_train:
        raise ValueError(f"k={k} > n_train={n_train}")
    if train_tile is None or train_tile >= n_train:
        return knn_search(
            queries, train, k, metric, compute_dtype=compute_dtype, n_valid=n_valid
        )
    limit = n_train if n_valid is None else jnp.minimum(n_train, n_valid)

    n_tiles = -(-n_train // train_tile)
    ragged = n_train % train_tile != 0
    sentinel = jnp.iinfo(jnp.int32).max

    n_q = queries.shape[0]
    init_d = jnp.full((n_q, k), jnp.inf, dtype=jnp.float32)
    init_i = jnp.full((n_q, k), sentinel, dtype=jnp.int32)

    def live(gidx, first):
        """Rows this step scores: not padding, and (in the clamped last
        tile) not scored by the step before."""
        ok = gidx < limit
        return ok & (gidx >= first) if ragged else ok

    def step(carry, tile_idx):
        best_d, best_i = carry
        first = tile_idx * train_tile  # the first row this step has to score
        start = jnp.minimum(first, n_train - train_tile) if ragged else first
        tile = lax.dynamic_slice_in_dim(train, start, train_tile, axis=0)
        d = pairwise_distance(queries, tile, metric, compute_dtype=compute_dtype)
        gidx = start + lax.broadcasted_iota(jnp.int32, (1, train_tile), 1)
        d = jnp.where(live(gidx, first), d, jnp.inf)
        if train_tile > k:
            # Reduce the tile to its local top-k *first* (exact: every
            # global top-k member inside this tile is also in the tile's
            # top-k), so the lexicographic merge sorts 2k candidates, not
            # k + train_tile.
            d, ti = topk_smallest(d, k)
            gidx = start + ti  # ti are tile-local columns
        gidx = jnp.where(live(gidx, first), gidx, sentinel)
        return merge_topk(best_d, best_i, d, jnp.broadcast_to(gidx, d.shape), k), None

    (best_d, best_i), _ = lax.scan(
        step, (init_d, init_i), jnp.arange(n_tiles, dtype=jnp.int32)
    )
    return best_d, best_i


def knn_search_approx(
    queries: jax.Array,
    train: jax.Array,
    k: int,
    *,
    recall_target: float = 0.95,
    compute_dtype=None,
    n_valid=None,
) -> Tuple[jax.Array, jax.Array]:
    """Approximate L2 KNN via ``lax.approx_max_k`` — the recall-vs-speed knob
    (SURVEY.md §7 step 6).  L2 only: uses the -||t||^2 + 2 q.t^T MIPS score
    so approx_max_k's aggregate-to-topk path applies.  ``n_valid`` (may be
    traced) masks trailing padding rows out of the candidate set."""
    from knn_tpu.ops.distance import _dot

    t32 = train.astype(jnp.float32)
    half_t_norm = 0.5 * jnp.sum(t32 * t32, axis=-1)[None, :]
    if compute_dtype is None:
        compute_dtype = queries.dtype
    # _dot requests HIGHEST precision for f32 inputs — without it the TPU
    # decomposes the f32 matmul into bf16 passes, silently costing distance
    # bits and raising the certified-path fallback rate.
    qt = _dot(queries, train, compute_dtype)
    score = qt - half_t_norm  # argmax_t score == argmin_t ||q-t||^2
    if n_valid is not None:
        cols = lax.broadcasted_iota(jnp.int32, (1, train.shape[0]), 1)
        score = jnp.where(cols < n_valid, score, -jnp.inf)
    neg_half, idx = lax.approx_max_k(score, k, recall_target=recall_target)
    q32 = queries.astype(jnp.float32)
    q_norm = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    return jnp.maximum(q_norm - 2.0 * neg_half, 0.0), idx
