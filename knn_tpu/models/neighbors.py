"""Unsupervised nearest-neighbor queries + sparse graph exports.

The estimator surface users of sklearn-style libraries reach for first:
``fit(X)`` then ``kneighbors`` / ``radius_neighbors`` with no labels,
plus CSR adjacency exports (``kneighbors_graph`` /
``radius_neighbors_graph``).  Built on the same tiled/sharded cores as
the classifier (ops.topk, ops.radius, parallel.ShardedKNN); graphs are
returned as raw CSR triples ``(data, indices, indptr)`` so the library
keeps zero scipy dependency — ``scipy.sparse.csr_matrix(triple,
shape=(n_queries, n_fit_rows))`` reconstructs the standard object when
scipy is around.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from knn_tpu.ops.radius import SENTINEL_IDX, radius_search
from knn_tpu.ops.topk import knn_search_tiled


class NearestNeighbors:
    """fit/query container for neighbor searches.

    Args:
      k: default neighbor count for :meth:`kneighbors`.
      radius: default radius for :meth:`radius_neighbors` (metric units,
        ops.radius.radius_threshold).
      max_neighbors: bounded width of radius results (TPU static shapes;
        ops.radius truncation contract).
      metric / train_tile / compute_dtype: as KNNClassifier.
      mesh: place the database across a device mesh once
        (parallel.ShardedKNN); queries then run the sharded programs.
    """

    def __init__(
        self,
        k: int = 5,
        *,
        radius: Optional[float] = None,
        max_neighbors: int = 128,
        metric: str = "l2",
        train_tile: Optional[int] = None,
        compute_dtype=None,
        mesh=None,
        merge: str = "allgather",
    ):
        self.k = k
        self.radius = radius
        self.max_neighbors = max_neighbors
        self.metric = metric
        self.train_tile = train_tile
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.merge = merge
        self._fit_X = None
        self._program = None

    @property
    def n_samples_fit(self) -> int:
        self._require_fit()
        return int(self._fit_X.shape[0])

    def fit(self, X) -> "NearestNeighbors":
        # host-resident: meshed fits hand the array to ShardedKNN (which
        # streams shards to their devices); a jnp.asarray here would
        # first commit a SECOND full copy to device 0
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2:
            raise ValueError(f"X must be 2-D, got {X.shape}")
        if self.k > X.shape[0]:
            raise ValueError(f"k={self.k} > n_samples={X.shape[0]}")
        self._fit_X = X
        self._program = None
        if self.mesh is not None:
            from knn_tpu.parallel.sharded import ShardedKNN

            self._program = ShardedKNN(
                X, mesh=self.mesh, k=self.k, metric=self.metric,
                merge=self.merge, train_tile=self.train_tile,
                compute_dtype=self.compute_dtype,
            )
        return self

    def _require_fit(self):
        if self._fit_X is None:
            raise RuntimeError("call fit() before querying")

    def _prep(self, Q):
        Q = jnp.asarray(Q)
        if Q.ndim != 2 or Q.shape[1] != self._fit_X.shape[1]:
            raise ValueError(f"queries {Q.shape} vs fit {self._fit_X.shape}")
        return Q

    # -- queries -----------------------------------------------------------
    def kneighbors(self, Q, k: Optional[int] = None, *,
                   return_sqrt: bool = False):
        """(dists [Q, k], idx [Q, k]); squared l2 values unless
        ``return_sqrt`` (ops.topk lexicographic semantics)."""
        self._require_fit()
        k = self.k if k is None else k
        Q = self._prep(Q)
        if self._program is not None:
            d, i = self._program.search(Q, k=k, return_sqrt=return_sqrt)
            return d, i
        d, i = knn_search_tiled(
            Q, self._fit_X, k, self.metric,
            train_tile=self.train_tile, compute_dtype=self.compute_dtype,
        )
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d = metric_values(d, self.metric)
        return d, i

    def radius_neighbors(self, Q, radius: Optional[float] = None):
        """(dists [Q, M], idx [Q, M], counts [Q]) — ops.radius bounded
        formulation; ``counts > max_neighbors`` flags truncation."""
        self._require_fit()
        radius = self.radius if radius is None else radius
        if radius is None:
            raise ValueError("no radius given (constructor or call)")
        Q = self._prep(Q)
        if self._program is not None:
            return self._program.radius_search(
                np.asarray(Q, np.float32), radius,
                max_neighbors=self.max_neighbors)
        return radius_search(
            Q, self._fit_X, radius, max_neighbors=self.max_neighbors,
            metric=self.metric, train_tile=self.train_tile,
            compute_dtype=self.compute_dtype,
        )

    # -- graphs ------------------------------------------------------------
    def kneighbors_graph(
        self, Q=None, k: Optional[int] = None, *, mode: str = "connectivity",
        include_self: bool = True,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """CSR triple ``(data, indices, indptr)`` of the k-NN adjacency
        [n_queries, n_samples_fit].  ``mode='connectivity'`` gives 1.0
        entries, ``'distance'`` the ranking-space distances.  ``Q=None``
        builds the fit-set self-graph: every fit row a query of the fit
        set.  As it always did, each row's neighbours then INCLUDE the
        row itself (an exact search finds it first, at distance ~0, and
        one of the k places is spent on it).

        ``include_self=False`` (``Q=None`` only; an estimator fitted on
        a ``mesh``, squared L2) builds the graph WITHOUT it: row i's
        entries are the first k rows j != i in lexicographic (float64
        squared-L2 distance, j) order, exact and certified
        (knn_tpu.join.knn_self_join: the placed rows queried against
        themselves in one pipelined bulk call, the row taken out by ID
        before the select).  An exact copy of row i stays, at distance
        0: dropping "whatever lies at distance 0" downstream would take
        the copies out with it, and dropping column j == i from an
        include-self graph leaves k - 1 neighbours, or k with the wrong
        last one where k copies come before i.  ``k`` is the
        estimator's own there."""
        self._require_fit()
        if mode not in ("connectivity", "distance"):
            raise ValueError(f"unknown mode {mode!r}")
        if not include_self:
            if Q is not None or self._program is None or k not in (
                    None, self.k):
                raise ValueError(
                    "include_self=False builds the fit set's own graph "
                    "with the placed program's bulk self-join: Q must be "
                    "None, k the estimator's, and the estimator fitted on "
                    "a mesh")
            from knn_tpu.join import knn_self_join

            d, i, _ = knn_self_join(self._program)
        else:
            d, i = self.kneighbors(self._fit_X if Q is None else Q, k)
        d, i = np.asarray(d), np.asarray(i)
        n_q, kk = i.shape
        data = (np.ones(n_q * kk, np.float32) if mode == "connectivity"
                else d.ravel().astype(np.float32))
        return data, i.ravel().astype(np.int64), np.arange(
            0, (n_q + 1) * kk, kk, dtype=np.int64)

    def radius_neighbors_graph(
        self, Q=None, radius: Optional[float] = None, *,
        mode: str = "connectivity", strict: bool = True,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """CSR triple of the within-radius adjacency.  Row widths vary
        (true CSR); ``strict=True`` raises when any query's in-radius
        set exceeds ``max_neighbors`` (the graph would silently lose
        edges), ``strict=False`` keeps the nearest ``max_neighbors``."""
        self._require_fit()
        if mode not in ("connectivity", "distance"):
            raise ValueError(f"unknown mode {mode!r}")
        Q = self._fit_X if Q is None else Q
        from knn_tpu.ops.radius import check_truncation

        d, i, counts = self.radius_neighbors(Q, radius)
        d, i, counts = np.asarray(d), np.asarray(i), np.asarray(counts)
        if strict:
            check_truncation(counts, self.max_neighbors,
                             "keep the nearest edges only")
        within = i != SENTINEL_IDX
        row_counts = within.sum(axis=1)
        indptr = np.zeros(i.shape[0] + 1, np.int64)
        np.cumsum(row_counts, out=indptr[1:])
        indices = i[within].astype(np.int64)
        data = (np.ones(indices.shape[0], np.float32)
                if mode == "connectivity"
                else d[within].astype(np.float32))
        return data, indices, indptr
