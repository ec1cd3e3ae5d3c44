"""KNN classifier: the reference's whole pipeline (distance -> sort -> vote,
knn_mpi.cpp:308-393) as a fit/predict estimator.

TPU-first design: predict is a single jitted program — tiled distance
matmul, streaming top-k, vectorized reference-semantics vote — compiled once
per (batch_shape, k, metric) and reused across query batches.  Queries are
processed in fixed-size batches (padding the tail) so XLA sees static shapes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from knn_tpu.ops.normalize import minmax_apply, minmax_stats
from knn_tpu.ops.topk import knn_search_tiled
from knn_tpu.ops.vote import majority_vote


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "num_classes", "train_tile", "compute_dtype")
)
def knn_predict(
    train: jax.Array,
    train_labels: jax.Array,
    queries: jax.Array,
    *,
    k: int,
    num_classes: int,
    metric: str = "l2",
    train_tile: Optional[int] = None,
    compute_dtype=None,
) -> jax.Array:
    """Functional core: predicted labels [Q] for one query batch.

    The fused equivalent of the reference's per-query loop
    (knn_mpi.cpp:315-338): distance fill -> top-k select -> majority vote.
    """
    _, idx = knn_search_tiled(
        queries, train, k, metric, train_tile=train_tile, compute_dtype=compute_dtype
    )
    return majority_vote(train_labels[idx], num_classes)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "train_tile", "compute_dtype")
)
def knn_kneighbors(
    train: jax.Array,
    queries: jax.Array,
    *,
    k: int,
    metric: str = "l2",
    train_tile: Optional[int] = None,
    compute_dtype=None,
) -> Tuple[jax.Array, jax.Array]:
    """(distances, indices) of the k nearest train rows per query."""
    return knn_search_tiled(
        queries, train, k, metric, train_tile=train_tile, compute_dtype=compute_dtype
    )


class KNNClassifier:
    """Brute-force KNN classifier with the reference's semantics.

    Args mirror the reference's compile-time config block
    (knn_mpi.cpp:108-119) but are runtime parameters:
      k: neighbors (ref ``K`` :109).
      metric: 'l2' | 'l1' | 'cosine' | 'dot' (ref ``Euclidean_distance`` :114).
      num_classes: ref ``class_cnt`` :113; inferred from labels if None.
      normalize: min-max normalize train at fit and queries at predict using
        **train-only** stats.  (The reference's transductive train∪test∪val
        normalization lives in knn_tpu.pipeline, which reproduces the full
        job; an estimator must not peek at queries at fit time.)
      train_tile: stream the database in tiles of this many rows (None =
        materialize the full |Q|x|T| distance matrix per batch).
      batch_size: queries per compiled step (tail batch is padded).
      compute_dtype: matmul input dtype, e.g. jnp.bfloat16 for MXU speed.
      mesh: a ``jax.sharding.Mesh`` from :func:`knn_tpu.parallel.make_mesh`
        — fit places the database across it once and every predict/
        kneighbors runs the sharded SPMD program (parallel.ShardedKNN).
        None = single-device jitted path (identical results).
      merge: db-axis merge strategy when meshed ('allgather' | 'ring').
      mode: 'exact' | 'certified' (meshed, l2 or cosine).  Certified
        predictions are certified CLASSES
        (ShardedKNN.predict_certified): with ``vote='majority'`` the
        exact ranked neighbours (float64 lexicographic order of the rows
        as given) and the reference's first-to-reach vote over them;
        with ``vote='softmax'`` (cosine) the class of the largest
        float64 total of ``exp(similarity / temperature)`` over the exact
        k nearest rows, which ``selector='pallas'`` votes and certifies
        on the device (a certificate over the k-th / (k+1)-th boundary
        and the class margins; the host re-votes in float64 only the
        queries it flags).  ``kneighbors`` is search_certified's.
      selector: coarse selector for certified mode ('approx' | 'pallas' |
        'exact').  The pallas selector returns f32-accurate kneighbors
        distances (see ShardedKNN.search_certified); the others float64.
      vote, temperature: certified mode's vote ('majority' | 'softmax')
        and the softmax vote's temperature; passed through to
        ShardedKNN.predict_certified.
    """

    def __init__(
        self,
        k: int = 5,
        metric: str = "l2",
        num_classes: Optional[int] = None,
        normalize: bool = False,
        train_tile: Optional[int] = None,
        batch_size: Optional[int] = None,
        compute_dtype=None,
        mesh=None,
        merge: str = "allgather",
        mode: str = "exact",
        selector: str = "approx",
        vote: str = "majority",
        temperature: Optional[float] = None,
    ):
        if mode not in ("exact", "certified"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode != "certified" and (vote != "majority"
                                    or temperature is not None):
            raise ValueError(
                "vote and temperature belong to mode='certified' "
                "(ShardedKNN.predict_certified); the exact modes run the "
                "reference's majority vote")
        if mode == "certified" and mesh is None:
            raise ValueError("mode='certified' needs a mesh (make_mesh(1, 1) is fine)")
        if mode == "certified" and metric not in ("l2", "sql2", "euclidean",
                                                  "cosine"):
            raise ValueError(
                "mode='certified' supports the l2 and cosine metrics only")
        self.k = k
        self.metric = metric
        self.num_classes = num_classes
        self.normalize = normalize
        self.train_tile = train_tile
        self.batch_size = batch_size
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.merge = merge
        self.mode = mode
        self.selector = selector
        self.vote = vote
        self.temperature = temperature
        self._train = None
        self._labels = None
        self._mins = None
        self._maxs = None
        self._program = None

    # -- fit ---------------------------------------------------------------
    def fit(self, X, y) -> "KNNClassifier":
        X = jnp.asarray(X)
        y = jnp.asarray(y, dtype=jnp.int32)
        if X.ndim != 2 or y.ndim != 1 or X.shape[0] != y.shape[0]:
            raise ValueError(f"bad shapes: X {X.shape}, y {y.shape}")
        if self.k > X.shape[0]:
            raise ValueError(f"k={self.k} > n_train={X.shape[0]}")
        if self.num_classes is None:
            self.num_classes = int(jnp.max(y)) + 1
        if self.normalize:
            self._mins, self._maxs = minmax_stats([X])
            X = minmax_apply(X, self._mins, self._maxs)
        self._train = X
        self._labels = y
        self._program = None  # a refit must never serve the old placement
        if self.mesh is not None:
            from knn_tpu.parallel.sharded import ShardedKNN

            # placed once; every predict/kneighbors reuses the placement
            self._program = ShardedKNN(
                np.asarray(X), mesh=self.mesh, k=self.k, metric=self.metric,
                merge=self.merge, train_tile=self.train_tile,
                compute_dtype=self.compute_dtype,
                labels=np.asarray(y), num_classes=self.num_classes,
            )
        return self

    def _require_fit(self):
        if self._train is None:
            raise RuntimeError("call fit() before predict()/kneighbors()")

    def _prep_queries(self, Q) -> jax.Array:
        Q = jnp.asarray(Q)
        if Q.ndim != 2 or Q.shape[1] != self._train.shape[1]:
            raise ValueError(f"queries {Q.shape} vs train {self._train.shape}")
        if self.normalize:
            Q = minmax_apply(Q, self._mins, self._maxs)
        return Q

    def _batched(self, Q, fn, n_out: int):
        """Run fn over fixed-size query batches, padding the tail — the
        static-shape replacement for the reference's divisibility aborts
        (knn_mpi.cpp:127-129)."""
        n = Q.shape[0]
        bs = self.batch_size or n
        outs = []
        for start in range(0, n, bs):
            chunk = Q[start : start + bs]
            if chunk.shape[0] < bs:
                chunk = jnp.pad(chunk, ((0, bs - chunk.shape[0]), (0, 0)))
            res = fn(chunk)
            res = res if isinstance(res, tuple) else (res,)
            outs.append(tuple(r[: min(bs, n - start)] for r in res))
        if len(outs) == 1:
            cat = outs[0]
        else:
            cat = tuple(
                jnp.concatenate([o[i] for o in outs], axis=0)
                for i in range(n_out)
            )
        return cat if n_out > 1 else cat[0]

    # -- inference ---------------------------------------------------------
    def predict(self, Q) -> jax.Array:
        """Predicted labels [Q] — the reference's KNN phase + vote."""
        self._require_fit()
        Q = self._prep_queries(Q)
        if self._program is not None:
            if self.mode == "certified":
                # (labels, stats), or (classes [Q, 1], totals, stats)
                labels, *_ = self._program.predict_certified(
                    np.asarray(Q), vote=self.vote,
                    temperature=self.temperature, selector=self.selector,
                    batch_size=self.batch_size,
                )
                return jnp.asarray(labels).reshape(-1)
            return self._batched(Q, self._program.predict, 1)
        return self._batched(
            Q,
            lambda c: knn_predict(
                self._train,
                self._labels,
                c,
                k=self.k,
                num_classes=self.num_classes,
                metric=self.metric,
                train_tile=self.train_tile,
                compute_dtype=self.compute_dtype,
            ),
            1,
        )

    def kneighbors(self, Q, *, return_sqrt: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
        """(distances, indices) of the k nearest neighbors per query.

        L2-family distances are SQUARED by default (the reference's
        monotone sqrt, knn_mpi.cpp:48, is dropped for ranking);
        ``return_sqrt=True`` returns true Euclidean values matching
        ``Euclidean_D`` / sklearn."""
        self._require_fit()
        Q = self._prep_queries(Q)
        if self._program is not None:
            if self.mode == "certified":
                d, i, _ = self._program.search_certified(
                    np.asarray(Q), selector=self.selector,
                    batch_size=self.batch_size, return_sqrt=return_sqrt,
                )
                return jnp.asarray(d), jnp.asarray(i)
            d, i = self._batched(Q, self._program.search, 2)
        else:
            d, i = self._batched(
                Q,
                lambda c: knn_kneighbors(
                    self._train,
                    c,
                    k=self.k,
                    metric=self.metric,
                    train_tile=self.train_tile,
                    compute_dtype=self.compute_dtype,
                ),
                2,
            )
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d = metric_values(d, self.metric)
        return d, i

    def score(self, Q, y) -> float:
        """Accuracy — ``acc_calc`` (knn_mpi.cpp:69-84)."""
        pred = np.asarray(self.predict(Q))
        return float(np.mean(pred == np.asarray(y)))
