"""The plain reference for squared-L2 top-k at ANY k, and the comparison
that decides ``correct`` there: what ``reference.py`` is for k = 100,
written for a k of a thousand and more (kNN-LM's 1,024 neighbours a
query) and on its own.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made; of the benchmark's own files it
takes ``reference.py``'s comparison and its bfloat16 rounding.  :func:`oracle_topk` is brute force in float64 over the
float32 rows and queries as given, a block of rows at a time: every
block's expanded-form distances, the first ``k + SLACK`` in (distance,
index) order of the block and of what was kept so far kept on, and at
the end those are re-scored by
direct difference one row at a time and ordered by (distance, index):
the exact lexicographic top-k.  :func:`lowprec_topk` is the same search
in a lower precision, the CONTROL that the comparison has to fail
(``control_topk.py``); no benchmark run calls it.  :func:`compare` gives
the numbers a configuration's ``limits`` name.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import reference
from reference import _round_bf16

#: rows a block: 16,384 x 1,024 float64 values are 128 MB
BLOCK = 16_384
#: candidates kept beyond k until the exact re-score: a float64
#: expanded-form distance is off by some 1e-16 of |q|^2 + |t|^2, which
#: can swap two rows that lie that close and no others
SLACK = 32
#: precisions lowprec_topk knows, highest first
PRECISIONS = ("f32", "bf16")


def _best(scores: np.ndarray, ids: np.ndarray, keep: int
          ) -> Tuple[np.ndarray, np.ndarray]:
    """The ``keep`` first of each row of ``scores`` [Q, W] in (score,
    id) order, with their ``ids`` [Q, W]: a partition finds the
    ``keep``-th score, and only the entries at or under it are sorted,
    so that equal scores at the cut are cut by id (exact copies of a
    row score alike to the bit)."""
    if scores.shape[1] <= keep:
        return scores, ids
    kth = np.partition(scores, keep - 1, axis=1)[:, keep - 1]
    out_s = np.empty((scores.shape[0], keep), scores.dtype)
    out_i = np.empty((scores.shape[0], keep), ids.dtype)
    for j in range(scores.shape[0]):
        at = np.flatnonzero(scores[j] <= kth[j])
        at = at[np.lexsort((ids[j, at], scores[j, at]))[:keep]]
        out_s[j], out_i[j] = scores[j, at], ids[j, at]
    return out_s, out_i


def oracle_topk(db: np.ndarray, q: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(indices [Q, k] int64, squared-L2 distances [Q, k] float64) in
    lexicographic (distance, index) order."""
    n = db.shape[0]
    if not 1 <= k <= n:
        raise ValueError(f"k={k} outside 1...{n}")
    q64 = q.astype(np.float64)
    keep = min(k + SLACK, n)
    kept_s = np.empty((q.shape[0], 0))
    kept_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, n, BLOCK):
        t = db[lo:lo + BLOCK].astype(np.float64)
        # |t|^2 - 2 q.t: the query's own norm moves no rank
        s = np.einsum("nd,nd->n", t, t)[None, :] - 2.0 * (q64 @ t.T)
        ids = np.broadcast_to(np.arange(lo, lo + t.shape[0]), s.shape)
        kept_s, kept_i = _best(np.concatenate([kept_s, s], axis=1),
                               np.concatenate([kept_i, ids], axis=1), keep)
    d = np.empty(kept_s.shape)
    for j in range(q.shape[0]):  # a query at a time: [keep, D] float64
        diff = db[kept_i[j]].astype(np.float64) - q64[j]
        d[j] = np.einsum("cd,cd->c", diff, diff)
    order = np.lexsort((kept_i, d), axis=-1)[:, :k]
    return (np.take_along_axis(kept_i, order, axis=1),
            np.take_along_axis(d, order, axis=1))


def lowprec_topk(db: np.ndarray, q: np.ndarray, k: int, precision: str
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The search in a lower precision, ranked by (distance, index) in
    that precision: ``f32`` is the expanded form ``|t|^2 - 2 q.t +
    |q|^2`` in float32 (what a float32 ranking pass computes), ``bf16``
    the same with rows, queries and distances rounded to bfloat16."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")

    def prep(x):
        x = np.asarray(x, np.float32)
        return _round_bf16(x) if precision == "bf16" else x

    qp = prep(q)
    qn = np.einsum("qd,qd->q", qp, qp, dtype=np.float32)
    kept_s = np.empty((q.shape[0], 0), np.float32)
    kept_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, db.shape[0], BLOCK):
        t = prep(db[lo:lo + BLOCK])
        s = (np.einsum("nd,nd->n", t, t, dtype=np.float32)[None, :]
             - np.float32(2.0) * (qp @ t.T) + qn[:, None])
        if precision == "bf16":
            s = _round_bf16(s)
        ids = np.broadcast_to(np.arange(lo, lo + t.shape[0]), s.shape)
        s, ids = (np.concatenate([kept_s, s], axis=1),
                  np.concatenate([kept_i, ids], axis=1))
        # ties at the k-th value are cut by index, so the cut is a sort
        order = np.lexsort((ids, s), axis=-1)[:, :k]
        kept_s = np.take_along_axis(s, order, axis=1)
        kept_i = np.take_along_axis(ids, order, axis=1)
    return kept_i, kept_s.astype(np.float64)


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray, db: np.ndarray = None, q: np.ndarray = None
            ) -> Dict[str, float]:
    """``reference.compare``'s numbers (rows whose indices differ
    anywhere, the recall, the widest relative gap between the sorted
    distances), under the signature ``drivers/sweep_ip.py``'s family
    calls: ``db`` and ``q`` are taken and not needed."""
    return reference.compare(got_i, got_d, want_i, want_d)
