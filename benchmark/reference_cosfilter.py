"""The plain reference of cosine search under a scalar range filter, and
the comparison that decides ``correct`` there: for each query the first
k, in lexicographic (float64 cosine distance, index) order, of the rows
whose attribute lies in the query's half-open range ``[lo, hi)``, padded
with index -1 and distance +inf where fewer than k rows qualify.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  The distance is
``reference_cos.py``'s to the letter (``c = 1 - q.t / (|q| |t|)`` in
float64 over the float32 rows and queries AS GIVEN, a zero norm at
cosine 0), written out here again so that this file stands alone; the
range is applied as a boolean mask read off the attribute as given, not
off any words or index built from it.  :func:`lowprec_topk` and
:func:`post_filter_topk` are the CONTROLS the comparison has to fail;
no benchmark run calls them.

``reference.recall``'s block size and bfloat16 rounding are reused by
import; the limits table is ``reference.Checks``, as for every cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from reference import CHUNK, _round_bf16

#: precisions lowprec_topk knows, highest first
PRECISIONS = ("f32", "bf16")
#: what a returned distance's error is measured against, beside the
#: distance itself (``reference_cos.py``'s floor, for its reason)
DIST_FLOOR = 0.125


def in_range(attr: np.ndarray, ranges: np.ndarray) -> np.ndarray:
    """bool ``[queries, rows]``: ``lo <= attr < hi``, in int64."""
    a = np.asarray(attr).astype(np.int64)[None, :]
    r = np.asarray(ranges).astype(np.int64)
    return (a >= r[:, :1]) & (a < r[:, 1:])


def _distance(dots: np.ndarray, den: np.ndarray) -> np.ndarray:
    """``1 - dots / den``, cosine 0 where ``den`` is 0 (a zero norm)."""
    return 1.0 - np.divide(dots, den, out=np.zeros_like(dots),
                           where=den > 0)


def _pad(ids: np.ndarray, c: np.ndarray, k: int
         ) -> Tuple[np.ndarray, np.ndarray]:
    """The first k of each row of ``(ids, c)`` by (distance, index), an
    entry at +inf as index -1, short rows padded likewise."""
    order = np.lexsort((ids, c), axis=-1)[:, :k]
    ids = np.take_along_axis(ids, order, axis=1)
    c = np.take_along_axis(c, order, axis=1)
    ids = np.where(np.isfinite(c), ids, -1)
    short = k - ids.shape[1]
    if short > 0:
        ids = np.pad(ids, ((0, 0), (0, short)), constant_values=-1)
        c = np.pad(c, ((0, 0), (0, short)), constant_values=np.inf)
    return ids.astype(np.int64), c


def oracle_topk(db: np.ndarray, attr: np.ndarray, q: np.ndarray,
                ranges: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """(indices [Q, k] int64, cosine distances [Q, k] float64): a scan in
    blocks of ``CHUNK`` rows, the out-of-range rows of each query at
    +inf, keeps the k+32 best candidates a query (a row enters only if
    it is in range and ties or beats the query's current k+32nd); those
    are re-scored row by row and ordered by (distance, index)."""
    q64 = q.astype(np.float64)
    qn = np.sqrt(np.einsum("qd,qd->q", q64, q64))
    r = np.asarray(ranges).astype(np.int64)
    nq, keep = q.shape[0], min(k + 32, db.shape[0])
    qid = np.repeat(np.arange(nq), keep)
    cand_s = np.full((nq, keep), np.inf)
    cand_i = np.zeros((nq, keep), np.int64)
    for lo in range(0, db.shape[0], CHUNK):
        t = db[lo:lo + CHUNK].astype(np.float64)
        a = np.asarray(attr[lo:lo + CHUNK]).astype(np.int64)[None, :]
        s = _distance(
            q64 @ t.T,
            qn[:, None] * np.sqrt(np.einsum("nd,nd->n", t, t))[None, :])
        s[~((a >= r[:, :1]) & (a < r[:, 1:]))] = np.inf
        rows, cols = np.nonzero(np.isfinite(s) & (s <= cand_s[:, -1:]))
        if rows.size == 0:
            continue
        all_q = np.concatenate([qid, rows])
        all_s = np.concatenate([cand_s.ravel(), s[rows, cols]])
        all_i = np.concatenate([cand_i.ravel(), lo + cols])
        order = np.lexsort((all_i, all_s, all_q))
        all_q, all_s, all_i = all_q[order], all_s[order], all_i[order]
        start = np.searchsorted(all_q, np.arange(nq))
        rank = np.arange(all_q.size) - start[all_q]
        top = rank < keep
        cand_s = all_s[top].reshape(nq, keep)
        cand_i = all_i[top].reshape(nq, keep)
    # a blocked matrix product may sum in another order than a row's own
    # dot product, so the kept are re-scored one row at a time (products
    # of float32 values are exact in float64) before they are ordered
    t = db[cand_i].astype(np.float64)
    c = _distance(np.einsum("qcd,qd->qc", t, q64),
                  qn[:, None] * np.sqrt(np.einsum("qcd,qcd->qc", t, t)))
    return _pad(cand_i, np.where(np.isfinite(cand_s), c, np.inf), k)


def lowprec_topk(db: np.ndarray, attr: np.ndarray, q: np.ndarray,
                 ranges: np.ndarray, k: int, precision: str
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The reference's search in a lower precision, ranked by (distance,
    index) in that precision over each query's rows in range.

    - ``f32``: rows and queries normalised in float32, ``1 - q^.t^`` as
      one float32 matrix product (what a float32 ranking pass over unit
      rows computes);
    - ``bf16``: the same with the unit rows, the unit queries and the
      resulting distances rounded to bfloat16.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")

    def prep(x):
        x = np.asarray(x, np.float32)
        n = np.sqrt(np.einsum("nd,nd->n", x, x, dtype=np.float32))
        x = x / np.where(n > 0, n, np.float32(1))[:, None]
        return _round_bf16(x) if precision == "bf16" else x

    qp = prep(q)
    best_s = np.empty((q.shape[0], 0), np.float32)
    best_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, db.shape[0], CHUNK):
        t = prep(db[lo:lo + CHUNK])
        s = np.float32(1) - qp @ t.T
        if precision == "bf16":
            s = _round_bf16(s)
        s[~in_range(attr[lo:lo + CHUNK], ranges)] = np.inf
        best_s = np.concatenate([best_s, s], axis=1)
        best_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(lo, lo + t.shape[0]),
                                     s.shape)], axis=1)
        if best_s.shape[1] > k:
            order = np.lexsort((best_i, best_s), axis=-1)[:, :k]
            best_s = np.take_along_axis(best_s, order, axis=1)
            best_i = np.take_along_axis(best_i, order, axis=1)
    return _pad(best_i, best_s.astype(np.float64), k)


def post_filter_topk(db: np.ndarray, attr: np.ndarray, q: np.ndarray,
                     ranges: np.ndarray, k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """What a post-filter gives: the UNFILTERED float64 top-k (every row
    in range), the rows outside each query's own range dropped, the
    rest padded."""
    everything = np.broadcast_to(
        np.array([[np.iinfo(np.int64).min, np.iinfo(np.int64).max]]),
        (q.shape[0], 2))
    top_i, top_c = oracle_topk(db, attr, q, everything, k)
    ok = np.take_along_axis(in_range(attr, ranges), top_i, axis=1)
    return _pad(top_i, np.where(ok, top_c, np.inf), k)


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray, attr: np.ndarray, ranges: np.ndarray
            ) -> Dict[str, float]:
    """``mismatched_rows`` (queries whose k indices differ anywhere,
    padding included), ``invalid_returned`` (returned indices whose
    attribute lies outside their query's range, or that name no row:
    read off the attribute, not off the oracle's answer),
    ``dist_err_max`` (the widest ``|got - want| / (want + 1/8)`` between
    the distances, position by position where both are finite; a
    distance that is finite on one side only reads +inf), and what the
    sample held: ``short_rows`` and ``empty_rows`` by the oracle."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    a = np.asarray(attr).astype(np.int64)
    r = np.asarray(ranges).astype(np.int64)
    named = got_i >= 0
    there = named & (got_i < a.size)
    at = a[np.where(there, got_i, 0)]
    invalid = named & ~(there & (at >= r[:, :1]) & (at < r[:, 1:]))
    both = np.isfinite(got_d) & np.isfinite(want_d)
    err = np.zeros(got_d.shape)
    err[both] = np.abs(got_d[both] - want_d[both]) / (
        want_d[both] + DIST_FLOOR)
    err[np.isfinite(got_d) != np.isfinite(want_d)] = np.inf
    found = (want_i >= 0).sum(axis=1)
    k = want_i.shape[1]
    return {"rows": int(got_i.shape[0]),
            "mismatched_rows": int((got_i != want_i).any(axis=1).sum()),
            "invalid_returned": int(invalid.sum()),
            "dist_err_max": float(err.max(initial=0.0)),
            "short_rows": int(((found > 0) & (found < k)).sum()),
            "empty_rows": int((found == 0).sum())}
