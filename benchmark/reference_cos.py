"""The plain reference for cosine search and the comparison that decides
``correct`` there.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  :func:`oracle_topk` is the exact
top-k by the smallest cosine distance ``c = 1 - q.t / (|q| |t|)``, in
lexicographic (c, index) order, computed in float64 over the float32
rows and queries AS GIVEN: nothing is normalised and rounded before the
product, every product of two float32 values is exact in float64, and
the norms are float64 roots of float64 sums of exact squares.  A row or
a query of zero norm has cosine 0 (distance 1) to everything.
:func:`lowprec_topk` is the same search in a lower precision: the
CONTROL that the comparison has to fail; no benchmark run calls it.
:func:`compare` gives the numbers a configuration's ``limits`` name.
``reference.compare`` divides by the wanted distance, which a cosine
distance of a near-duplicate brings to 0, so this one measures a
distance's error against ``c + 1/8``: the form of the program's own
bound, ``2^-18 * c`` for the device's float32 arithmetic and ``2^-21``
for the rounding of the unit rows it places.

``reference.recall``, its block size and its bfloat16 rounding are reused
by import; the limits table is ``reference.Checks``, as for every cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from reference import CHUNK, _round_bf16, recall

#: precisions lowprec_topk knows, highest first
PRECISIONS = ("f32", "bf16")
#: what a returned distance's error is measured against, beside the
#: distance itself (module docstring)
DIST_FLOOR = 0.125


def _distance(dots: np.ndarray, den: np.ndarray) -> np.ndarray:
    """``1 - dots / den``, cosine 0 where ``den`` is 0 (a zero norm)."""
    return 1.0 - np.divide(dots, den, out=np.zeros_like(dots),
                           where=den > 0)


def oracle_topk(db: np.ndarray, q: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(indices [Q, k] int64, cosine distances [Q, k] float64): a scan in
    blocks of ``CHUNK`` rows keeps the k+32 best candidates per query (a
    row enters only if it ties or beats the query's current k+32nd),
    then those are re-scored row by row and ordered by (distance,
    index)."""
    q64 = q.astype(np.float64)
    qn = np.sqrt(np.einsum("qd,qd->q", q64, q64))
    nq, keep = q.shape[0], min(k + 32, db.shape[0])
    qid = np.repeat(np.arange(nq), keep)
    cand_s = np.full((nq, keep), np.inf)
    cand_i = np.zeros((nq, keep), np.int64)
    tbuf = np.empty((min(CHUNK, db.shape[0]), db.shape[1]))
    sbuf = np.empty((nq, tbuf.shape[0]))
    for lo in range(0, db.shape[0], CHUNK):
        n = min(CHUNK, db.shape[0] - lo)
        t, s = tbuf[:n], sbuf[:, :n]
        np.copyto(t, db[lo:lo + n])
        np.matmul(q64, t.T, out=s)
        s[...] = _distance(
            s, qn[:, None] * np.sqrt(np.einsum("nd,nd->n", t, t))[None, :])
        if lo == 0 and n >= keep:
            cols = np.argsort(s, axis=1, kind="stable")[:, :keep].ravel()
            rows = qid
        else:
            rows, cols = np.nonzero(s <= cand_s[:, -1:])
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
    order = np.lexsort((cand_i, c), axis=-1)[:, :k]
    return (np.take_along_axis(cand_i, order, axis=1),
            np.take_along_axis(c, order, axis=1))


def lowprec_topk(db: np.ndarray, q: np.ndarray, k: int, precision: str
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The reference's search in a lower precision, ranked by (distance,
    index) in that precision.

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
        best_s = np.concatenate([best_s, s], axis=1)
        best_i = np.concatenate(
            [best_i, np.broadcast_to(np.arange(lo, lo + t.shape[0]),
                                     s.shape)], axis=1)
        if best_s.shape[1] > k:
            order = np.lexsort((best_i, best_s), axis=-1)[:, :k]
            best_s = np.take_along_axis(best_s, order, axis=1)
            best_i = np.take_along_axis(best_i, order, axis=1)
    order = np.lexsort((best_i, best_s), axis=-1)[:, :k]
    return (np.take_along_axis(best_i, order, axis=1),
            np.take_along_axis(best_s, order, axis=1).astype(np.float64))


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray, db: np.ndarray, q: np.ndarray
            ) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for one block of
    queries: rows whose indices differ anywhere, the recall, and the
    widest ``|got - want| / (want + 1/8)`` between the sorted distances
    (which near-tie swaps of indices leave alone).  ``db`` and ``q`` are
    taken as every ``reference_<metric>.compare`` takes them and not
    read: a cosine distance carries its own scale."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    err = np.abs(np.sort(got_d, axis=1) - want_d) / (want_d + DIST_FLOOR)
    err = np.where(np.isfinite(got_d).all(axis=1, keepdims=True), err, np.inf)
    return {"rows": int(got_i.shape[0]),
            "mismatched_rows": int((got_i != want_i).any(axis=1).sum()),
            "recall": recall(got_i, want_i),
            "dist_err_max": float(err.max())}
