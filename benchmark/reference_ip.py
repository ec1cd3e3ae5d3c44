"""The plain reference for inner-product (MIPS) search and the comparison
that decides ``correct`` there.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  :func:`oracle_topk` is the exact
top-k by the largest inner product, in lexicographic (-q.t, index)
order, computed in float64 over the float32 rows and queries as given
and returned as scores ``-q.t`` (ascending, like a distance).
:func:`lowprec_topk` is the same search in a lower precision: the
CONTROL that the comparison has to fail; no benchmark run calls it.
:func:`compare` gives the numbers a configuration's ``limits`` name.
``reference.compare`` divides by the wanted distance, which is negative
here, so this one measures a score's error against ``|q|^2 + M``, M the
largest squared row norm: the scale of the squared distance an
inner-product search by norm augmentation really computes.

``reference.recall``, its block size and its bfloat16 rounding are reused
by import; the limits table is ``reference.Checks``, as for every cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from reference import CHUNK, _round_bf16, recall

#: precisions lowprec_topk knows, highest first
PRECISIONS = ("f32", "bf16")


def oracle_topk(db: np.ndarray, q: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(indices [Q, k] int64, scores -q.t [Q, k] float64): a scan in
    blocks of ``CHUNK`` rows keeps the k+32 best candidates per query (a
    row enters only if it ties or beats the query's current k+32nd),
    then those are re-scored row by row and ordered by (score, index)."""
    q64 = q.astype(np.float64)
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
        np.negative(s, out=s)
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
    s = -np.einsum("qcd,qd->qc", db[cand_i].astype(np.float64), q64)
    order = np.lexsort((cand_i, s), axis=-1)[:, :k]
    return (np.take_along_axis(cand_i, order, axis=1),
            np.take_along_axis(s, order, axis=1))


def lowprec_topk(db: np.ndarray, q: np.ndarray, k: int, precision: str
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The reference's search in a lower precision, ranked by (score,
    index) in that precision.

    - ``f32``: ``-q.t`` as a float32 matrix product (what a float32
      ranking pass computes);
    - ``bf16``: the same with rows, queries and the resulting scores
      rounded to bfloat16.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")

    def prep(x):
        x = np.asarray(x, np.float32)
        return _round_bf16(x) if precision == "bf16" else x

    qp = prep(q)
    best_s = np.empty((q.shape[0], 0), np.float32)
    best_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, db.shape[0], CHUNK):
        t = prep(db[lo:lo + CHUNK])
        s = -(qp @ t.T)
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


def score_scale(db: np.ndarray, q: np.ndarray) -> np.ndarray:
    """[Q] float64 ``|q|^2 + M``, M the largest squared row norm, taken
    in blocks of ``CHUNK`` rows."""
    m = 0.0
    for lo in range(0, db.shape[0], CHUNK):
        t = db[lo:lo + CHUNK].astype(np.float64)
        m = max(m, float(np.einsum("nd,nd->n", t, t).max()))
    q64 = q.astype(np.float64)
    return np.einsum("qd,qd->q", q64, q64) + m


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray, db: np.ndarray, q: np.ndarray
            ) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for one block of
    queries: rows whose indices differ anywhere, the recall, and the
    widest ``|got - want| / (|q|^2 + M)`` between the sorted scores
    (which near-tie swaps of indices leave alone)."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    err = np.abs(np.sort(got_d, axis=1) - want_d) / score_scale(db, q)[:, None]
    err = np.where(np.isfinite(got_d).all(axis=1, keepdims=True), err, np.inf)
    return {"rows": int(got_i.shape[0]),
            "mismatched_rows": int((got_i != want_i).any(axis=1).sum()),
            "recall": recall(got_i, want_i),
            "score_err_max": float(err.max())}
