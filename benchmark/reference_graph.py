"""The plain reference for an exact k-NN GRAPH (every row of the corpus a
query of the corpus, its own row no answer) and the comparison that
decides ``correct`` there.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  :func:`oracle_graph` gives, for
each checked row i, the first k rows j != i in lexicographic (float64
squared-L2 distance over the float32 rows as given, j) order: the
distances of row i to ALL rows, in blocks of ``CHUNK`` rows so that 64
checked rows against 5M fit, row i taken out BY ID before anything is
ranked.  An exact copy of row i is a row like any other: it stays, at
distance 0, and copies rank among themselves by id.

:func:`control` is the same search computed WRONGLY in one stated way,
which the comparison has to fail (``control_graph.py``, the tests); no
benchmark run calls it:

- ``keep_self``: the k+1 search with nothing dropped, its first k: the
  row itself leads every list;
- ``drop_zero``: the row is taken out by DISTANCE 0 instead of by id,
  which takes its exact copies out with it: wrong exactly on the rows
  that have a copy, right on every other;
- ``f32``, ``bf16``: the right exclusion, ranked by the expanded form
  in float32, or with rows and distances rounded to bfloat16
  (``reference.lowprec_topk``'s arithmetic).

``reference.CHUNK`` and its bfloat16 rounding are reused by import; the
limits table is ``reference.Checks``, as for every cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from reference import CHUNK, _round_bf16, recall

CONTROLS = ("keep_self", "drop_zero", "f32", "bf16")


def _kept(db: np.ndarray, at: np.ndarray, keep: int, own_out: bool
          ) -> np.ndarray:
    """ids ``[R, keep]`` of the ``keep`` rows nearest each row of ``at``
    by a float64 expanded-form scan in blocks of ``CHUNK`` rows (a row
    enters only if it ties or beats the current ``keep``-th), the row
    itself taken out by id where ``own_out``: the candidates that
    :func:`_ranked` re-scores exactly.  ``reference.oracle_topk``'s
    scan, with the exclusion."""
    q64 = db[at].astype(np.float64)
    nq = at.size
    qid, own = np.repeat(np.arange(nq), keep), np.arange(nq)
    cand_s = np.full((nq, keep), np.inf)
    cand_i = np.zeros((nq, keep), np.int64)
    tbuf = np.empty((min(CHUNK, db.shape[0]), db.shape[1]))
    sbuf = np.empty((nq, tbuf.shape[0]))
    for lo in range(0, db.shape[0], CHUNK):
        n = min(CHUNK, db.shape[0] - lo)
        t, s = tbuf[:n], sbuf[:, :n]
        np.copyto(t, db[lo:lo + n])
        np.matmul(q64, t.T, out=s)
        s *= -2.0
        s += np.einsum("nd,nd->n", t, t)[None, :]
        if own_out:
            here = (at >= lo) & (at < lo + n)
            s[own[here], at[here] - lo] = np.inf
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
        order = np.lexsort((all_s, all_q))  # by query, then by score
        all_q, all_s, all_i = all_q[order], all_s[order], all_i[order]
        start = np.searchsorted(all_q, np.arange(nq))
        top = np.arange(all_q.size) - start[all_q] < keep
        cand_s = all_s[top].reshape(nq, keep)
        cand_i = all_i[top].reshape(nq, keep)
    return cand_i


def _ranked(db: np.ndarray, at: np.ndarray, cand_i: np.ndarray, k: int,
            zero_out: bool = False) -> Tuple[np.ndarray, np.ndarray]:
    """The candidates re-scored by direct difference in float64 (an
    expanded form is off in its last bits at a tie, and reads an exact
    copy's 0 as 1e-16) and ordered by (distance, id); ``zero_out``
    takes every candidate at distance 0 out first."""
    diff = db[at].astype(np.float64)[:, None, :] - db[cand_i].astype(
        np.float64)
    d = np.einsum("qcd,qcd->qc", diff, diff)
    if zero_out:
        d[d == 0.0] = np.inf
    order = np.lexsort((cand_i, d), axis=-1)[:, :k]
    return (np.take_along_axis(cand_i, order, axis=1),
            np.take_along_axis(d, order, axis=1))


def oracle_graph(db: np.ndarray, at: np.ndarray, k: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """(ids [R, k] int64, squared-L2 distances [R, k] float64) of the
    rows ``at``: the graph's rows for them (module docstring)."""
    at = np.asarray(at, np.int64)
    return _ranked(db, at, _kept(db, at, min(k + 32, db.shape[0] - 1), True),
                   k)


def _lowprec(db: np.ndarray, at: np.ndarray, k: int, precision: str
             ) -> Tuple[np.ndarray, np.ndarray]:
    """``reference.lowprec_topk``'s float32 and bfloat16 rankings, the
    row itself out by id."""
    def prep(x):
        return _round_bf16(x) if precision == "bf16" else x

    q = prep(db[at])
    qn = np.einsum("qd,qd->q", q, q, dtype=np.float32)
    best_s = np.empty((at.size, 0), np.float32)
    best_i = np.empty((at.size, 0), np.int64)
    own = np.arange(at.size)
    for lo in range(0, db.shape[0], CHUNK):
        t = prep(db[lo:lo + CHUNK])
        s = (np.einsum("nd,nd->n", t, t, dtype=np.float32)[None, :]
             - np.float32(2.0) * (q @ t.T) + qn[:, None])
        if precision == "bf16":
            s = _round_bf16(s)
        here = (at >= lo) & (at < lo + t.shape[0])
        s[own[here], at[here] - lo] = np.inf
        # the chunk's own k best, then the merge: what is sorted stays small
        part = np.argpartition(s, k, axis=1)[:, :k + 1] \
            if s.shape[1] > k + 1 else np.broadcast_to(
                np.arange(s.shape[1]), s.shape)
        worst = np.take_along_axis(s, part, axis=1).max(axis=1)
        rows, cols = np.nonzero(s <= worst[:, None])
        slot = np.arange(rows.size) - np.searchsorted(rows, rows)
        add_s = np.full((at.size, slot.max() + 1), np.inf, np.float32)
        add_i = np.full(add_s.shape, np.iinfo(np.int64).max, np.int64)
        add_s[rows, slot], add_i[rows, slot] = s[rows, cols], lo + cols
        best_s = np.concatenate([best_s, add_s], axis=1)
        best_i = np.concatenate([best_i, add_i], axis=1)
        order = np.lexsort((best_i, best_s), axis=-1)[:, :k]
        best_s = np.take_along_axis(best_s, order, axis=1)
        best_i = np.take_along_axis(best_i, order, axis=1)
    return best_i, best_s.astype(np.float64)


def control(db: np.ndarray, at: np.ndarray, k: int, how: str
            ) -> Tuple[np.ndarray, np.ndarray]:
    """The graph's rows for ``at`` computed wrongly as ``how`` says
    (``CONTROLS``; module docstring)."""
    if how not in CONTROLS:
        raise ValueError(f"control {how!r} not in {CONTROLS}")
    at = np.asarray(at, np.int64)
    if how in ("f32", "bf16"):
        return _lowprec(db, at, k, how)
    return _ranked(db, at, _kept(db, at, min(k + 33, db.shape[0]), False),
                   k, zero_out=how == "drop_zero")


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for the checked
    rows: the entries of the id lists that differ (over rows x k), the
    rows that hold one, the recall, and the widest relative gap between
    the sorted distances, a wanted distance of 0 (an exact copy) taking
    nothing but 0."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    gap = np.abs(np.sort(got_d, axis=1) - want_d)
    rel = gap / np.maximum(want_d, np.finfo(np.float64).tiny)
    rel = np.where(np.isfinite(got_d).all(axis=1, keepdims=True), rel, np.inf)
    differ = got_i != want_i
    return {"rows": int(got_i.shape[0]),
            "mismatched_ids": int(differ.sum()),
            "mismatched_rows": int(differ.any(axis=1).sum()),
            "recall": recall(got_i, want_i),
            "dist_rel_err_max": float(rel.max())}
