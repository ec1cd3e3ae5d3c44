"""The plain reference and the comparison that decides ``correct``.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  :func:`oracle_topk` is the exact
lexicographic (squared-L2 distance, index) top-k in float64 (a copy of
the oracle ``chip_smoke.py`` proved on the chip in PR 21, returning the
distances too).  :func:`lowprec_topk` is the same search computed in a
lower precision: the CONTROL that the comparison has to fail
(``control.py``, ``tests/``); no benchmark run calls it.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

CHUNK = 65_536
#: precisions lowprec_topk knows, highest first
PRECISIONS = ("f32", "bf16", "int4")


def oracle_topk(db: np.ndarray, q: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(indices [Q, k] int64, squared-L2 distances [Q, k] float64): a
    chunked expanded-form scan keeps the k+32 best candidates per query
    (a row enters only if it beats the query's current k+32nd), then
    those are re-scored by direct difference and ordered by (distance,
    index)."""
    q64 = q.astype(np.float64)
    nq, keep = q.shape[0], min(k + 32, db.shape[0])
    qid = np.repeat(np.arange(nq), keep)
    cand_s = np.full((nq, keep), np.inf)
    cand_i = np.zeros((nq, keep), np.int64)
    # buffers made once and written in place: a fresh 67 MB array per
    # chunk costs more in page faults than the arithmetic does
    tbuf = np.empty((min(CHUNK, db.shape[0]), db.shape[1]))
    sbuf = np.empty((nq, tbuf.shape[0]))
    for lo in range(0, db.shape[0], CHUNK):
        n = min(CHUNK, db.shape[0] - lo)
        t, s = tbuf[:n], sbuf[:, :n]
        np.copyto(t, db[lo:lo + n])
        np.matmul(q64, t.T, out=s)
        s *= -2.0
        s += np.einsum("nd,nd->n", t, t)[None, :]
        # <=, so that a tie with the current k+32nd still enters: which
        # of the tied stays is settled by the exact re-score below
        if lo == 0 and n >= keep:
            # nothing to beat yet: the chunk's own best, lowest index
            # first among equals (as the merge below keeps them)
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
        rank = np.arange(all_q.size) - start[all_q]
        top = rank < keep
        cand_s = all_s[top].reshape(nq, keep)
        cand_i = all_i[top].reshape(nq, keep)
    # expanded-form scores at a tie can differ in the last float64 bits,
    # so the k+32 kept are re-scored exactly before they are ordered
    diff = q64[:, None, :] - db[cand_i].astype(np.float64)
    d = np.einsum("qcd,qcd->qc", diff, diff)
    order = np.lexsort((cand_i, d), axis=-1)[:, :k]
    return (np.take_along_axis(cand_i, order, axis=1),
            np.take_along_axis(d, order, axis=1))


def _round_bf16(x: np.ndarray) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even),
    held as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def lowprec_topk(db: np.ndarray, q: np.ndarray, k: int, precision: str
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """The reference's search in a lower precision, ranked by
    (distance, index) in that precision.

    - ``f32``: expanded form ``|t|^2 - 2 q.t + |q|^2`` in float32 (what a
      float32 ranking pass computes);
    - ``bf16``: the same with rows, queries and the resulting distances
      rounded to bfloat16;
    - ``int4``: rows and queries quantized to 16 levels over their value
      range, distances in float32.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    lo_v = float(min(db[:CHUNK].min(), q.min()))
    hi_v = float(max(db[:CHUNK].max(), q.max()))

    def prep(x):
        x = np.asarray(x, np.float32)
        if precision == "bf16":
            return _round_bf16(x)
        if precision == "int4":
            step = np.float32((hi_v - lo_v) / 15.0 or 1.0)
            return (np.round((x - np.float32(lo_v)) / step) * step
                    + np.float32(lo_v)).astype(np.float32)
        return x

    qp = prep(q)
    qn = np.einsum("qd,qd->q", qp, qp, dtype=np.float32)
    best_s = np.empty((q.shape[0], 0), np.float32)
    best_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, db.shape[0], CHUNK):
        t = prep(db[lo:lo + CHUNK])
        s = (np.einsum("nd,nd->n", t, t, dtype=np.float32)[None, :]
             - np.float32(2.0) * (qp @ t.T) + qn[:, None])
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


def recall(got: np.ndarray, want: np.ndarray) -> float:
    """Mean per-query overlap of two [Q, k] index arrays, as sets."""
    return float(np.mean([len(set(a) & set(b)) / want.shape[1]
                          for a, b in zip(np.asarray(got), want)]))


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for one block of
    queries: rows whose indices differ anywhere, the recall, and the
    widest relative gap between the sorted distances (which near-tie
    swaps of indices leave alone)."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    gap = np.abs(np.sort(got_d, axis=1) - want_d)
    rel = gap / np.maximum(want_d, np.finfo(np.float64).tiny)
    rel = np.where(np.isfinite(got_d).all(axis=1, keepdims=True), rel, np.inf)
    return {"rows": int(got_i.shape[0]),
            "mismatched_rows": int((got_i != want_i).any(axis=1).sum()),
            "recall": recall(got_i, want_i),
            "dist_rel_err_max": float(rel.max())}


class Checks:
    """The numbers compared in a run, each beside its limit; ``correct``
    is all of them inside their limits."""

    def __init__(self) -> None:
        self.rows: List[dict] = []

    def add(self, name: str, value: float, limit: float, *,
            at_least: bool = False) -> None:
        value = float(value)
        ok = bool(np.isfinite(value)
                  and (value >= limit if at_least else value <= limit))
        self.rows.append({"check": name, "value": value,
                          "limit": float(limit),
                          "rule": ">=" if at_least else "<=", "ok": ok})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)
