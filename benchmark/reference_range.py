"""The plain reference for range search and the comparison that decides
``correct`` there.

Independent of ``knn_tpu``: numpy only, nothing imported from the
program and nothing the program made.  :func:`oracle_range` gives, for
each query, every row whose float64 squared-L2 distance is at or under
``radius_sq`` (INCLUSIVE), over the float32 rows and queries as given,
in (distance, index) order, in big-ann-benchmarks' range-search format:
``lims`` int64 ``[Q + 1]``, query ``i``'s results are
``idx[lims[i]:lims[i + 1]]`` with ``dist`` beside them.  Its three
``broken`` forms are the CONTROL that the comparison has to fail
(``control_range.py``, ``tests/``); no benchmark run calls them.
:func:`compare` gives the numbers a configuration's ``limits`` name.

``reference.CHUNK`` is reused by import; the limits table is
``reference.Checks``, as for every cell.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from reference import CHUNK

#: the broken references oracle_range knows: the first ``cap`` results
#: alone (a top-k answer, no completion), an exclusive boundary, and
#: rows and queries quantized to 16 levels
BROKEN = ("topk_only", "exclusive", "int4")

Ranges = Tuple[np.ndarray, np.ndarray, np.ndarray]


def _int4(x: np.ndarray, lo_v: float, hi_v: float) -> np.ndarray:
    step = np.float32((hi_v - lo_v) / 15.0 or 1.0)
    return (np.round((np.asarray(x, np.float32) - np.float32(lo_v)) / step)
            * step + np.float32(lo_v)).astype(np.float32)


def oracle_range(db: np.ndarray, q: np.ndarray, radius_sq: float, *,
                 broken: Optional[str] = None, cap: int = 100) -> Ranges:
    """``(lims, idx, dist)``: a scan in blocks of ``CHUNK`` rows marks,
    by the expanded form in float64, every row that could be at or under
    the radius (the form's own rounding allowed for), then those are
    re-scored by direct difference, kept where ``d <= radius_sq``, and
    ordered by (distance, index) within each query.  ``broken`` names
    one of :data:`BROKEN` (``cap`` is ``topk_only``'s k)."""
    if broken is not None and broken not in BROKEN:
        raise ValueError(f"broken {broken!r} not in {BROKEN}")
    if broken == "int4":
        lo_v = float(min(db[:CHUNK].min(), q.min()))
        hi_v = float(max(db[:CHUNK].max(), q.max()))
        q = _int4(q, lo_v, hi_v)
    q64 = q.astype(np.float64)
    qn = np.einsum("qd,qd->q", q64, q64)
    rows_q, rows_t = [], []
    tbuf = np.empty((min(CHUNK, db.shape[0]), db.shape[1]))
    sbuf = np.empty((q.shape[0], tbuf.shape[0]))
    for lo in range(0, db.shape[0], CHUNK):
        n = min(CHUNK, db.shape[0] - lo)
        t, s = tbuf[:n], sbuf[:, :n]
        np.copyto(t, db[lo:lo + n] if broken != "int4"
                  else _int4(db[lo:lo + n], lo_v, hi_v))
        tn = np.einsum("nd,nd->n", t, t)
        np.matmul(q64, t.T, out=s)
        s *= -2.0
        s += tn[None, :]
        s += qn[:, None]
        # the expanded form errs by a few float64 ulps of |q|^2 + |t|^2:
        # 2^-40 of it is far over that and far under any real gap
        slack = 2.0 ** -40 * (qn[:, None] + tn[None, :])
        a, b = np.nonzero(s <= radius_sq + slack)
        rows_q.append(a)
        rows_t.append(b + lo)
    a, b = np.concatenate(rows_q), np.concatenate(rows_t)
    t = db[b] if broken != "int4" else _int4(db[b], lo_v, hi_v)
    diff = t.astype(np.float64) - q64[a]
    d = np.einsum("nd,nd->n", diff, diff)
    keep = d < radius_sq if broken == "exclusive" else d <= radius_sq
    a, b, d = a[keep], b[keep], d[keep]
    order = np.lexsort((b, d, a))
    a, b, d = a[order], b[order], d[order]
    if broken == "topk_only":
        start = np.searchsorted(a, np.arange(q.shape[0]))
        keep = np.arange(a.size) - start[a] < cap
        a, b, d = a[keep], b[keep], d[keep]
    lims = np.zeros(q.shape[0] + 1, np.int64)
    np.cumsum(np.bincount(a, minlength=q.shape[0]), out=lims[1:])
    return lims, b.astype(np.int64), d


def take(ranges: Ranges, rows) -> Ranges:
    """The result lists of the queries ``rows`` (in that order) out of a
    batch's ``(lims, idx, dist)``, as ranges of their own."""
    lims, idx, dist = ranges
    rows = np.asarray(rows, np.int64)
    counts = lims[rows + 1] - lims[rows]
    out = np.zeros(rows.size + 1, np.int64)
    np.cumsum(counts, out=out[1:])
    at = np.repeat(lims[rows] - out[:-1], counts) + np.arange(out[-1])
    return out, np.asarray(idx)[at], np.asarray(dist)[at]


def concat(parts) -> Ranges:
    """Several ``(lims, idx, dist)`` one after another."""
    parts = list(parts)
    sizes = np.concatenate([np.diff(p[0]) for p in parts])
    lims = np.zeros(sizes.size + 1, np.int64)
    np.cumsum(sizes, out=lims[1:])
    return (lims, np.concatenate([p[1] for p in parts]),
            np.concatenate([p[2] for p in parts]))


def compare(got: Ranges, want: Ranges) -> Dict[str, float]:
    """The numbers a comparison with the oracle gives for one block of
    queries: ``mismatched_rows``, the queries whose index list differs
    from the oracle's in length or anywhere; ``dist_rel_err_max``, the
    widest relative gap between the distances of the queries whose
    lengths agree (a non-finite distance reads as infinite); and the
    sizes (``rows``, ``results``, ``most_results``, ``empty_rows``)."""
    g_lims, g_idx, g_d = (np.asarray(x) for x in got)
    w_lims, w_idx, w_d = want
    if g_lims.shape != w_lims.shape or g_idx.shape != g_d.shape:
        raise ValueError(
            f"answer shapes {g_lims.shape}/{g_idx.shape}/{g_d.shape} are "
            f"not a range answer to {w_lims.size - 1} queries")
    bad, rel = 0, 0.0
    for i in range(w_lims.size - 1):
        gi = g_idx[g_lims[i]:g_lims[i + 1]]
        wi = w_idx[w_lims[i]:w_lims[i + 1]]
        if gi.size != wi.size:
            bad += 1
            continue
        bad += int((gi != wi).any())
        gd = np.asarray(g_d[g_lims[i]:g_lims[i + 1]], np.float64)
        wd = w_d[w_lims[i]:w_lims[i + 1]]
        if gd.size:
            gap = np.abs(gd - wd) / np.maximum(wd, np.finfo(np.float64).tiny)
            rel = max(rel, float(gap.max()) if np.isfinite(gd).all()
                      else np.inf)
    sizes = np.diff(w_lims)
    return {"rows": int(sizes.size), "mismatched_rows": bad,
            "dist_rel_err_max": rel, "results": int(sizes.sum()),
            "most_results": int(sizes.max(initial=0)),
            "empty_rows": int((sizes == 0).sum())}
