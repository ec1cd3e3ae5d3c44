"""The plain reference of filtered search: for each query the first k,
in lexicographic (float64 squared-L2 distance, index) order, of the rows
whose bag of tags holds EVERY tag the query names, padded with index -1
and distance +inf where fewer than k rows qualify.  numpy and float64
only, nothing of the program's: validity is read off the rows' bags as
given (CSR, row -> tag ids), not off any index built from them.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from reference import CHUNK


def valid_rows(indptr: np.ndarray, tags: np.ndarray, wanted) -> np.ndarray:
    """Rows (ascending) whose bag holds every tag in ``wanted`` (ids
    under 0 name no tag): one pass over the bags a tag."""
    n = indptr.size - 1
    ok = np.ones(n, bool)
    row_of = None
    for t in wanted:
        if t < 0:
            continue
        if row_of is None:
            row_of = np.repeat(np.arange(n), np.diff(indptr))
        has = np.zeros(n, bool)
        has[row_of[tags == t]] = True
        ok &= has
    return np.flatnonzero(ok)


def oracle_topk(db: np.ndarray, indptr: np.ndarray, tags: np.ndarray,
                q: np.ndarray, filter_tags: np.ndarray, k: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """``(indices [Q, k] int64, distances [Q, k] float64)``."""
    out_i = np.full((q.shape[0], k), -1, np.int64)
    out_d = np.full((q.shape[0], k), np.inf)
    for qi in range(q.shape[0]):
        rows = valid_rows(indptr, tags, filter_tags[qi])
        q64 = q[qi].astype(np.float64)
        d = np.empty(rows.size)
        for lo in range(0, rows.size, CHUNK):
            diff = db[rows[lo:lo + CHUNK]].astype(np.float64) - q64
            d[lo:lo + CHUNK] = np.einsum("nd,nd->n", diff, diff)
        order = np.lexsort((rows, d))[:k]
        out_i[qi, :order.size] = rows[order]
        out_d[qi, :order.size] = d[order]
    return out_i, out_d


def compare(got_i: np.ndarray, got_d: np.ndarray, want_i: np.ndarray,
            want_d: np.ndarray, indptr: np.ndarray, tags: np.ndarray,
            filter_tags: np.ndarray) -> Dict[str, float]:
    """``mismatched_rows`` (queries whose k indices differ anywhere,
    padding included), ``invalid_returned`` (returned indices whose bag
    lacks a tag of their query: read off the bags, not off the oracle's
    answer), ``dist_rel_err_max`` (the widest relative gap between the
    returned and the oracle's distances, position by position; a
    distance that is finite on one side only reads +inf), and what the
    sample held: ``short_rows`` and ``empty_rows`` by the oracle."""
    got_i, got_d = np.asarray(got_i), np.asarray(got_d, np.float64)
    if got_i.shape != want_i.shape or got_d.shape != want_d.shape:
        raise ValueError(
            f"answer shapes {got_i.shape}/{got_d.shape} are not the "
            f"reference's {want_i.shape}/{want_d.shape}")
    invalid = 0
    for qi in range(got_i.shape[0]):
        for r in got_i[qi]:
            if r < 0:
                continue
            bag = tags[indptr[r]:indptr[r + 1]] if r < indptr.size - 1 \
                else tags[:0]
            invalid += any(t >= 0 and t not in bag for t in filter_tags[qi])
    both = np.isfinite(got_d) & np.isfinite(want_d)
    rel = np.zeros(got_d.shape)
    rel[both] = np.abs(got_d[both] - want_d[both]) / np.maximum(
        want_d[both], np.finfo(np.float64).tiny)
    rel[np.isfinite(got_d) != np.isfinite(want_d)] = np.inf
    found = (want_i >= 0).sum(axis=1)
    k = want_i.shape[1]
    return {"rows": int(got_i.shape[0]),
            "mismatched_rows": int((got_i != want_i).any(axis=1).sum()),
            "invalid_returned": int(invalid),
            "dist_rel_err_max": float(rel.max(initial=0.0)),
            "short_rows": int(((found > 0) & (found < k)).sum()),
            "empty_rows": int((found == 0).sum())}
