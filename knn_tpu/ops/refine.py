"""Exact candidate refinement: restore recall@k = 1.0 after a fast coarse
pass.

The TPU path ranks with float32 (or bfloat16) distances; at 1M-database
scale a handful of near-boundary neighbors can swap order vs the float64
oracle (the expanded-square cancellation SURVEY.md §7 hard part (c)).  The
fix is the classic two-phase scheme: take k + margin candidates from the
fast pass, re-score JUST those in float64 on host (O(Q·m·D), trivial next
to the O(Q·N·D) coarse pass), and re-select the exact lexicographic top-k.

Exactness condition: every true top-k member appears in the coarse
top-(k+margin).  The coarse pass's worst-case distance error is a few
float32 ulps of the squared-norm magnitude, so a margin of a few dozen
covers it at SIFT1M scale; the benchmark's float64 oracle
(benchmark/reference.py) checks every run's sampled answers against it.

The float64 temporaries are made in blocks of at most ``_BLOCK_ELEMS``
elements (:func:`_block_rows`), so that each is served from the heap and
none grows with the batch.  What is worth sharing goes to one small pool
of host threads (:func:`pool_map`), made at the first call that has more
than one part.  :func:`rank_correct_runs` with many members cuts its
QUERIES into contiguous ranges, even in members, and a range runs every
phase of the correction (mask, gather, re-score, sort, scatter) on one
thread; with few members the sorts are too short to share, and it stays
on the calling thread but for the blocks of its re-score, which the pool
shares as it shares :func:`exact_pair_scores`' blocks of pairs.  numpy
releases the GIL in the gather, the cast, the arithmetic and the sorts.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

from knn_tpu import obs

#: float64 elements one re-score temporary may hold (8 MB): at SIFT
#: bench shape the unchunked refine allocated ~1 GB twice over and ran
#: ~40% slower (measured chunk sweep, 2026-07)
_BLOCK_ELEMS = 1 << 20

#: host threads that share one call's parts (a rank correction's query
#: ranges or blocks, a pair scorer's blocks, a query map's rows).  The
#: chip's host has 13 cores and shares them with the runtime's transfer
#: threads: read at 4 / 6 / 8 threads (PR 60, one traced and one 30 s
#: run each), ``knnlm1m.sweep_k1024`` rank-corrects a batch in 439 / 426
#: / 404 ms and answers 6,981 / 7,063 / 7,065 q/s (the device paces it
#: from 4 on), ``gist1m.sweep`` answers 20,485 / 20,348 / 20,254 and
#: ``imagenet-knn768.sweep_vote`` 27,858 / 27,675 / 27,618: nothing
#: past 4, so 4 stays
_POOL_THREADS = min(4, os.cpu_count() or 1)

#: tight pairs below which a range of a rank correction is not worth a
#: thread of its own.  On the chip's host (PR 60) ranges of about 1,000
#: members made the correction's sorts and scatters twice as long as
#: the single thread's at ``gist1m.sweep``, and longer with every
#: thread; ranges of 16,000 a third as long at ``knnlm1m.sweep_k1024``
#: (a pair is one or two members).  Presumably: on a small range those
#: are numpy calls of a few microseconds, and threads that hand the
#: interpreter's lock back and forth between such calls lose more than
#: they share
_RANGE_PAIRS = 4096

_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()
#: ``.member`` is True on the pool's own threads
_in_pool = threading.local()


def _block_rows(row_elems: int) -> int:
    """How many rows of ``row_elems`` elements make one block."""
    return max(1, _BLOCK_ELEMS // max(1, row_elems))


def _join_pool() -> None:
    _in_pool.member = True


def _shared_pool() -> ThreadPoolExecutor:
    """The process's one re-score pool, made on first use: a batch must
    not pay thread start-up, and concurrent callers share the threads."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=_POOL_THREADS,
                thread_name_prefix="knn-rank-correct",
                initializer=_join_pool)
        return _pool


def pool_map(fn, parts) -> list:
    """``[fn(part) for part in parts]``, the parts shared among the
    re-score pool's threads where there are several; one part runs on
    the caller's thread, and so does every part of a map made ON a pool
    thread (a part of an outer map: a pool thread never waits for the
    pool)."""
    if len(parts) > 1 and not getattr(_in_pool, "member", False):
        # list(): reading every result re-raises a worker's exception
        return list(_shared_pool().map(fn, parts))
    return [fn(part) for part in parts]


def norms_of_f64(rows64: np.ndarray, out: np.ndarray) -> np.ndarray:
    """``out[j]`` = the Euclidean norm of ``rows64[j]`` (float64 [b, D],
    widened float32 values: the squares are exact, each row's sum is its
    own, pairwise).  Returns the squares' buffer for the caller to
    reuse.  THE norm of the cosine contract: placements, batches and
    the host's scorers all divide by these numbers."""
    sq = rows64 * rows64
    np.sum(sq, axis=-1, out=out)
    np.sqrt(out, out=out)
    return sq


def row_norms_f64(x: np.ndarray) -> np.ndarray:
    """[n] float64 Euclidean norms of the rows of ``x`` [n, D]
    (:func:`norms_of_f64`), a block of rows at a time
    (:func:`_block_rows`) so no temporary grows with ``n``."""
    out = np.empty(x.shape[0])
    block = _block_rows(x.shape[1])
    for lo in range(0, x.shape[0], block):
        norms_of_f64(x[lo : lo + block].astype(np.float64),
                     out[lo : lo + block])
    return out


def norms_rows(norms, sel):
    """A cosine call's ``(query norms, db row norms)`` held to the
    queries ``sel`` (a slice or positions); None stays None."""
    return None if norms is None else (norms[0][sel], norms[1])


def cosine_distance(dots: np.ndarray, den: np.ndarray) -> np.ndarray:
    """``1 - dots / den`` in place in ``dots``: the cosine distance from
    float64 inner products and the products of the two norms.  A row or
    a query of zero norm (``den`` 0; its inner product is an exact 0)
    has cosine 0 to everything, distance 1."""
    np.divide(dots, den, out=dots, where=den > 0)
    return np.subtract(1.0, dots, out=dots)


def _pairwise_f64(queries: np.ndarray, cand: np.ndarray, metric: str,
                  norms=None) -> np.ndarray:
    """[Q, m] float64 distances between each query and its own candidate
    rows (cand is [Q, m, D]).  ``norms`` (cosine only): ``(query norms
    [Q], candidate norms [Q, m])`` in float64 where the caller keeps
    them; taken from the arrays here otherwise."""
    q = queries.astype(np.float64)[:, None, :]
    c = cand.astype(np.float64)
    m = metric.lower()
    if m in ("l2", "sql2", "euclidean"):
        diff = c - q
        return np.einsum("qmd,qmd->qm", diff, diff)
    if m in ("l1", "manhattan"):
        return np.abs(c - q).sum(-1)
    if m == "cosine":
        # q.t / (|q| |t|) of the values as given: every product exact,
        # nothing normalised before the sum
        qn, cn = norms if norms is not None else (
            np.sqrt(np.einsum("qmd,qmd->qm", q, q))[:, 0],
            np.sqrt(np.einsum("qmd,qmd->qm", c, c)))
        return cosine_distance(np.einsum("qmd,qmd->qm", c, q),
                               qn[:, None] * cn)
    if m == "dot":
        return -np.einsum("qmd,qmd->qm", c, q)
    raise ValueError(f"unknown metric {metric!r}")


def _score_members(db_np: np.ndarray, queries_np: np.ndarray,
                   cand: np.ndarray, rows: np.ndarray, metric: str,
                   out: np.ndarray, norms=None) -> float:
    """``out[j]`` = the float64 ``metric`` value between query
    ``rows[j]`` and db row ``cand[j]``: squared L2 by direct difference,
    the negated inner product (``"dot"``), or the cosine distance
    ``1 - q.t / (|q| |t|)`` (``"cosine"``; ``norms`` = ``(query norms
    [Q], db row norms [N])`` in float64, :func:`row_norms_f64`'s).
    f32 -> f64 is exact, so the in-place arithmetic on the widened rows
    equals widening both sides first; each member's sum is its own.
    Returns the moment the rows had been gathered and widened
    (``time.perf_counter``): before it the gather, after it the
    arithmetic."""
    acc = db_np[cand].astype(np.float64)
    gathered = time.perf_counter()
    if metric in ("dot", "cosine"):
        # products of two float32 values are exact in float64; only the
        # sum rounds (pairwise: under (D+1) * 2^-53 * sum |q_i t_i|)
        acc *= queries_np[rows]
        np.sum(acc, axis=-1, out=out)
        if metric == "dot":
            np.negative(out, out=out)
        else:
            cosine_distance(out, norms[0][rows] * norms[1][cand])
    else:
        acc -= queries_np[rows]
        np.einsum("nd,nd->n", acc, acc, out=out)
    return gathered


def exact_pair_scores(db_np: np.ndarray, queries_np: np.ndarray,
                      rows: np.ndarray, cand: np.ndarray,
                      metric: str = "l2") -> np.ndarray:
    """[P] float64 ``metric`` values of the flat pairs (query
    ``rows[j]``, db row ``cand[j]``), by :func:`_score_members`'
    arithmetic, made a block of pairs at a time and shared among the
    pool's threads like :func:`rank_correct_runs`' re-score.  Every
    ``cand`` names a db row."""
    out = np.empty(cand.size)
    block = _block_rows(db_np.shape[1])
    starts = range(0, cand.size, block)

    def score(lo: int) -> None:
        _score_members(db_np, queries_np, cand[lo : lo + block],
                       rows[lo : lo + block], metric, out[lo : lo + block])

    pool_map(score, starts)
    return out


def exact_scores(db_np: np.ndarray, queries_np: np.ndarray,
                 idx: np.ndarray, metric: str) -> np.ndarray:
    """[Q, k] float64 ``metric`` values between each query and the db
    rows ``idx`` [Q, k] names (:func:`exact_pair_scores` over the
    flattened pairs).  Indices past the db (the sentinel) read +inf."""
    n_q, k = idx.shape
    flat = np.asarray(idx, np.int64).reshape(-1)
    out = exact_pair_scores(
        db_np, queries_np, np.repeat(np.arange(n_q), k),
        np.clip(flat, 0, db_np.shape[0] - 1), metric)
    return np.where(flat < db_np.shape[0], out, np.inf).reshape(n_q, k)


def host_exact_range(db_np: np.ndarray, q_np: np.ndarray, radius_sq: float
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Unconditional last-resort exact range scan on the host: every
    pair (query row of ``q_np``, db row) whose float64 direct-difference
    squared distance is ``<= radius_sq``, as flat ``(query positions,
    db rows, distances)`` in no particular order.  A block of rows at a
    time (:func:`_block_rows`); O(Q*N*D) host arithmetic, for the few
    queries whose result count passes the device completion's collect
    width."""
    block = _block_rows(db_np.shape[1])
    qs, ts, ds = [], [], []
    for qi in range(q_np.shape[0]):
        q64 = q_np[qi].astype(np.float64)
        for lo in range(0, db_np.shape[0], block):
            diff = db_np[lo : lo + block].astype(np.float64)
            diff -= q64
            d = np.einsum("nd,nd->n", diff, diff)
            hit = np.flatnonzero(d <= radius_sq)
            if hit.size:
                qs.append(np.full(hit.size, qi, np.int64))
                ts.append(hit + lo)
                ds.append(d[hit])
    if not qs:
        return (np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0))
    return np.concatenate(qs), np.concatenate(ts), np.concatenate(ds)


#: the three phases of :func:`rank_correct_runs`: names of its profiler
#: annotations (``knn.`` before them) and of the once-a-call spans a
#: certified call records of their sums (parallel.sharded)
PHASE_BUFFERS = "certified.rank_correct.buffers"
PHASE_SCORE = "certified.rank_correct.score"
PHASE_ORDER = "certified.rank_correct.order"


def _member_ranges(pairs: np.ndarray) -> list:
    """The queries of one rank correction cut into contiguous ``(lo,
    hi)`` ranges for the pool, EVEN IN MEMBERS and not in queries: tie
    runs crowd in some queries.  ``pairs`` [Q] counts each query's tight
    pairs, what the mask shows of its members before it is expanded (a
    run of p pairs has p + 1 members), and a range ends at the query
    where their running sum passes its share.  As many ranges as the
    pool has threads, fewer where a range would hold under
    ``_RANGE_PAIRS`` pairs, so one where the call's members are too few
    for ranges to pay."""
    n_q = pairs.size
    run = np.cumsum(pairs)
    total = int(run[-1]) if n_q else 0
    parts = max(1, min(_POOL_THREADS, total // _RANGE_PAIRS))
    if parts == 1:
        return [(0, n_q)]
    shares = total * np.arange(1, parts) / parts
    cuts = np.unique(np.concatenate(
        ([0], np.searchsorted(run, shares) + 1, [n_q])))
    return list(zip(cuts[:-1].tolist(), cuts[1:].tolist()))


def _correct_range(gi, tight, k, queries_np, db_np, d32k, metric, norms,
                   gw, d_out) -> Tuple[dict, int]:
    """One range of :func:`rank_correct_runs`' queries, every phase of
    it on the calling thread.  The arguments are the call's, cut to the
    range (views); ``gw`` [q, W] int64 and ``d_out`` [q, k] float64 or
    None are the range's rows of the call's outputs, filled here: the
    copies of the windowed indices and of ``d32k``, then the members of
    each tie run re-scored a block at a time (:func:`_score_members`;
    the blocks go through :func:`pool_map`, which shares them where the
    range is the call's only one, on the calling thread, and runs them
    in turn where the range is itself a part on a pool thread) and put
    back in float64 order.  Opens no span and asks for none: returns
    ``(seconds by phase, members)``, the phases timed where they run
    and annotated ``knn.<phase>`` on this thread."""
    secs = {}
    with obs.trace.phase(secs, "order_s", PHASE_ORDER):
        inv = np.zeros((tight.shape[0], tight.shape[1] + 1), dtype=bool)
        inv[:, :-1] |= tight
        inv[:, 1:] |= tight
        rows, cols = np.nonzero(inv)
    with obs.trace.phase(secs, "buffers_s", PHASE_BUFFERS):
        if d_out is not None:
            d_out[...] = d32k
        gw[...] = gi[:, : gw.shape[1]]
    if rows.size == 0:
        return secs, 0
    with obs.trace.phase(secs, "order_s", PHASE_ORDER):
        cand = gw[rows, cols]
        safe = np.clip(cand, 0, db_np.shape[0] - 1)
        d64 = np.empty(rows.size)
    block = _block_rows(db_np.shape[1])

    def score(lo: int) -> Tuple[float, float]:
        t0 = time.perf_counter()
        gathered = _score_members(
            db_np, queries_np, safe[lo : lo + block],
            rows[lo : lo + block], metric, d64[lo : lo + block], norms)
        return gathered - t0, time.perf_counter() - gathered

    with obs.trace.phase(secs, "score_s", PHASE_SCORE):
        timed = pool_map(score, range(0, rows.size, block))
    secs["gather_s"] = sum(g for g, _ in timed)
    secs["arith_s"] = sum(a for _, a in timed)
    with obs.trace.phase(secs, "order_s", PHASE_ORDER):
        d64 = np.where(cand < db_np.shape[0], d64, np.inf)
        # maximal runs of consecutive involved positions; (rows, cols)
        # comes position-sorted from nonzero, so each run is one
        # contiguous block
        new_run = np.ones(rows.size, dtype=bool)
        new_run[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1] + 1)
        run_id = np.cumsum(new_run) - 1
        # lexicographic sort within each run; runs are contiguous
        # ascending in both the original flat order and the
        # (run_id-primary) sorted order, so flat positions realign
        # block-for-block
        order = np.lexsort((cand, d64, run_id))
        gw[rows, cols] = cand[order]
        if d_out is not None:
            in_k = cols < k
            d_sorted = d64[order]
            d_out[rows[in_k], cols[in_k]] = d_sorted[in_k]
    return secs, int(rows.size)


def rank_correct_runs(
    gi: np.ndarray,
    tight: np.ndarray,
    k: int,
    queries_np: np.ndarray,
    db_np: np.ndarray,
    d32k: Optional[np.ndarray] = None,
    metric: str = "l2",
    norms=None,
) -> Tuple[Optional[np.ndarray], np.ndarray, int]:
    """Float64 repair of a device-ranked candidate list from the near-tie
    mask ALONE — no distance matrix crosses the device->host link.

    ``gi`` [Q, m1] device-ranked candidate indices; ``tight`` [Q, W-1]
    bool marks adjacent pairs closer than the f32 rank slack, already
    restricted by the device program to finite values before the top-k
    boundary's first big gap (rows with no provable boundary were flagged
    ``bad`` there and rerun exactly — they never reach this function's
    fast path).  Members of each maximal run of tight pairs are re-scored
    in float64 and re-sorted lexicographically IN PLACE: a correction can
    never cross an uninvolved neighbor, because the gap there exceeds the
    slack while corrections move less than a third of it.

    ``metric`` is what the members are re-scored and re-sorted by:
    squared L2 between the arrays as given, ``"dot"``, the negated
    float64 inner product, or ``"cosine"``, ``1 - q.t / (|q| |t|)`` in
    float64.  A dot placement's rows and queries arrive norm-augmented
    (parallel.sharded): the query's appended column is an exact zero,
    so that product IS the inner product of the original columns, and
    the run is ordered by (-q.t, index) of the problem as posed, not by
    the augmented difference, whose appended column was rounded to
    float32.  A cosine placement hands over the rows and queries AS
    GIVEN (not the float32 unit rows the device ranked) with ``norms``
    = ``(query norms [Q], db row norms [N])`` in float64
    (:func:`row_norms_f64`), so the run is ordered by the cosine of the
    problem as posed, not by the distance of the rounded unit rows.

    ``d32k`` [Q, k] float64 (optional): the device's top-k distances;
    when given, corrected positions < k get their exact float64 values
    patched in and the array is returned — None skips distance output
    entirely (callers that only need indices save the transfer).

    **What runs where.**  The calling thread counts each query's tight
    pairs, cuts the queries into contiguous ranges even in members
    (:func:`_member_ranges`: as many as the pool has threads, fewer
    where a range would hold under ``_RANGE_PAIRS`` pairs) and allocates
    the outputs; every range then runs ALL of its phases on one thread
    of the pool (:func:`_correct_range`, through :func:`pool_map`): the
    mask's expansion and ``nonzero``, the copies of its rows of ``d32k``
    and of the windowed indices, the float64 re-score of its members a
    block of at most :func:`_block_rows` at a time, the run ids,
    ``lexsort`` and the scatter back.  A call with too few members for
    ranges is ONE range on the calling thread, and the blocks of its
    re-score are what the pool shares (a call of one block shares
    nothing).  Ranges write disjoint rows of the shared outputs, a tie
    run never crosses a query and each member's sum is its own, so the
    answer does not depend on the cut, the blocking or thread timing.

    The innermost span open on the calling thread (the caller's
    ``certified.rank_correct``, read once, before the map) is told
    ``members``, ``parts`` (the ranges of this call) and ``threads``
    (the pool's width), and where this call's seconds went: three
    phases, each a ``knn.<phase>`` profiler annotation on the thread
    that runs it.  ``buffers_s`` (the outputs and the copies into
    them), ``score_s`` (the re-score) and ``order_s`` (everything else:
    mask, ``nonzero``, run ids, ``lexsort``, scatter) are shares of
    WALL time: what the calling thread did itself, plus the map's wall
    time split in proportion to the ranges' summed seconds of each
    phase (a lone range's phases are wall time as they stand).
    ``gather_s`` (the fancy-index gather of the members' rows
    with its widening) and ``arith_s`` (the subtraction or product and
    the sum) are sums over the blocks whichever thread ran them.

    Returns (d_out or None, i_out [Q, k] int64, corrected_row_count).
    """
    n_q = gi.shape[0]
    w = tight.shape[1] + 1
    if w < k:
        raise ValueError(f"tie mask window {w} < k={k}")
    sp = obs.current_span()
    own = {}  # the calling thread's seconds
    with obs.trace.phase(own, "order_s", PHASE_ORDER):
        pairs = np.count_nonzero(tight, axis=1)
        ranges = _member_ranges(pairs)
    with obs.trace.phase(own, "buffers_s", PHASE_BUFFERS):
        d_out = None if d32k is None else np.empty_like(d32k)
        gw = np.empty((n_q, w), dtype=np.int64)

    def correct(cut: Tuple[int, int]) -> Tuple[dict, int]:
        sel = slice(*cut)
        return _correct_range(
            gi[sel], tight[sel], k, queries_np[sel], db_np,
            None if d32k is None else d32k[sel], metric,
            norms_rows(norms, sel), gw[sel],
            None if d_out is None else d_out[sel])

    t0 = time.perf_counter()
    told = pool_map(correct, ranges)
    wall = time.perf_counter() - t0
    summed = {key: sum(secs.get(key, 0.0) for secs, _ in told)
              for key in ("buffers_s", "score_s", "order_s",
                          "gather_s", "arith_s")}
    inside = summed["buffers_s"] + summed["score_s"] + summed["order_s"]
    for key in ("buffers_s", "score_s", "order_s"):
        sp.set(key, own.get(key, 0.0)
               + (wall * summed[key] / inside if inside else 0.0))
    sp.set("gather_s", summed["gather_s"])
    sp.set("arith_s", summed["arith_s"])
    sp.set("members", sum(members for _, members in told))
    sp.set("parts", len(ranges))
    sp.set("threads", _POOL_THREADS)
    return d_out, gw[:, :k], int(np.count_nonzero(pairs))


def refine_exact(
    db: np.ndarray,
    queries: np.ndarray,
    cand_idx: np.ndarray,
    k: int,
    metric: str = "l2",
    norms=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """(distances [Q, k] float64, indices [Q, k] int64): the exact
    lexicographic (distance, index) top-k among each query's candidates.

    ``cand_idx`` is [Q, m] with m >= k, from the coarse device pass.
    Duplicate or sentinel (>= len(db)) candidate indices are tolerated:
    duplicates keep one copy ranked by index, sentinels rank last.
    ``norms`` (``metric="cosine"`` only, optional): ``(query norms [Q],
    db row norms [N])`` in float64 where the caller keeps them.
    """
    cand_idx = np.asarray(cand_idx, dtype=np.int64)
    n_q, m = cand_idx.shape
    if m < k:
        raise ValueError(f"need >= {k} candidates, got {m}")
    valid = cand_idx < db.shape[0]
    safe_idx = np.where(valid, cand_idx, 0)
    # the [Qc, m, D] float64 gather+diff temporaries, a block at a time
    d = np.empty((n_q, m))
    chunk = _block_rows(m * db.shape[1])
    for lo in range(0, n_q, chunk):
        part = safe_idx[lo : lo + chunk]
        d[lo : lo + chunk] = _pairwise_f64(
            queries[lo : lo + chunk], db[part], metric,
            None if norms is None else (norms[0][lo : lo + chunk],
                                        norms[1][part]))
    d = np.where(valid, d, np.inf)
    # kill duplicate candidates (keep lowest occurrence by (d, idx) order)
    srt = np.lexsort((cand_idx, d), axis=-1)
    d_sorted = np.take_along_axis(d, srt, axis=-1)
    i_sorted = np.take_along_axis(cand_idx, srt, axis=-1)
    dup = np.zeros_like(i_sorted, dtype=bool)
    dup[:, 1:] = i_sorted[:, 1:] == i_sorted[:, :-1]
    d_sorted = np.where(dup, np.inf, d_sorted)
    srt2 = np.lexsort((i_sorted, d_sorted), axis=-1)[:, :k]
    return (
        np.take_along_axis(d_sorted, srt2, axis=-1),
        np.take_along_axis(i_sorted, srt2, axis=-1),
    )


def refine_shared_exact(
    db: np.ndarray,
    queries: np.ndarray,
    positions: np.ndarray,
    k: int,
    metric: str = "l2",
) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`refine_exact` where every query shares ONE candidate set
    (a 1-D position array) — the IVF certified-fallback shape, where a
    flagged query re-scores every live row.  Bitwise-identical to
    ``refine_exact(db, queries, np.broadcast_to(positions, (Q, M)), k)``
    (it IS that call; the broadcast view materializes only per chunk
    inside refine_exact's gather, never as a [Q, M] index array)."""
    positions = np.asarray(positions, dtype=np.int64).reshape(-1)
    cand = np.broadcast_to(positions, (queries.shape[0], positions.shape[0]))
    return refine_exact(db, queries, cand, k, metric)


def vote_exact(labels: np.ndarray, c: np.ndarray, temperature: float,
               classes_out: int) -> Tuple[np.ndarray, np.ndarray]:
    """The weighted vote in float64 from scored neighbours: ``labels``
    [F, k] and cosine distances ``c`` [F, k] in rank order; every
    neighbour adds ``exp((1 - c) / temperature)`` to its label's total
    (one at +inf, padding, adds nothing).  Returns (classes [F,
    classes_out] int32, totals [F, classes_out] float64): the classes with
    a total above 0 in lexicographic (-total, class) order, padded with
    class -1 at total 0.  A class's total is summed one neighbour at a
    time in rank order, so equal rows under equal labels give equal
    totals to the bit."""
    labels = np.asarray(labels, np.int64)
    n_f, k = labels.shape
    w = np.exp((1.0 - c) / float(temperature))
    w[~np.isfinite(c)] = 0.0
    same = labels[:, :, None] == labels[:, None, :]
    totals = np.zeros((n_f, k))
    for b in range(k):
        totals += np.where(same[:, :, b], w[:, b, None], 0.0)
    stands = ~(same & np.tri(k, k, -1, dtype=bool)).any(-1) & (totals > 0)
    neg = np.where(stands, -totals, np.inf)
    cls = np.where(stands, labels, np.iinfo(np.int64).max)
    if classes_out > k:
        pad = ((0, 0), (0, classes_out - k))
        neg = np.pad(neg, pad, constant_values=np.inf)
        cls = np.pad(cls, pad, constant_values=np.iinfo(np.int64).max)
    order = np.lexsort((cls, neg), axis=-1)[:, :classes_out]
    neg = np.take_along_axis(neg, order, axis=-1)
    cls = np.take_along_axis(cls, order, axis=-1)
    there = neg < np.inf
    return (np.where(there, cls, -1).astype(np.int32),
            np.where(there, -neg, 0.0))


def revote_exact(db: np.ndarray, queries: np.ndarray, cand_idx: np.ndarray,
                 labels: np.ndarray, k: int, temperature: float,
                 classes_out: int, norms=None
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The float64 answer of the weighted vote for queries whose device
    vote was flagged: the exact lexicographic (cosine distance, index)
    top-k among each query's candidates ``cand_idx`` [F, m >= k]
    (:func:`refine_exact` on the rows and queries as given, ``norms``
    their float64 norms), then :func:`vote_exact` over those k.  Returns
    (classes, totals, the k members' indices [F, k])."""
    c, members = refine_exact(db, queries, cand_idx, k, "cosine", norms)
    classes, totals = vote_exact(
        labels[np.minimum(members, labels.shape[0] - 1)], c, temperature,
        classes_out)
    return classes, totals, members
