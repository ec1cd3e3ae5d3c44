"""Certified-exact KNN: an approximate coarse pass made *provably* exact.

The exact tiled path (ops.topk.knn_search_tiled) is selection-bound: the
distance matmul is ~1% of its runtime, the per-tile ``lax.top_k`` the rest.
TPU hardware has a much faster selector — the bin-reduction behind
``lax.approx_max_k`` (the XLA ApproxTopK op; see the TPU-KNN paper in
PAPERS.md) — but it can *miss* true neighbors, and a miss is invisible to
two-phase refinement (ops.refine can only reorder candidates it was given).

This module closes the gap with a certificate:

1. **coarse**: approx_max_k fetches k + margin candidates per query at
   near-MXU speed;
2. **refine**: ops.refine re-scores candidates in float64 → provisional
   exact top-k and its kth distance d_k;
3. **certify**: one more matmul-bound pass counts, per query, the database
   points with float32 distance below a threshold, where the float32
   error bound tol (``certification_tolerance``) sets the slack.  The
   sharded driver (parallel.sharded._certify_counted) picks the
   threshold ADAPTIVELY: the refine knows every candidate's float64
   distance, so it counts against the midpoint of the first
   inter-neighbor gap at rank j >= k that clears 2*tol — count <= j
   proves no outsider sits at or below the j-th candidate, and ranks
   <= j are float64-refined.  (A fixed ``d_k + tol`` threshold
   false-alarms whenever ANY point lies within tol of d_k — measured
   ~2.4% of SIFT1M queries; a clearable gap inside the margin window
   almost always exists, so the adaptive form certifies those.)
4. **fallback**: queries failing certification (misses OR gapless tie
   windows) rerun through the exact tiled path.  Soundness never depends
   on the false-alarm rate; only speed does.

Net effect: exact results (recall@k = 1.0 by construction) at the
approximate path's throughput, with a fallback whose cost scales with the
actual miss/alarm rate instead of the worst case.

The reference has no analogue — its selection is a full std::sort per
query (knn_mpi.cpp:323,366); this replaces it with MXU-speed selection
plus a proof.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from knn_tpu import obs
from knn_tpu.obs import names as _mn
from knn_tpu.ops.refine import norms_rows, refine_exact
from knn_tpu.ops.topk import knn_search_tiled


@functools.partial(jax.jit, static_argnames=("tile",))
def count_below(
    db: jax.Array,
    queries: jax.Array,
    thresholds: jax.Array,
    *,
    tile: int = 131072,
    n_valid=None,
) -> jax.Array:
    """Per query, how many database rows have squared-L2 distance strictly
    below the query's threshold — one matmul-bound pass, no selection.

    [Q] int32.  Distances are computed exactly like the fast path
    (float32 expanded square), so thresholds must already include any
    tolerance the caller wants.  Rows at index >= ``n_valid`` (may be
    traced) are padding and never counted — the db-shard contract shared
    with ops.topk.knn_search.
    """
    n = db.shape[0]
    tile = min(tile, n)  # never pad a small db up to a full default tile
    limit = n if n_valid is None else jnp.minimum(n, n_valid)
    n_tiles = -(-n // tile)
    padded = n_tiles * tile
    if padded != n:
        db = jnp.pad(db, ((0, padded - n), (0, 0)))
    tiles = db.reshape(n_tiles, tile, db.shape[-1])

    q32 = queries.astype(jnp.float32)
    q_norm = jnp.sum(q32 * q32, axis=-1, keepdims=True)
    thr = thresholds[:, None].astype(jnp.float32)

    def step(acc, args):
        tile_idx, t = args
        t32 = t.astype(jnp.float32)
        t_norm = jnp.sum(t32 * t32, axis=-1)[None, :]
        qt = lax.dot_general(
            q32, t32, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST,
        )
        d = jnp.maximum(q_norm + t_norm - 2.0 * qt, 0.0)
        col = tile_idx * tile + lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        hit = (d < thr) & (col < limit)
        return acc + jnp.sum(hit.astype(jnp.int32), axis=-1), None

    acc0 = jnp.zeros(queries.shape[0], dtype=jnp.int32)
    acc, _ = lax.scan(step, acc0, (jnp.arange(n_tiles, dtype=jnp.int32), tiles))
    return acc


def _approx_candidates(
    queries: jax.Array, db: jax.Array, m: int, *, compute_dtype=None,
    recall_target: float = 0.99,
) -> jax.Array:
    """[Q, m] candidate indices from the hardware bin-reduction selector
    (ops.topk.knn_search_approx: MIPS-form squared L2 + approx_max_k)."""
    from knn_tpu.ops.topk import knn_search_approx

    _, idx = knn_search_approx(
        queries, db, m,
        recall_target=recall_target,
        compute_dtype=jnp.float32 if compute_dtype is None else compute_dtype,
    )
    return idx


#: float32 squared-distance error bound factor: |err| <~ eps * (||q||^2+||t||^2)
#: with a safety factor for the matmul reduction tree.
_F32_EPS = float(np.finfo(np.float32).eps)


def certification_tolerance(
    queries_np: np.ndarray, db_np: np.ndarray,
    *, db_norm_max: Optional[float] = None, q_norm: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Per-query additive slack [Q] covering the float32 distance error in
    the certificate's count pass (see module docstring, step 3).

    ``db_norm_max`` / ``q_norm`` let batched callers hoist the float64
    norm reductions out of their batch loop."""
    if q_norm is None:
        q_norm = (queries_np.astype(np.float64) ** 2).sum(-1)
    if db_norm_max is None:
        db_norm_max = float((db_np.astype(np.float64) ** 2).sum(-1).max())
    return 8.0 * _F32_EPS * (q_norm + db_norm_max)


def host_exact_knn(
    db_np: np.ndarray, q_np: np.ndarray, k: int, *, tile: Optional[int] = None,
    q_chunk: int = 8, metric: str = "l2", norms=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Unconditional last-resort exact KNN: tiled float64 direct-difference
    full scan on host (no expanded-square cancellation, no approximation,
    no certificate needed).  O(Q*N*D) host FLOPs — only for the handful of
    queries that fail re-certification after the widened fallback.
    ``metric="dot"`` scans by the negated float64 inner product instead
    (each product of two float32 values is exact in float64), and
    ``metric="cosine"`` by ``1 - q.t / (|q| |t|)`` from the same
    products (``norms``: ``(query norms [Q], row norms [N])`` in float64
    where the caller keeps them, ops.refine.row_norms_f64's otherwise;
    a zero norm has cosine 0 to everything)."""
    from knn_tpu.ops.refine import cosine_distance, row_norms_f64

    n = db_np.shape[0]
    n_q = q_np.shape[0]
    k = min(k, n)
    if metric == "cosine" and norms is None:
        norms = row_norms_f64(q_np), row_norms_f64(db_np)
    if tile is None:
        # bound the [q_chunk, tile, D] float64 broadcast temporaries at a
        # fixed ~128 MB budget regardless of dimensionality
        tile = max(128, (1 << 24) // (q_chunk * max(1, db_np.shape[1])))
    bd = np.full((n_q, k), np.inf)
    bi = np.full((n_q, k), np.iinfo(np.int64).max, dtype=np.int64)
    for qlo in range(0, n_q, q_chunk):
        qf = q_np[qlo : qlo + q_chunk].astype(np.float64)
        cd, ci = bd[qlo : qlo + q_chunk], bi[qlo : qlo + q_chunk]
        for lo in range(0, n, tile):
            t = db_np[lo : lo + tile].astype(np.float64)
            if metric == "dot":
                dt = -(qf[:, None, :] * t[None, :, :]).sum(-1)
            elif metric == "cosine":
                dt = cosine_distance(
                    (qf[:, None, :] * t[None, :, :]).sum(-1),
                    norms[0][qlo : qlo + q_chunk, None]
                    * norms[1][None, lo : lo + tile])
            else:
                dt = ((qf[:, None, :] - t[None, :, :]) ** 2).sum(-1)
            it = np.broadcast_to(
                np.arange(lo, lo + t.shape[0], dtype=np.int64)[None, :], dt.shape
            )
            alld = np.concatenate([cd, dt], axis=-1)
            alli = np.concatenate([ci, it], axis=-1)
            srt = np.lexsort((alli, alld), axis=-1)[:, :k]
            cd = np.take_along_axis(alld, srt, -1)
            ci = np.take_along_axis(alli, srt, -1)
        bd[qlo : qlo + q_chunk], bi[qlo : qlo + q_chunk] = cd, ci
    return bd, bi


def repair_widen(m: int, max_widen: int) -> int:
    """The width of :func:`repair_uncertified`'s exact re-select: the
    ONE home of it, for a caller that launches the re-select itself
    ahead of the repair (the join's pipeline)."""
    return min(max(2 * m, m + 64), max_widen)


def _columns_without(idx: np.ndarray, own: np.ndarray) -> np.ndarray:
    """The columns ``[B, w - 1]`` of ``idx`` ``[B, w]`` that are left, in
    order, when each row loses the one entry equal to ``own[b]``; a row
    that holds no such entry loses its last."""
    keep = np.argsort(idx == own[:, None], axis=1, kind="stable")[:, :-1]
    return np.sort(keep, axis=1)


#: the span a caller holds open around :func:`repair_uncertified`, and
#: the two phases of the host's half of it: names of their profiler
#: annotations (``knn.`` before them) and of the spans recorded once
#: where ``certified.repair`` is recorded once (a call; a block of the
#: bulk self-join), children of it
REPAIR_SPAN = "certified.repair"
PHASE_REFINE = "certified.repair.refine"
PHASE_HOST_SCAN = "certified.repair.host_scan"


def _tell_repair(secs: dict, rows: int, selected: int, proven: int,
                 scanned: int, masked: bool = False) -> None:
    """Record what one :func:`repair_uncertified` did on the host, under
    the trace id of the span the caller holds open: both phases (0.0
    where one did not run, so a reader never finds a series missing),
    the candidates the re-select handed over (``selected``) by whether
    the refine gathered them (``rows``) or :func:`_within_reach` left
    them out, and the queries by what settled them.  ``masked``: a
    filtered call's repair, said on the refine's span."""
    tid = obs.current_span().trace_id
    obs.record_span(PHASE_REFINE, tid, secs.get("refine_s", 0.0),
                    parent=REPAIR_SPAN, rows=rows, selected=selected,
                    **({"masked": True} if masked else {}))
    obs.record_span(PHASE_HOST_SCAN, tid, secs.get("host_scan_s", 0.0),
                    parent=REPAIR_SPAN, queries=scanned)
    obs.counter(_mn.REPAIR_REFINE_ROWS, outcome="refined").inc(rows)
    obs.counter(_mn.REPAIR_REFINE_ROWS, outcome="thinned").inc(
        selected - rows)
    obs.counter(_mn.REPAIR_QUERIES, outcome="proven").inc(proven)
    obs.counter(_mn.REPAIR_QUERIES, outcome="host_scan").inc(scanned)


#: how many tolerances past the k-th float32 score a candidate of the
#: widened re-select can lie and still rank among the exact top-k: one
#: for its own score's error, one for the k-th's (the proof is step 1's
#: in :func:`repair_uncertified`).  The proof's factor, not a knob: the
#: tests set it to +inf (every candidate refined: the path before the
#: thinning) and to 0 (the float32 order trusted: wrong answers)
_REACH_TOLS = 2.0


def _within_reach(fs: np.ndarray, fi: np.ndarray, k: int, tol: np.ndarray,
                  exclude: Optional[np.ndarray], norms) -> np.ndarray:
    """The candidates ``[B, keep]`` of the widened re-select (``fs``
    ascending float32 scores, ``fi`` their row ids, both ``[B, widen]``)
    that the float64 refine has to score, ``k <= keep <= widen``: a
    self-join's own row (``exclude``) dropped by id first, then the
    longest prefix, over the call's queries, of candidates whose score
    lies within ``_REACH_TOLS * tol`` (``tol`` ``[B]``, the pair slack
    in it) of the k-th left.  Every candidate past it is provably
    outside its query's exact top-k (:func:`repair_uncertified`, step
    1).  A k-th score that is not finite (a filtered query whose valid
    rows ran out) puts nothing past reach: the comparison is false.
    ``norms`` (a cosine call's ``(query norms, row norms)``, else None):
    a row of zero norm is selected at half its cosine distance, so the
    k-th score is taken among the candidates of nonzero norm."""
    if exclude is not None:
        left = _columns_without(fi, exclude)
        fs = np.take_along_axis(fs, left, axis=1)
        fi = np.take_along_axis(fi, left, axis=1)
    ranked = fs
    if norms is not None:
        ranked = np.sort(np.where(
            norms[1][np.minimum(fi, norms[1].size - 1)] > 0, fs, np.inf),
            axis=1)
    t_k = ranked[:, min(k, fs.shape[1]) - 1]
    reach = ~(fs > (t_k + _REACH_TOLS * tol)[:, None])
    return fi[:, : max(k, int(np.flatnonzero(reach.any(axis=0))[-1]) + 1)]


def _host_scan(d, i, k, sb, rank_q, db_np, metric, norms, valid_rows_fn,
               own) -> None:
    """Step 3 of :func:`repair_uncertified`: the float64 host scan of the
    queries ``sb`` (positions in ``d`` / ``i``), written in place, in the
    form the call takes: ``own`` (a self-join: each query's own row id,
    scanned for k + 1 and dropped), a filtered call's ``valid_rows_fn``
    (each query's valid rows alone, a cosine call's kept norms cut to
    them), or the plain scan."""
    if own is not None:
        hd, hi = host_exact_knn(db_np, rank_q[sb], k + 1, metric=metric)
        left = _columns_without(hi, own)
        d[sb] = np.take_along_axis(hd, left, axis=1)
        i[sb] = np.take_along_axis(hi, left, axis=1)
    elif valid_rows_fn is None:
        d[sb], i[sb] = host_exact_knn(db_np, rank_q[sb], k, metric=metric,
                                      norms=norms_rows(norms, sb))
    else:
        for pos in sb:
            rows = valid_rows_fn(pos)
            d[pos], i[pos] = np.inf, np.iinfo(np.int64).max
            if rows.size:
                hd, hi = host_exact_knn(
                    db_np[rows], rank_q[pos][None], k, metric=metric,
                    norms=None if norms is None else (
                        norms[0][pos : pos + 1], norms[1][rows]))
                d[pos, : hd.shape[1]] = hd[0]
                i[pos, : hd.shape[1]] = rows[hi[0]]


def repair_uncertified(
    d: np.ndarray,
    i: np.ndarray,
    k: int,
    m: int,
    bad: np.ndarray,
    q_np: np.ndarray,
    db_np: np.ndarray,
    *,
    select_fn,
    max_widen: int,
    db_norm_max: Optional[float] = None,
    metric: str = "l2",
    pair_slack: float = 0.0,
    dot_shift: float = 0.0,
    rank_queries: Optional[np.ndarray] = None,
    norms=None,
    valid_rows_fn=None,
    exclude: Optional[np.ndarray] = None,
) -> dict:
    """Shared fallback repair for both certified pipelines (single-device
    :func:`knn_search_certified` and the sharded
    ``ShardedKNN.search_certified``) — ONE source of truth for the exactness
    escalation:

    1. widened exact-selector re-select (``widen = min(max(2m, m+64),
       max_widen)``), timed by the caller's ``select_fn`` as the span
       ``certified.repair.reselect``, + float64 refine
       (``ops.refine.refine_exact``) of the candidates whose float32
       score can still reach the top-k (:func:`_within_reach`), timed
       here as the phase ``certified.repair.refine``: with the scores
       ``fs`` ascending, ``t_k`` the k-th of them and tol the bound on
       a score's error that step 2 uses, a candidate j with ``fs[j] >
       t_k + 2 tol`` is out before a row is gathered.  The k first
       candidates are k distinct rows of true distance <= fs + tol <=
       t_k + tol; j has true distance >= fs[j] - tol, strictly over
       all k of them; so k rows rank strictly before j in (distance,
       index) order whatever the indices, and the refine's top-k over
       the candidates kept is its top-k over all of them, value for
       value (each pair's float64 sum is its own).  The refine takes
       the longest kept prefix over the call's flagged queries, one
       rectangular slice; equal scores (dense ties) and a k-th score
       that is not finite keep everything;
    2. re-certification via the widened selection's own exclusion value:
       every db row NOT selected has f32 score >= the widen-th selected
       score v_w, hence true distance >= v_w - tol — so
       ``d_k + tol < v_w`` proves the repair exact with ZERO extra
       database passes (this replaced a count-below pass plus a frequent
       float64 host scan: the count certificate false-alarmed whenever
       any point sat within tol of d_k, which at k=100/1M happens for
       ~1 query per sweep, each costing ~1s of host scan); no span of
       its own: it stays the self time of the caller's
       ``certified.repair``, with the original indices' copy and the
       count of genuine misses;
    3. unconditional float64 host scan (:func:`host_exact_knn`) only for
       queries whose k-th/widen-th gap is inside the f32 tolerance
       (heavy duplicate ties) — structurally rare; timed here as the
       phase ``certified.repair.host_scan``.

    Both phases are ``knn.<phase>`` profiler annotations and are
    recorded once a call of this function, 0.0 where one did not run,
    as children of ``certified.repair`` under the trace id of the span
    the caller holds open (``obs.current_span()``), with
    ``knn_tpu_repair_queries_total{outcome}``: ``proven`` by step 2,
    ``host_scan`` by step 3 (the ``host_exact_queries`` of the stats).

    ``metric="dot"`` (inner-product placements, parallel.sharded: rows
    and queries arrive norm-augmented, ``dot_shift`` = M, the largest
    squared row norm): the refine and the host scan rank by the float64
    NEGATED INNER PRODUCT s = -q.t on the arrays as given (the query's
    appended column is an exact zero), so ``d`` holds s at the repaired
    rows.  The selection still scores in the augmented squared-L2 space,
    where a row's exact value is D' = |q|^2 + M + 2 s + c_t with |c_t|
    <= ``pair_slack`` / 2 (the appended column's float32 rounding).  So
    step 2 compares there: a row NOT selected has D'(u) >= v_w - tol,
    hence 2 s(u) >= v_w - tol - pair_slack / 2 - |q|^2 - M, and
    ``|q|^2 + M + 2 s_k + tol + pair_slack < v_w`` proves s(u) > s_k
    with pair_slack / 2 to spare.  Step 1's band there: a first-k
    candidate has 2 s <= t_k + tol + pair_slack / 2 - |q|^2 - M, j has
    2 s(j) >= fs[j] - tol - pair_slack / 2 - |q|^2 - M, so ``fs[j] >
    t_k + 2 tol + pair_slack`` puts j out, and the band taken, 2 (tol +
    pair_slack), covers it.

    ``metric="cosine"`` (cosine placements: ``q_np`` holds the float32
    UNIT queries the selection runs on, ``rank_queries`` the queries AS
    GIVEN, ``db_np`` the rows as given, ``norms`` their float64 norms):
    the refine and the host scan rank by the float64 cosine distance c
    = 1 - q.t / (|q| |t|) of the values as given, so ``d`` holds c at
    the repaired rows.  The selection scores the placed unit rows,
    where a row's exact value is D' = 2 c + p_t with |p_t| <
    ``pair_slack`` (the normalisation's float32 rounding on both sides,
    parallel.sharded.COS_UNIT_SLACK).  A row NOT selected has D'(u) >=
    v_w - tol, hence 2 c(u) > v_w - tol - pair_slack, and ``2 c_k + tol
    + pair_slack < v_w`` proves c(u) > c_k.  Step 1's band there: a
    first-k candidate has 2 c < t_k + tol + pair_slack, j has 2 c(j) >
    fs[j] - tol - pair_slack, so ``fs[j] > t_k + 2 (tol + pair_slack)``
    puts j out: the band taken.  A row of zero norm is
    placed as it is, at D' = |q^|^2 <= 1 + 2^-22, and has c = 1: left
    out of a selection that this inequality proves, it has v_w <= D' +
    tol and so 2 c_k < 1 + 2^-22, c_k < c.  SELECTED, it stands at half
    its cosine distance, so step 1 takes ``t_k`` among the candidates
    of nonzero norm (``norms``, which a cosine call therefore hands
    over): those k rows have 2 c < t_k + tol + pair_slack as before,
    and a zero row past the band has fs <= 1 + 2^-22 + tol, hence all k
    of them 2 c < 2 = twice its own.

    ``valid_rows_fn(position) -> ascending row ids`` (a filtered call,
    parallel.sharded: ``select_fn`` then selects among each query's
    valid rows only, +inf and the sentinel once they run out): a
    widened selection that ran out (``v_w`` = +inf) holds EVERY valid
    row, so nothing is excluded and step 2 proves the repair whatever
    the k-th distance, +inf included; step 3's scan reads the query's
    valid rows alone (:func:`host_exact_knn` over that gather) and pads
    a short answer with +inf and the int64 sentinel.  Step 1 there: a
    ``t_k`` of +inf (fewer than k valid rows) keeps every candidate; a
    finite one drops the +inf sentinels past reach, which rank last in
    the refine anyway.  Under ``metric="cosine"`` nothing else changes:
    the slack bounds a PAIR of placed rows, so every inequality above
    holds among the valid rows as it does among all; the host scan
    hands :func:`host_exact_knn` the kept norms of the rows it gathers.

    ``exclude`` (int ``[B]``, a self-join: the row each flagged query
    IS, parallel.sharded's ``_SelfJoinCall``) takes that one row out by
    id, never by distance: it is dropped from the widened selection
    before the refine (``select_fn`` selected among all rows, so the
    ``widen``-th score still bounds every row left out, and step 2 is
    the inequality it was over one candidate fewer; step 1's ``t_k`` is
    the k-th score of the candidates left), and step 3 scans
    for k + 1 and drops it there (or the last, where k + 1 rows at the
    same distance come before it in index order).  An exact copy of the
    query stays, at distance 0.

    ``select_fn(q_bad [B,D], widen) -> (f32 scores [B, widen] ascending,
    candidate indices [B, widen])``.
    Mutates ``d``/``i`` in place at rows ``bad``; returns a stats dict:
    ``fallback_genuine_misses`` (repair CHANGED the answer — the coarse
    pass really missed a neighbor), ``fallback_false_alarms`` (repair
    reproduced the original answer — the certificate's tolerance cried
    wolf), and ``host_exact_queries`` (escalations to the float64 host
    scan) when nonzero.  The miss/alarm split is a measurement
    for the tuner: it tells it whether to grow the margin
    (misses) or tighten the tolerance (alarms).
    """
    if metric == "cosine" and norms is None:
        raise ValueError("a cosine repair needs the rows' float64 norms")
    secs = {}
    if not bad.size:
        _tell_repair(secs, 0, 0, 0, 0)
        return {"fallback_genuine_misses": 0, "fallback_false_alarms": 0}
    orig_i = i[bad].copy()
    fs, fi = select_fn(q_np[bad], repair_widen(m, max_widen))
    fs = np.asarray(fs, dtype=np.float64)
    fi = np.asarray(fi)
    rank_q = q_np if rank_queries is None else rank_queries
    q_norm = (q_np[bad].astype(np.float64) ** 2).sum(-1)
    tol = certification_tolerance(
        q_np[bad], db_np, db_norm_max=db_norm_max, q_norm=q_norm
    ) + pair_slack
    with obs.trace.phase(secs, "refine_s", PHASE_REFINE):
        fi = _within_reach(fs, fi, k, tol, exclude, norms)
        fd2, fi2 = refine_exact(db_np, rank_q[bad], fi, k, metric,
                                norms_rows(norms, bad))
        d[bad], i[bad] = fd2, fi2
    # the k-th value in the selection's own space (docstring)
    d_k = fd2[:, k - 1]
    if metric == "dot":
        d_k = q_norm + dot_shift + 2.0 * d_k
    elif metric == "cosine":
        d_k = 2.0 * d_k
    v_w = fs[:, -1]  # exclusion value of the widened f32 selection
    unproven = d_k + tol >= v_w
    if valid_rows_fn is not None:
        unproven &= np.isfinite(v_w)
    still = np.flatnonzero(unproven)
    if still.size:
        sb = bad[still]
        own = None if exclude is None else exclude[still]
        with obs.trace.phase(secs, "host_scan_s", PHASE_HOST_SCAN):
            _host_scan(d, i, k, sb, rank_q, db_np, metric, norms,
                       valid_rows_fn, own)
    n_bad, host_exact = int(bad.size), int(still.size)
    genuine = int((i[bad] != orig_i).any(axis=-1).sum())
    out = {
        "fallback_genuine_misses": genuine,
        "fallback_false_alarms": n_bad - genuine,
    }
    if host_exact:
        out["host_exact_queries"] = host_exact
    _tell_repair(secs, int(fi.size),
                 n_bad * (fs.shape[1] - (exclude is not None)),
                 n_bad - host_exact, host_exact,
                 masked=valid_rows_fn is not None)
    return out


def pallas_candidate_fn(**knobs):
    """A ``candidate_fn`` for :func:`knn_search_certified` that runs the
    fused Pallas kernel's coarse pass (ops.pallas_knn) at any supported
    precision — including the int8 MXU arm (``precision="int8"``, which
    quantizes both sides per call via ops.quantize).

    The count-below certificate is COARSE-PRECISION-INDEPENDENT: step 3
    counts EVERY database row against the float64-refined threshold, so
    a quantized (or outright wrong) coarse pass can raise the fallback
    rate but can never cost exactness — no threshold widening by the
    quantization bound ε is needed on this path, unlike the one-pass
    exclusion-bound certificate (parallel.sharded), whose lb lives in
    kernel-score space and therefore widens by ε there."""
    from knn_tpu.ops.pallas_knn import pallas_knn_candidates

    def fn(q, db, m):
        return pallas_knn_candidates(q, db, m, **knobs)

    return fn


def knn_search_certified(
    queries,
    db,
    k: int,
    *,
    margin: int = 28,
    tile: int = 131072,
    compute_dtype=None,
    recall_target: float = 0.99,
    candidate_fn=None,
) -> Tuple[np.ndarray, np.ndarray, dict]:
    """Exact lexicographic (distance, index) top-k via the certified
    approximate pipeline.  Returns (dists_f64 [Q, k], idx [Q, k], stats).

    ``candidate_fn(queries, db, m) -> [Q, m] indices`` overrides the coarse
    pass (e.g. with the Pallas bin-min kernel — see
    :func:`pallas_candidate_fn`, incl. the int8 arm); default is the
    ApproxTopK selector.

    ``stats`` reports ``fallback_queries`` — how many queries failed
    certification and reran exactly (0 in the common case; correctness
    never depends on it).
    """
    queries_np = np.asarray(queries, dtype=np.float32)
    db_np = np.asarray(db, dtype=np.float32)
    n_q = queries_np.shape[0]
    n = db_np.shape[0]
    if k > n:
        raise ValueError(f"k={k} > n_db={n}")
    m = min(k + margin, n)

    q_j = jnp.asarray(queries_np)
    db_j = jnp.asarray(db_np)

    if candidate_fn is None:
        cand = _approx_candidates(
            q_j, db_j, m, compute_dtype=compute_dtype, recall_target=recall_target
        )
    else:
        cand = candidate_fn(q_j, db_j, m)
    d, i = refine_exact(db_np, queries_np, np.asarray(cand), k)

    # certification threshold: kth true distance plus the f32 error bound
    db_norm_max = float((db_np.astype(np.float64) ** 2).sum(-1).max())
    thresholds = d[:, k - 1] + certification_tolerance(
        queries_np, db_np, db_norm_max=db_norm_max
    )
    counts = np.asarray(count_below(db_j, q_j, jnp.asarray(thresholds), tile=tile))

    bad = np.flatnonzero(counts > k)
    repair = repair_uncertified(
        d, i, k, m, bad, queries_np, db_np,
        select_fn=lambda qb, widen: knn_search_tiled(
            jnp.asarray(qb), db_j, widen, "l2", train_tile=min(tile, n)
        ),
        max_widen=n,
        db_norm_max=db_norm_max,
    )
    stats = {"fallback_queries": int(bad.size),
             "certified": n_q - int(bad.size), **repair}
    return d, i, stats
