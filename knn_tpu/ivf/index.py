"""The approximate-first IVF tier with a certified escape hatch.

Brute force streams every db byte past every query; where the row
stream bounds a configuration, the only way past it is to stream
fewer bytes.  This tier prunes the stream with an
inverted file — and unlike every off-the-shelf IVF, a per-query
certificate DETECTS when the probe missed and repairs it with the
existing exact fallback, so recall@k is measured and gateable, never
silently lost.

How the pieces map onto machinery that already exists:

- **Coarse quantizer** (:mod:`knn_tpu.ivf.kmeans`): seeded Lloyd, SPMD
  assign via the sharded k=1 search, host f64 segment-mean update.
- **List-major placement**: corpus rows permuted into
  centroid-contiguous blocks.  A search gathers ONLY the probed lists'
  extents (plus their delta tails) into one segment, pads it to a fixed
  ladder rung, and feeds the UNMODIFIED host-tier segment program
  (:func:`knn_tpu.parallel.sharded.segment_search_program`) — the
  traced ``n_valid`` operand masks the pad, so probing shrinks
  streamed db bytes with no new kernel and no recompile per probe set.
  ``selector="pallas"`` runs the same gathered block through
  :func:`knn_tpu.ops.pallas_knn.knn_search_pallas` (streaming/fused ×
  f32/bf16x3/int8), equally unmodified.
- **Certificate** (the PR 3 bound extended to centroid residuals): for
  any row ``x`` in an unprobed list ``l`` with centroid ``c_l`` and
  residual radius ``r_l = max ||x - c_l||``, the triangle inequality
  gives ``||q - x|| >= ||q - c_l|| - r_l``.  If the refined k-th
  distance beats that bound for EVERY unprobed non-empty list (and the
  within-block float32 tolerance check passes), the probed answer is
  PROVABLY the exact answer.  Otherwise the query is repaired by an
  exact f64 re-score of all live rows (``ops.refine``) — so the final
  ``(d, i)`` is ALWAYS anchored in :func:`knn_tpu.ops.refine.
  refine_exact` over the canonical corpus, which makes results
  selector-, precision-, and kernel-independent by construction
  (``nprobe == ncentroids`` reproduces exact brute force bitwise).
- **Mutability**: per-list delta tails absorb inserts (PR 13
  discipline: epoch visibility, id-based tombstones, budgeted refusal),
  and compaction re-clusters the survivors on a background thread with
  an atomic snapshot swap (docs/INDEX.md).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from knn_tpu import obs
from knn_tpu.index.artifact import MutationBudgetError
from knn_tpu.ivf.kmeans import train_kmeans
from knn_tpu.ops.certified import certification_tolerance
from knn_tpu.ops.refine import refine_exact, refine_shared_exact

#: coarse selectors this tier accepts: "exact" routes the gathered
#: block through the host-tier segment program (compute-dtype f32, the
#: counted-certificate tolerance below assumes it); "pallas" routes it
#: through knn_search_pallas (which certifies itself over the block,
#: any precision/kernel)
SELECTORS = ("exact", "pallas")

#: relative slack on the unprobed-list lower bound: the certificate
#: compares f64 values computed from exactly-representable f32 inputs,
#: so a sliver of multiplicative headroom dwarfs the f64 rounding while
#: erring ONLY toward extra fallback (never a wrong certification)
_BOUND_SLACK = 1e-9

_ENV_NPROBE = "KNN_TPU_IVF_NPROBE"
_ENV_NCENTROIDS = "KNN_TPU_IVF_NCENTROIDS"
_ENV_TRAIN_ITERS = "KNN_TPU_IVF_TRAIN_ITERS"
_ENV_SEED = "KNN_TPU_IVF_SEED"


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    return default if raw in (None, "") else int(raw)


class _IVFSnapshot:
    """One immutable view of the index: searches pin a snapshot, so
    compaction swaps are atomic from a request's point of view."""

    __slots__ = (
        "epoch", "ncentroids", "centroids", "cent64", "residuals",
        "list_base_pos", "list_sizes", "tail_assign", "n_base",
        "all_rows", "all_ids", "live_mask", "live_positions", "n_live",
        "_pos_cache", "_norm2",
    )

    def __init__(self, **kw):
        for name in self.__slots__:
            setattr(self, name, kw.get(name))
        self._pos_cache = {}
        self._norm2 = None

    @property
    def n_all(self) -> int:
        return self.all_rows.shape[0]

    def norm2(self) -> np.ndarray:
        """[n_all] f64 squared row norms (lazy, shared by every group's
        within-block tolerance)."""
        if self._norm2 is None:
            r = self.all_rows.astype(np.float64)
            self._norm2 = np.einsum("nd,nd->n", r, r)
        return self._norm2

    def positions_for(self, key: Tuple[int, ...]) -> np.ndarray:
        """Sorted canonical positions of every LIVE row in the probed
        lists ``key`` — base extents plus matching delta-tail rows,
        tombstones filtered.  Sorted ascending so block-local
        lexicographic tie order equals canonical tie order."""
        hit = self._pos_cache.get(key)
        if hit is not None:
            return hit
        parts = [self.list_base_pos[l] for l in key]
        if self.tail_assign.size:
            sel = np.isin(self.tail_assign, np.asarray(key, np.int64))
            parts.append(self.n_base + np.flatnonzero(sel))
        pos = (np.concatenate(parts) if parts
               else np.empty(0, np.int64)).astype(np.int64)
        pos = np.sort(pos[self.live_mask[pos]])
        self._pos_cache[key] = pos
        return pos


class IVFIndex:
    """A mutable, certified IVF placement over one canonical corpus.

    ``search_certified`` returns ``(d, ids, stats)`` with ``d`` the
    exact squared-L2 float64 distances (``return_sqrt=True`` for true
    Euclidean) — exact for EVERY query, because certified probes are
    proven exact and flagged probes are repaired.  L2 metric only: the
    residual bound is a Euclidean triangle inequality.
    """

    def __init__(
        self,
        train,
        ids=None,
        *,
        mesh,
        k: int,
        ncentroids: Optional[int] = None,
        nprobe: Optional[int] = None,
        train_iters: Optional[int] = None,
        seed: Optional[int] = None,
        metric: str = "l2",
        margin: int = 8,
        train_tile: Optional[int] = None,
        seg_min_rows: int = 256,
        delta_max_rows: int = 65536,
        compact_tail_rows: Optional[int] = None,
        compact_tombstones: Optional[int] = None,
    ):
        if metric.lower() != "l2":
            raise ValueError(
                f"IVFIndex supports metric='l2' only (the residual "
                f"certificate is a Euclidean triangle inequality), got "
                f"{metric!r}")
        base = np.ascontiguousarray(np.asarray(train, np.float32))
        if base.ndim != 2:
            raise ValueError(f"train must be [N, D], got {base.shape}")
        n = base.shape[0]
        self.mesh = mesh
        self.metric = "l2"
        self.dim = int(base.shape[1])
        self.k = int(k)
        self.margin = int(margin)
        self.train_tile = train_tile
        self.ncentroids = int(ncentroids) if ncentroids is not None else (
            _env_int(_ENV_NCENTROIDS, max(1, int(round(n ** 0.5)))))
        self.ncentroids = max(1, min(self.ncentroids, n))
        self.nprobe = int(nprobe) if nprobe is not None else (
            _env_int(_ENV_NPROBE, max(1, self.ncentroids // 4)))
        self.nprobe = max(1, min(self.nprobe, self.ncentroids))
        self.train_iters = int(train_iters) if train_iters is not None \
            else _env_int(_ENV_TRAIN_ITERS, 5)
        self.seed = int(seed) if seed is not None \
            else _env_int(_ENV_SEED, 0)
        if self.k > n:
            raise ValueError(f"k={self.k} > n={n}")
        ids_arr = (np.arange(n, dtype=np.int64) if ids is None
                   else np.asarray(ids, np.int64).reshape(-1))
        if ids_arr.shape[0] != n:
            raise ValueError(f"{ids_arr.shape[0]} ids for {n} rows")
        if np.unique(ids_arr).shape[0] != n:
            raise ValueError("ids must be unique")
        from knn_tpu.parallel.mesh import db_topology

        hosts, chips = db_topology(mesh)
        self._db_shards = hosts * chips
        self._seg_min = int(seg_min_rows)
        self._delta_max = int(delta_max_rows)
        self._compact_tail_rows = compact_tail_rows
        self._compact_tombstones = compact_tombstones
        self._lock = threading.Condition()
        self._compact_lock = threading.Lock()
        self._closed = False
        self._compactor_t: Optional[threading.Thread] = None
        self._compactions = 0
        self._last_compaction: Optional[dict] = None
        self._last_search: Optional[dict] = None
        self.epoch = 0
        self._tail_parts: list = []
        self._tail_id_parts: list = []
        self._tail_assign_parts: list = []
        self._tail_len = 0
        self._tombstones: set = set()
        self._snap_cache: Optional[_IVFSnapshot] = None
        self._train_base(base, ids_arr)
        self._live = set(ids_arr.tolist())
        # health/statusz registration (weak; no-op when obs disabled):
        # surfaces epoch/tail/tombstone state and the drift sketches
        obs.health.register_index(self)

    # -- placement ---------------------------------------------------------
    def _train_base(self, base: np.ndarray, base_ids: np.ndarray) -> None:
        """(Re)cluster ``base`` and install it as the list-major
        placement.  Caller holds no lock on first build; compaction
        calls this off-path and installs under the lock itself."""
        km = train_kmeans(base, self.ncentroids, mesh=self.mesh,
                          iters=self.train_iters, seed=self.seed,
                          train_tile=self.train_tile)
        # stable sort -> centroid-contiguous extents whose in-extent
        # order preserves canonical (insertion) order, so block-local
        # tie ranking equals canonical tie ranking
        perm = np.argsort(km.assign, kind="stable").astype(np.int64)
        starts = np.zeros(self.ncentroids + 1, np.int64)
        np.cumsum(km.counts, out=starts[1:])
        self._base = base
        self._base_ids = base_ids
        self._centroids = km.centroids
        self._residuals = km.residuals.copy()
        self._base_assign = km.assign
        self._list_base_pos = tuple(
            perm[starts[l]:starts[l + 1]]
            for l in range(self.ncentroids))
        self._base_counts = km.counts.copy()
        # train-time drift baseline (knn_tpu.obs.drift): built ONLY
        # when telemetry is on — KNN_TPU_OBS=0 means no sketches at
        # all, the pinned obs-off contract
        self._drift = None
        if obs.enabled():
            from knn_tpu.obs.drift import QueryDriftMonitor

            norms = np.sqrt(np.einsum(
                "nd,nd->n", base.astype(np.float64),
                base.astype(np.float64)))
            self._drift = QueryDriftMonitor(
                train_norms=norms, assign_baseline=km.counts)

    def _assign_host(self, rows: np.ndarray) -> np.ndarray:
        """Nearest-centroid assignment for delta-tail rows, host f64
        with lexicographic ties — any assignment is VALID for the
        certificate as long as the residual radius covers it, which
        :meth:`insert` maintains."""
        r64 = rows.astype(np.float64)
        c64 = self._centroids.astype(np.float64)
        d = ((r64[:, None, :] - c64[None, :, :]) ** 2).sum(-1)
        return np.argmin(d, axis=1).astype(np.int64)

    def _snapshot(self) -> _IVFSnapshot:
        with self._lock:
            if self._snap_cache is not None:
                return self._snap_cache
            n_base = self._base.shape[0]
            tail = (np.concatenate(self._tail_parts)
                    if self._tail_parts
                    else np.empty((0, self.dim), np.float32))
            tail_ids = (np.concatenate(self._tail_id_parts)
                        if self._tail_id_parts
                        else np.empty(0, np.int64))
            tail_assign = (np.concatenate(self._tail_assign_parts)
                           if self._tail_assign_parts
                           else np.empty(0, np.int64))
            all_rows = np.concatenate([self._base, tail])
            all_ids = np.concatenate([self._base_ids, tail_ids])
            live_mask = np.ones(all_rows.shape[0], bool)
            if self._tombstones:
                dead = np.isin(all_ids,
                               np.fromiter(self._tombstones, np.int64,
                                           len(self._tombstones)))
                live_mask &= ~dead
            live_positions = np.flatnonzero(live_mask).astype(np.int64)
            sizes = self._base_counts + np.bincount(
                tail_assign, minlength=self.ncentroids)
            snap = _IVFSnapshot(
                epoch=self.epoch,
                ncentroids=self.ncentroids,
                centroids=self._centroids,
                cent64=self._centroids.astype(np.float64),
                residuals=self._residuals.copy(),
                list_base_pos=self._list_base_pos,
                list_sizes=sizes,
                tail_assign=tail_assign,
                n_base=n_base,
                all_rows=all_rows,
                all_ids=all_ids,
                live_mask=live_mask,
                live_positions=live_positions,
                n_live=int(live_positions.shape[0]),
            )
            self._snap_cache = snap
            return snap

    # -- rungs -------------------------------------------------------------
    def _seg_rung(self, rows: int, m: int) -> int:
        """Smallest segment ladder rung holding ``rows``: rungs double
        from a floor that guarantees every db shard can rank ``m`` rows
        and divides evenly across shards — so steady-state probing hits
        a handful of compiled shapes, never one per probe set."""
        floor = max(self._seg_min, m * self._db_shards)
        floor = -(-floor // self._db_shards) * self._db_shards
        cap = floor
        while cap < rows:
            cap *= 2
        return cap

    def _q_rung(self, rows: int) -> int:
        from knn_tpu.parallel.mesh import QUERY_AXIS

        cap = int(self.mesh.shape[QUERY_AXIS])
        while cap < rows:
            cap *= 2
        return cap

    # -- search ------------------------------------------------------------
    def _probe(self, q64: np.ndarray, snap: _IVFSnapshot, nprobe: int):
        """(probes [Q, P] sorted list ids, unprobed_lb [Q] f64,
        nearest [Q] int64): the probe pick, each query's lower bound
        over every UNPROBED non-empty list — ``min_l (||q - c_l|| -
        r_l)`` — computed in f64 with the direct-difference form (no
        cancellation), and the nearest centroid (the drift sketch's
        assignment stream)."""
        n_q = q64.shape[0]
        c = snap.ncentroids
        cd = np.empty((n_q, c))
        for lo in range(0, n_q, 128):
            diff = q64[lo:lo + 128, None, :] - snap.cent64[None, :, :]
            cd[lo:lo + 128] = np.sqrt(np.einsum("qcd,qcd->qc", diff, diff))
        order = np.lexsort(
            (np.broadcast_to(np.arange(c), cd.shape), cd), axis=-1)
        probes = np.sort(order[:, :nprobe], axis=-1)
        lb = cd - snap.residuals[None, :]
        np.put_along_axis(lb, order[:, :nprobe], np.inf, axis=-1)
        lb[:, snap.list_sizes == 0] = np.inf
        return probes, lb.min(axis=-1), order[:, 0]

    def _coarse_counted(self, q_grp: np.ndarray, pos: np.ndarray,
                        snap: _IVFSnapshot, kk: int, m: int):
        """Gathered-block coarse pass through the UNMODIFIED host-tier
        segment program (rung-padded, traced n_valid), refined to exact
        f64 finals; returns (d_ref, p_ref, complete) where ``complete``
        certifies the refined top-kk is the exact block top-kk (the
        f32-tolerance exclusion bound of PR 3, applied to the block).

        Queries whose exclusion bound fails (an f32-cancellation
        artifact of the coarse pass, NOT a probe miss) escalate WITHIN
        the block: every gathered row re-scores in f64, which is
        complete by construction and streams no bytes beyond the rows
        the probe already gathered — the full-corpus fallback stays
        reserved for genuine residual-bound failures."""
        import jax.numpy as jnp

        from knn_tpu.ops.pallas_knn import PAD_VAL
        from knn_tpu.parallel.collectives import replicate, shard
        from knn_tpu.parallel.mesh import QUERY_AXIS, db_axes
        from knn_tpu.parallel.sharded import (
            _INT_SENTINEL, segment_search_program)

        real = int(pos.shape[0])
        n_g = q_grp.shape[0]
        rung = self._seg_rung(real, m)
        prog = segment_search_program(
            self.mesh, m, self.metric, train_tile=self.train_tile,
            compute_dtype=jnp.float32)
        seg = np.full((rung, self.dim), PAD_VAL, np.float32)
        seg[:real] = snap.all_rows[pos]
        q_pad = np.zeros((self._q_rung(n_g), self.dim), np.float32)
        q_pad[:n_g] = q_grp
        qp = shard(q_pad, self.mesh, QUERY_AXIS)
        tp = shard(seg, self.mesh, db_axes(self.mesh))
        nv = replicate(np.asarray([real], np.int32), self.mesh)
        d32, i32 = prog(qp, tp, nv)
        d32 = np.asarray(d32)[:n_g]
        i32 = np.asarray(i32)[:n_g]
        valid = i32 != _INT_SENTINEL
        cand = np.where(valid, pos[np.clip(i32, 0, real - 1)], snap.n_all)
        d_ref, p_ref = refine_exact(snap.all_rows, q_grp, cand, kk)
        if real <= m:
            # every block row was a candidate: complete by construction
            return d_ref, p_ref, np.ones(n_g, bool)
        # rows outside the coarse top-m have f32 distance >= d32[:, m-1];
        # the tolerance converts that into an f64 exclusion bound
        tol = certification_tolerance(
            q_grp, snap.all_rows,
            db_norm_max=float(snap.norm2()[pos].max()))
        outsider_lb = d32[:, m - 1].astype(np.float64) - tol
        complete = d_ref[:, kk - 1] < outsider_lb
        bad = np.flatnonzero(~complete)
        if bad.size:
            d_ref[bad], p_ref[bad] = refine_shared_exact(
                snap.all_rows, q_grp[bad], pos, kk)
            complete[bad] = True
        return d_ref, p_ref, complete

    def _coarse_pallas(self, q_grp: np.ndarray, pos: np.ndarray,
                       snap: _IVFSnapshot, kk: int, margin: int,
                       pallas_kw: dict):
        """Gathered-block coarse pass through the UNMODIFIED Pallas
        wrapper (streaming/fused × f32/bf16x3/int8): its own certificate
        + fallback make the block top-kk exact, so the re-refine here
        only re-anchors values/ties to the canonical f64 form."""
        from knn_tpu.ops.pallas_knn import knn_search_pallas

        _, i_c, _stats = knn_search_pallas(
            q_grp, snap.all_rows[pos], kk, margin=margin, **pallas_kw)
        cand = pos[np.asarray(i_c)]
        d_ref, p_ref = refine_exact(snap.all_rows, q_grp, cand, kk)
        return d_ref, p_ref, np.ones(q_grp.shape[0], bool)

    def search_certified(
        self,
        queries,
        *,
        k: Optional[int] = None,
        nprobe: Optional[int] = None,
        selector: str = "exact",
        margin: Optional[int] = None,
        precision: str = "highest",
        kernel: str = "tiled",
        tile_n: Optional[int] = None,
        block_q: Optional[int] = None,
        return_sqrt: bool = False,
    ):
        """(d [Q, k] f64, ids [Q, k] int64, stats): EXACT nearest
        neighbors of the live corpus — probed lists answer, the
        residual certificate checks, flagged queries repair via the
        exact f64 fallback.  See the module docstring for the proof
        obligation each step discharges."""
        if selector not in SELECTORS:
            raise ValueError(
                f"selector {selector!r} not in {SELECTORS}")
        q = np.ascontiguousarray(np.asarray(queries, np.float32))
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(
                f"queries shape {q.shape} incompatible with dim "
                f"{self.dim}")
        k = self.k if k is None else int(k)
        margin = self.margin if margin is None else int(margin)
        snap = self._snapshot()
        if snap.n_live < k:
            raise ValueError(
                f"k={k} exceeds live rows {snap.n_live}")
        nprobe_r = self.nprobe if nprobe is None else int(nprobe)
        nprobe_r = max(1, min(nprobe_r, snap.ncentroids))
        n_q = q.shape[0]
        t0 = time.perf_counter()
        q64 = q.astype(np.float64)
        probes, unprobed_lb, nearest = self._probe(q64, snap, nprobe_r)
        if self._drift is not None:
            self._drift.observe(
                norms=np.sqrt(np.einsum("qd,qd->q", q64, q64)),
                assignments=nearest)
        d_out = np.full((n_q, k), np.inf)
        pos_out = np.full((n_q, k), snap.n_all, np.int64)
        flagged = np.zeros(n_q, bool)
        rows_gathered = 0
        m = k + margin
        pallas_kw = {"precision": precision, "kernel": kernel}
        if tile_n is not None:
            pallas_kw["tile_n"] = tile_n
        if block_q is not None:
            pallas_kw["block_q"] = block_q
        groups: dict = {}
        for qi in range(n_q):
            groups.setdefault(tuple(probes[qi].tolist()), []).append(qi)
        # certificate-margin telemetry: how close each probed answer
        # came to the unprobed-list bound (1.0 = miles of headroom,
        # ~0 = one insert away from fallback, < 0 = the bound failed)
        margins: list = [] if obs.enabled() else None
        for key, members in groups.items():
            qi = np.asarray(members, np.int64)
            pos = snap.positions_for(key)
            rows_gathered += int(pos.shape[0]) * qi.shape[0]
            if pos.shape[0] < k:
                flagged[qi] = True  # probe can't even fill k: repair
                continue
            q_grp = q[qi]
            if selector == "pallas":
                d_ref, p_ref, complete = self._coarse_pallas(
                    q_grp, pos, snap, k, margin, pallas_kw)
            else:
                d_ref, p_ref, complete = self._coarse_counted(
                    q_grp, pos, snap, k, m)
            d_out[qi] = d_ref
            pos_out[qi] = p_ref
            s_k = np.sqrt(d_ref[:, k - 1])
            lb = unprobed_lb[qi]
            bound_ok = s_k < lb * (1.0 - _BOUND_SLACK)
            flagged[qi] = ~(complete & bound_ok)
            if margins is not None:
                fin = np.isfinite(lb)
                if fin.any():
                    margins.extend(
                        ((lb[fin] - s_k[fin])
                         / np.maximum(np.abs(lb[fin]), 1e-30)).tolist())
        if margins:
            obs.histogram(obs.names.CERTIFIED_MARGIN,
                          path="ivf").observe_many(margins)
        n_bad = int(flagged.sum())
        misses = 0
        recall_sum = float(n_q - n_bad)  # certified queries: exactly 1.0
        if n_bad:
            bad = np.flatnonzero(flagged)
            d_fb, p_fb = refine_shared_exact(
                snap.all_rows, q[bad], snap.live_positions, k)
            for row, qi in enumerate(bad):
                before = pos_out[qi][pos_out[qi] < snap.n_all]
                hit = int(np.isin(p_fb[row], before).sum())
                recall_sum += hit / k
                if hit < k:
                    misses += 1
            d_out[bad] = d_fb
            pos_out[bad] = p_fb
        ids_out = snap.all_ids[
            np.clip(pos_out, 0, snap.n_all - 1)]
        wall = time.perf_counter() - t0
        stats = self._search_stats(
            snap, n_q=n_q, k=k, nprobe=nprobe_r, selector=selector,
            precision=precision, n_groups=len(groups),
            rows_gathered=rows_gathered, n_bad=n_bad, misses=misses,
            recall_sum=recall_sum, wall=wall)
        if return_sqrt:
            d_out = np.sqrt(d_out)
        return d_out, ids_out, stats

    def _search_stats(self, snap, *, n_q, k, nprobe, selector, precision,
                      n_groups, rows_gathered, n_bad, misses, recall_sum,
                      wall) -> dict:
        from knn_tpu.analysis.widths import db_operand_nbytes

        prec = precision if precision else "default"
        per_row = sum(db_operand_nbytes(1, self.dim, prec).values())
        brute_b = float(n_q) * snap.n_live * per_row
        probed_b = float(rows_gathered) * per_row
        stats = {
            "epoch": snap.epoch,
            "queries": n_q,
            "k": k,
            "ncentroids": snap.ncentroids,
            "nprobe": nprobe,
            "selector": selector,
            "groups": n_groups,
            "certified_queries": n_q - n_bad,
            "fallback_queries": n_bad,
            "fallback_rate": n_bad / n_q if n_q else 0.0,
            "genuine_misses": misses,
            "recall_at_k": recall_sum / n_q if n_q else 1.0,
            "rows_gathered": rows_gathered,
            "probe_fraction": (rows_gathered / (n_q * snap.n_live)
                               if n_q and snap.n_live else 0.0),
            "bytes_streamed_ratio": (probed_b / brute_b
                                     if brute_b else 0.0),
            "wall_s": round(wall, 6),
        }
        if obs.enabled():
            # the per-search quality stats, as scrapable gauges beside
            # the dict the caller gets (satellite: registry export)
            for name, key in (
                (obs.names.IVF_FALLBACK_RATE, "fallback_rate"),
                (obs.names.IVF_RECALL_AT_K, "recall_at_k"),
                (obs.names.IVF_PROBE_FRACTION, "probe_fraction"),
                (obs.names.IVF_BYTES_STREAMED_RATIO,
                 "bytes_streamed_ratio"),
            ):
                obs.gauge(name, selector=selector).set(stats[key])
            from knn_tpu.obs.drift import index_health

            index_health(snap.list_sizes,
                         int(snap.tail_assign.shape[0]),
                         snap.n_all, snap.n_live)
        with self._lock:
            self._last_search = stats
        return stats

    # -- mutation ----------------------------------------------------------
    def insert(self, vectors, ids) -> dict:
        """Append rows to the probed tier's delta tails (by nearest
        centroid, residual radius widened to keep the certificate
        sound).  Same contract as MutableIndex.insert: epoch
        visibility, unique fresh ids, budgeted refusal."""
        v = np.ascontiguousarray(np.asarray(vectors, np.float32))
        if v.ndim != 2 or v.shape[1] != self.dim:
            raise ValueError(
                f"vectors must be [N, {self.dim}], got {v.shape}")
        ids_arr = np.asarray(ids, dtype=np.int64).reshape(-1)
        if ids_arr.shape[0] != v.shape[0]:
            raise ValueError(
                f"{ids_arr.shape[0]} ids for {v.shape[0]} rows")
        if np.unique(ids_arr).shape[0] != ids_arr.shape[0]:
            raise ValueError("insert ids must be unique")
        with self._lock:
            for i in ids_arr.tolist():
                if i in self._live:
                    raise ValueError(f"id {i} is already live")
                if i in self._tombstones:
                    raise ValueError(
                        f"id {i} was deleted this epoch; compact() "
                        f"before reusing the id")
            if self._tail_len + v.shape[0] > self._delta_max:
                raise MutationBudgetError(
                    f"delta tail full: {self._tail_len} + {v.shape[0]} "
                    f"rows exceeds delta_max_rows={self._delta_max}; "
                    f"compact()")
            assign = self._assign_host(v)
            diff = v.astype(np.float64) - \
                self._centroids.astype(np.float64)[assign]
            dist = np.sqrt(np.einsum("nd,nd->n", diff, diff))
            np.maximum.at(self._residuals, assign, dist)
            self._tail_parts.append(v)
            self._tail_id_parts.append(ids_arr)
            self._tail_assign_parts.append(assign)
            self._tail_len += v.shape[0]
            self._live.update(ids_arr.tolist())
            self._snap_cache = None
            tail_len = self._tail_len
            self._lock.notify_all()
        return {"epoch": self.epoch, "tail_rows": tail_len}

    def delete(self, ids) -> dict:
        """Tombstone live ids: rows stay placed until compaction but
        every gather filters them, so they are exactly invisible (the
        conservative residual radius keeps unprobed-list bounds sound).
        ``KeyError`` on unknown/dead ids, same as MutableIndex."""
        ids_arr = np.asarray(ids, dtype=np.int64).reshape(-1)
        with self._lock:
            for i in ids_arr.tolist():
                if i not in self._live:
                    raise KeyError(f"id {i} is not live")
            n_base = self._base_ids.shape[0]
            live_after = (n_base + self._tail_len
                          - len(self._tombstones) - ids_arr.shape[0])
            if live_after < self.k:
                raise MutationBudgetError(
                    f"delete would leave {live_after} live rows < "
                    f"k={self.k}")
            self._tombstones.update(ids_arr.tolist())
            self._live.difference_update(ids_arr.tolist())
            self._snap_cache = None
            n_tombs = len(self._tombstones)
            self._lock.notify_all()
        return {"epoch": self.epoch, "tombstones": n_tombs}

    # -- compaction --------------------------------------------------------
    def compact(self) -> dict:
        """Re-cluster the surviving rows into a fresh list-major
        placement OFF the serving path, then swap under the lock —
        searches in flight keep their snapshot; post-cut writes carry
        over into the new epoch's delta tails."""
        t0 = time.perf_counter()
        with self._compact_lock:
            with self._lock:
                snap = self._snapshot()
                cut_parts = len(self._tail_parts)
                tomb_cut = set(self._tombstones)
            survivors = np.ascontiguousarray(
                snap.all_rows[snap.live_positions])
            surv_ids = snap.all_ids[snap.live_positions]
            km = train_kmeans(survivors, self.ncentroids, mesh=self.mesh,
                              iters=self.train_iters, seed=self.seed,
                              train_tile=self.train_tile)
            perm = np.argsort(km.assign, kind="stable").astype(np.int64)
            starts = np.zeros(self.ncentroids + 1, np.int64)
            np.cumsum(km.counts, out=starts[1:])
            with self._lock:
                carried_rows = self._tail_parts[cut_parts:]
                carried_ids = self._tail_id_parts[cut_parts:]
                self._base = survivors
                self._base_ids = surv_ids
                self._centroids = km.centroids
                self._residuals = km.residuals.copy()
                self._base_assign = km.assign
                self._base_counts = km.counts.copy()
                self._list_base_pos = tuple(
                    perm[starts[l]:starts[l + 1]]
                    for l in range(self.ncentroids))
                self._tail_parts = list(carried_rows)
                self._tail_id_parts = list(carried_ids)
                self._tail_assign_parts = []
                self._tail_len = 0
                for part in carried_rows:
                    assign = self._assign_host(part)
                    diff = part.astype(np.float64) - \
                        self._centroids.astype(np.float64)[assign]
                    dist = np.sqrt(np.einsum("nd,nd->n", diff, diff))
                    np.maximum.at(self._residuals, assign, dist)
                    self._tail_assign_parts.append(assign)
                    self._tail_len += part.shape[0]
                self._tombstones -= tomb_cut
                self.epoch += 1
                self._compactions += 1
                self._snap_cache = None
                report = {
                    "epoch": self.epoch,
                    "rows": int(survivors.shape[0]),
                    "carried_tail_rows": self._tail_len,
                    "tombstones_dropped": len(tomb_cut),
                    "tombstones_carried": len(self._tombstones),
                    "wall_s": round(time.perf_counter() - t0, 4),
                }
                self._last_compaction = report
        obs.record_span("index.compact", f"ivf-compact-{report['epoch']}",
                        report["wall_s"], rows=report["rows"])
        return report

    def _compact_due(self) -> bool:
        if (self._compact_tail_rows is not None
                and self._tail_len >= self._compact_tail_rows):
            return True
        if (self._compact_tombstones is not None
                and len(self._tombstones) >= self._compact_tombstones):
            return True
        return False

    def start_compactor(self, interval_s: float = 0.05) -> None:
        """Background compaction on the ctor thresholds — the live
        mixed-traffic shape: writes keep landing, the compactor
        re-clusters off-path, snapshots swap atomically."""
        if self._compactor_t is not None and self._compactor_t.is_alive():
            return

        def loop():
            while True:
                with self._lock:
                    while not self._closed and not self._compact_due():
                        self._lock.wait(timeout=interval_s)
                    if self._closed:
                        return
                try:
                    self.compact()
                except Exception:  # pragma: no cover - keep serving
                    pass

        t = threading.Thread(target=loop, name="ivf-compactor",
                             daemon=True)
        self._compactor_t = t
        t.start()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._lock.notify_all()
        if self._compactor_t is not None:
            self._compactor_t.join(timeout=10.0)

    def __enter__(self) -> "IVFIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- reporting ---------------------------------------------------------
    def serving_engine(self, **kw) -> "IVFServingEngine":
        return IVFServingEngine(self, **kw)

    def stats(self) -> dict:
        with self._lock:
            n_base = self._base_ids.shape[0]
            out = {
                "epoch": self.epoch,
                "ncentroids": self.ncentroids,
                "nprobe": self.nprobe,
                "train_iters": self.train_iters,
                "seed": self.seed,
                "base_rows": int(n_base),
                "tail_rows": self._tail_len,
                "tombstones": len(self._tombstones),
                "live_rows": (n_base + self._tail_len
                              - len(self._tombstones)),
                "compactions": self._compactions,
                "compactor_alive": (
                    self._compactor_t is not None
                    and self._compactor_t.is_alive()),
                "metric": self.metric,
                **({"last_compaction": dict(self._last_compaction)}
                   if self._last_compaction else {}),
                **({"last_search": dict(self._last_search)}
                   if self._last_search else {}),
                **({"drift": self._drift.status()}
                   if self._drift is not None else {}),
            }
            return out


class _IVFPending:
    """A completed IVF serving request (the probed search runs at
    submit time against the pinned snapshot; ``result()`` just hands
    the arrays back — same handle surface the queue drives)."""

    __slots__ = ("trace_id", "tenant", "_result")

    def __init__(self, trace_id, tenant, result):
        self.trace_id = trace_id
        self.tenant = tenant
        self._result = result

    def result(self):
        return self._result


class IVFServingEngine:
    """The serving frontend of an :class:`IVFIndex`: duck-types the
    ``ServingEngine`` surface ``QueryQueue`` drives (``buckets``,
    ``_dim``, ``submit() -> handle``, ``apply_write``, ``stats``),
    pinning every request to one index snapshot so background
    compaction swaps are atomic from a request's view."""

    def __init__(self, index: IVFIndex, *, buckets: Sequence[int] = (8, 16)):
        import itertools

        self.index = index
        self.k = index.k
        self._dim = index.dim
        self._buckets = tuple(int(b) for b in buckets)
        self._seq = itertools.count()

    @property
    def buckets(self):
        return self._buckets

    @property
    def warmed_ops(self):
        return {"search"}

    def warmup(self, ops: Sequence[str] = ("search",)) -> dict:
        """Drive one probed search per bucket so the segment programs
        for the current rungs compile before live traffic arrives."""
        for b in self._buckets:
            q = np.zeros((int(b), self._dim), np.float32)
            self.index.search_certified(q)
        return {"search": len(self._buckets)}

    def submit(self, queries, *, op: str = "search",
               trace_id=None, tenant=None) -> _IVFPending:
        if op != "search":
            raise ValueError(
                f"IVFServingEngine serves op='search' only, got {op!r}")
        q = np.ascontiguousarray(np.asarray(queries, np.float32))
        if q.ndim != 2 or q.shape[1] != self._dim:
            raise ValueError(
                f"queries shape {q.shape} incompatible with database "
                f"dim {self._dim}")
        tid = trace_id if trace_id is not None else f"ivf-{next(self._seq)}"
        # shadow audit sampling (knn_tpu.obs.audit): pin the snapshot
        # BEFORE the search so the replay judges the served answer
        # against the exact corpus state it was served from
        audit_q = q.copy() if obs.audit.sampled(tid) else None
        snap = self.index._snapshot() if audit_q is not None else None
        t0 = time.perf_counter()
        d, ids, _stats = self.index.search_certified(q, k=self.k)
        obs.record_span("serving.request", tid,
                        time.perf_counter() - t0, op="ivf_search")
        if audit_q is not None:
            self._submit_audit(tid, tenant, audit_q, d, ids, snap,
                               _stats.get("epoch"))
        return _IVFPending(tid, tenant, (d, ids))

    def _submit_audit(self, tid, tenant, q_audit, d, ids,
                      snap, search_epoch) -> None:
        """Enqueue one sampled, already-served request for off-path
        exact replay (knn_tpu.obs.audit).  The oracle closure scans
        every live row of the pinned snapshot in f64 — ONLY on the
        audit worker thread.  Failure-proof: the request was served;
        a broken audit layer degrades to a dropped record."""
        try:
            if search_epoch != snap.epoch:
                # a compaction swapped between the snapshot pin and the
                # search: the evidence is unjudgeable — drop it loudly
                obs.counter(obs.names.AUDIT_DROPPED,
                            reason="epoch_moved").inc()
                return
            k = self.k

            def oracle(queries, served_ids):
                from knn_tpu.ops.refine import (
                    _pairwise_f64,
                    refine_shared_exact,
                )

                od, o_pos = refine_shared_exact(
                    snap.all_rows, queries, snap.live_positions, k)
                oi = snap.all_ids[np.clip(o_pos, 0, snap.n_all - 1)]
                order = np.argsort(snap.all_ids, kind="stable")
                sorted_ids = snap.all_ids[order]
                sid = np.asarray(served_ids, np.int64)[:, :k]
                j = np.clip(np.searchsorted(sorted_ids, sid), 0,
                            sorted_ids.shape[0] - 1)
                pos = order[j]
                valid = (sorted_ids[j] == sid) & snap.live_mask[pos]
                se = _pairwise_f64(
                    queries, snap.all_rows[np.where(valid, pos, 0)],
                    "l2")
                return od, oi, np.where(valid, se, np.inf)

            obs.audit.submit(obs.audit.AuditRecord(
                trace_id=tid,
                tenant=tenant,
                k=k,
                queries=q_audit,
                served_d=np.asarray(d),
                served_ids=np.asarray(ids),
                epoch=int(snap.epoch),
                cost_rows=int(q_audit.shape[0]) * int(snap.n_live),
                oracle=oracle,
            ))
        except Exception:  # noqa: BLE001 - audit must not fail serving
            obs.emit_event("audit.submit_error", op="ivf_search",
                           trace_id=tid)

    def search(self, queries, *, return_sqrt: bool = False):
        d, ids = self.submit(queries).result()
        if return_sqrt:
            d = np.sqrt(d)
        return d, ids

    def apply_write(self, kind: str, *, vectors=None, ids=None) -> dict:
        if kind == "insert":
            return self.index.insert(vectors, ids)
        if kind == "delete":
            return self.index.delete(ids)
        raise ValueError(
            f"unknown write kind {kind!r}; expected insert|delete")

    def stats(self, **kw) -> dict:
        return {"index": self.index.stats()}
