"""Mutable index: delta-shard inserts, tombstone deletes, and
snapshot-swap compaction over the immutable placement machinery.

Every ``ShardedKNN`` placement is immutable by design — the database is
padded, sharded, and transferred once, and every compiled program bakes
the row count in.  TPU-KNN's thesis (arXiv:2206.14286) is that brute
force at peak FLOP/s needs no tree to rebuild, which reduces mutability
to pure **delta management**:

- **Delta shard** — :meth:`MutableIndex.insert` appends rows to a small
  device-resident TAIL placement searched alongside the main placement
  on every query.  The tail pads up a geometric capacity ladder (the
  PR 1 bucket-ladder discipline) and its search program takes the valid
  row count as a TRACED operand (``parallel.sharded._hosttier_program``
  — the host-tier sweep program reused verbatim), so inserts never
  trigger a recompile while the tail stays on its ladder rung.
- **Tombstone deletes** — :meth:`MutableIndex.delete` marks ids dead.
  Searches run WIDENED by a fixed certify reserve (the main placement
  is built at ``k_eff = k + reserve``), so after dead rows are masked
  out of the merged candidate list the surviving top-k is provably the
  exact top-k of the live rows: at most ``reserve`` tombstones can
  precede them, and the widened select already ranked past that many.
  This is the PR 3 bound discipline applied to masking — the certify
  width covers the mask, so exactness claims survive deletion; delete
  refuses LOUDLY past the reserve (compaction resets it).
- **Snapshot-swap compaction** — :meth:`MutableIndex.compact` builds a
  fresh placement from the surviving rows (re-quantizing on demand —
  the int8 placement is per-``ShardedKNN`` and rebuilds lazily), warms
  a replacement serving engine OFF the serving path, and swaps it in
  atomically under the index lock between serving micro-batches: the
  epoch counter bumps, in-flight batches finish on the snapshot they
  pinned at submit, and no search ever observes a half-swapped state.

Exactness contract (the pinned mutation oracle, tests/test_index.py):
after ANY interleaving of inserts, deletes, and compactions,
:meth:`MutableIndex.search_certified` results are bitwise-identical to
a fresh index built from the surviving rows — across coarse precisions
(f32/bf16x3/int8) and kernels (tiled/streaming/fused).  The mechanism:
the certified machinery proves each part's candidate list exact, final
distances are float64-refined per pair (``ops.refine`` — per-pair
deterministic arithmetic, placement-invariant), and the cross-part
merge is the same lexicographic (distance, position) order the device
merge tree runs, under a monotone position map.

Unsupported placements refuse loudly instead of serving stale results:
host-RAM-tier and multi-host placements raise
:class:`~knn_tpu.index.artifact.MutationUnsupportedError` on
``insert``/``delete`` (docs/INDEX.md).
"""

from __future__ import annotations

import os
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from knn_tpu import obs
from knn_tpu.index.artifact import (
    MutationBudgetError,
    MutationUnsupportedError,
)
from knn_tpu.obs import names as _mn

#: delta-tail capacity ladder defaults (rows); overridable per index or
#: via KNN_TPU_DELTA_MIN_ROWS / KNN_TPU_DELTA_MAX_ROWS
DELTA_MIN_ROWS = 256
DELTA_MAX_ROWS = 65536
#: certify-widening reserve: the main placement selects k + reserve so
#: up to ``reserve`` tombstones can be masked without losing exactness
#: (KNN_TPU_DELTA_RESERVE)
DELTA_RESERVE = 32

#: int64 sentinel for "no candidate" positions in the merged list —
#: larger than any real global position, so it sorts last and maps to
#: id -1 (dead) in the filter
_SENT64 = np.int64(1) << 62


def _env_int(name: str, default: Optional[int]) -> Optional[int]:
    """Strict env parse (the admission-switch discipline: a typo'd knob
    raises instead of silently running at the default)."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError as e:
        raise ValueError(f"{name}={raw!r} is not an int") from e


def _env_float(name: str, default: Optional[float]) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return float(raw)
    except ValueError as e:
        raise ValueError(f"{name}={raw!r} is not a number") from e


class _Snapshot:
    """One immutable, search-consistent view of the index: everything a
    query needs, pinned at :meth:`MutableIndex._snapshot` time.  Swaps
    replace the index's CURRENT snapshot; in-flight searches keep
    theirs (and, through it, the old placement and engine) alive until
    they finish — the epoch visibility rule."""

    __slots__ = ("epoch", "main", "base_ids", "tail", "tail_ids",
                 "tail_len", "tail_parts_count", "tomb_ids", "engine",
                 "n_base", "all_ids", "k_eff")

    def __init__(self, epoch, main, base_ids, tail, tail_ids,
                 tail_parts_count, tomb_ids, engine, k_eff):
        self.epoch = epoch
        self.main = main
        self.base_ids = base_ids
        self.tail = tail  # [T, D] f32 or None
        self.tail_ids = tail_ids
        self.tail_len = 0 if tail is None else tail.shape[0]
        self.tail_parts_count = tail_parts_count
        self.tomb_ids = tomb_ids  # sorted int64 array
        self.engine = engine
        self.n_base = base_ids.shape[0]
        self.all_ids = (base_ids if tail is None
                        else np.concatenate([base_ids, tail_ids]))
        self.k_eff = k_eff

    def live_rows(self) -> int:
        return self.n_base + self.tail_len - self.tomb_ids.shape[0]

    def ids_of(self, pos: np.ndarray) -> np.ndarray:
        """External ids for global positions; sentinel / out-of-range
        positions map to -1 (dead)."""
        n_total = self.all_ids.shape[0]
        valid = (pos >= 0) & (pos < n_total)
        safe = np.clip(pos, 0, n_total - 1)
        return np.where(valid, self.all_ids[safe], np.int64(-1))


class _TailHandle:
    """An in-flight tail dispatch: device outputs + the redo closure the
    transient-retry fetch discipline needs (parallel.sharded)."""

    __slots__ = ("out", "redo", "rows", "n_base")

    def __init__(self, out, redo, rows: int, n_base: int):
        self.out = out
        self.redo = redo
        self.rows = rows
        self.n_base = n_base

    def fetch(self) -> Tuple[np.ndarray, np.ndarray]:
        """(d [rows, k_t] f32, pos [rows, k_t] int64 global positions;
        masked slots carry +inf / the int64 sentinel).  d and pos come
        from the SAME execution — a transient fetch failure relaunches
        and rebinds both (the host-tier collect discipline)."""
        from knn_tpu.parallel.sharded import (
            _INT_SENTINEL,
            _fetch_or_redispatch,
        )

        cur = {"out": self.out}

        def redo0():
            cur["out"] = self.redo()
            return cur["out"][0]

        d = _fetch_or_redispatch(self.out[0], redo0, "delta-tail fetch")
        i = np.asarray(cur["out"][1])
        d = np.asarray(d)[: self.rows]
        i = i[: self.rows].astype(np.int64)
        pad = i == _INT_SENTINEL
        pos = np.where(pad, _SENT64, i + self.n_base)
        return d, pos


class MutableIndex:
    """A mutable KNN index over an immutable main placement plus a
    device-resident delta tail and an id tombstone set (see the module
    docstring for the design).  ``search``/``search_certified`` return
    ``(distances, ids)`` in EXTERNAL id space (``ids`` at construction,
    ``insert``'s ids afterwards), never raw placement positions.

    Thread-safety: guarded by ``self._lock`` (a Condition: writers
    notify the background compactor).  Searches pin a consistent
    snapshot under the lock and then run lock-free on it; the lock is
    never held across a device dispatch or an XLA compile.
    """

    def __init__(
        self,
        train,
        ids: Optional[Sequence[int]] = None,
        *,
        mesh,
        k: int,
        metric: str = "l2",
        merge: Optional[str] = None,
        train_tile: Optional[int] = None,
        compute_dtype=None,
        reserve: Optional[int] = None,
        delta_min_rows: Optional[int] = None,
        delta_max_rows: Optional[int] = None,
        compact_tail_rows: Optional[int] = None,
        compact_tombstones: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
    ):
        from knn_tpu.parallel.mesh import db_topology
        from knn_tpu.parallel.sharded import ShardedKNN

        if metric.lower() not in ("l2", "sql2", "euclidean"):
            raise MutationUnsupportedError(
                f"MutableIndex supports the l2 metric family only, got "
                f"{metric!r} (cosine re-normalizes rows at placement "
                f"and L1 has no certified bound; docs/INDEX.md)")
        train = np.ascontiguousarray(np.asarray(train, np.float32))
        if train.ndim != 2:
            raise ValueError(f"train must be 2-D, got {train.shape}")
        n, dim = train.shape
        if ids is None:
            ids_arr = np.arange(n, dtype=np.int64)
        else:
            ids_arr = np.asarray(ids, dtype=np.int64).reshape(-1)
            if ids_arr.shape[0] != n:
                raise ValueError(
                    f"ids length {ids_arr.shape[0]} != rows {n}")
            if np.unique(ids_arr).shape[0] != n:
                raise ValueError("ids must be unique")
        self.k = int(k)
        self.dim = int(dim)
        self.mesh = mesh
        self.metric = metric.lower()
        if reserve is None:
            reserve = _env_int("KNN_TPU_DELTA_RESERVE", DELTA_RESERVE)
        self._reserve = int(reserve)
        if self._reserve < 1:
            raise ValueError(
                f"reserve must be >= 1, got {self._reserve}")
        self._delta_min = int(delta_min_rows
                              if delta_min_rows is not None else
                              _env_int("KNN_TPU_DELTA_MIN_ROWS",
                                       DELTA_MIN_ROWS))
        self._delta_max = int(delta_max_rows
                              if delta_max_rows is not None else
                              _env_int("KNN_TPU_DELTA_MAX_ROWS",
                                       DELTA_MAX_ROWS))
        self._compact_tail_rows = (
            compact_tail_rows if compact_tail_rows is not None else
            _env_int("KNN_TPU_COMPACT_TAIL_ROWS", None))
        self._compact_tombstones = (
            compact_tombstones if compact_tombstones is not None else
            _env_int("KNN_TPU_COMPACT_TOMBSTONES", None))
        hosts, chips = db_topology(mesh)
        self._db_shards = hosts * chips
        self._multihost = hosts > 1
        #: constructor args replayed by compaction when it builds the
        #: fresh placement — ONE home, so a compacted placement can
        #: never silently differ from the original's configuration
        self._ctor = dict(metric=self.metric, merge=merge,
                          train_tile=train_tile,
                          compute_dtype=compute_dtype,
                          hbm_budget_bytes=hbm_budget_bytes)
        k_eff = self._k_eff_for(n)
        if k_eff < self.k:
            if self.k > n:
                raise ValueError(f"k={k} > {n} database rows")
            raise ValueError(
                f"k={k} exceeds the per-shard row count "
                f"({-(-n // self._db_shards)} rows over "
                f"{self._db_shards} db shards); use fewer db shards")
        self._main = ShardedKNN(train, mesh=mesh, k=k_eff, **self._ctor)
        #: tail searches always select k + reserve (constant across
        #: epochs -> one compiled tail program per capacity rung)
        self._k_tail = self.k + self._reserve
        if self._delta_min < 1 or self._delta_max < self._delta_min:
            raise ValueError(
                f"delta ladder [{self._delta_min}, {self._delta_max}] "
                f"is not a valid range")
        self._lock = threading.Condition()
        self._epoch = 0
        self._base_ids = ids_arr
        self._tail_parts: List[np.ndarray] = []
        self._tail_id_parts: List[np.ndarray] = []
        self._tail_len = 0
        self._tombstones: set = set()
        self._live: set = set(ids_arr.tolist())
        self._snap_cache: Optional[_Snapshot] = None
        self._tail_place: Optional[dict] = None
        self._inner_engine = None
        self._engine_kwargs: Optional[dict] = None
        self._compactions = 0
        self._last_compaction: Optional[dict] = None
        self._closed = False
        self._compactor_t: Optional[threading.Thread] = None
        #: serializes compactions (never held together with _lock on
        #: the same thread EXCEPT in the documented compact() order:
        #: _compact_lock first, _lock only for the brief swap)
        self._compact_lock = threading.Lock()
        obs.gauge(_mn.INDEX_EPOCH).set(0.0)
        obs.gauge(_mn.INDEX_TAIL_ROWS).set(0.0)
        obs.gauge(_mn.INDEX_TOMBSTONES).set(0.0)
        obs.health.register_index(self)

    # -- construction helpers ---------------------------------------------
    def _k_eff_for(self, n_rows: int) -> int:
        """The widened select width for an ``n_rows`` main placement:
        k + reserve, capped by the rows a shard can actually rank."""
        padded = -(-n_rows // self._db_shards) * self._db_shards
        return min(self.k + self._reserve, n_rows,
                   padded // self._db_shards)

    @property
    def budget(self) -> int:
        """Tombstones the CURRENT epoch can absorb before exactness
        would need a wider select than the placement compiled —
        delete() refuses past it, compaction resets it."""
        return self._main.k - self.k

    # -- refusals ----------------------------------------------------------
    def _require_mutable(self, what: str) -> None:
        if self._main._host_tier is not None:
            raise MutationUnsupportedError(
                f"{what}: this placement runs the host-RAM shard tier "
                f"(corpus exceeds the per-host HBM budget); the delta "
                f"tail has no resident placement to merge against — "
                f"compact offline and rebuild, or raise the budget "
                f"(docs/INDEX.md)")
        if self._multihost:
            raise MutationUnsupportedError(
                f"{what}: multi-host placements have no write "
                f"replication protocol yet — a single-host write would "
                f"silently serve stale results from the other hosts "
                f"(docs/INDEX.md)")

    # -- snapshots ---------------------------------------------------------
    def _snapshot(self) -> _Snapshot:
        """The current consistent view (cached; invalidated by every
        mutation and swap).  Cheap on the serving path: one lock hop
        when the cache is warm."""
        with self._lock:
            snap = self._snap_cache
            if snap is not None:
                return snap
            tail = (None if self._tail_len == 0 else
                    np.concatenate(self._tail_parts))
            tail_ids = (None if self._tail_len == 0 else
                        np.concatenate(self._tail_id_parts))
            snap = _Snapshot(
                self._epoch, self._main, self._base_ids, tail, tail_ids,
                len(self._tail_parts),
                np.asarray(sorted(self._tombstones), np.int64),
                self._inner_engine, self._main.k)
            self._snap_cache = snap
            return snap

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    # -- writes ------------------------------------------------------------
    def insert(self, vectors, ids) -> dict:
        """Append rows to the delta tail under fresh unique ids.
        Visible to every search submitted after this returns (epoch
        visibility: searches already in flight keep their snapshot).
        Raises :class:`MutationBudgetError` past the tail's top ladder
        rung and ``ValueError`` on id reuse — including ids tombstoned
        this epoch (their mask would shadow the new row; compaction
        frees the id)."""
        self._require_mutable("insert")
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
                    f"rows exceeds the {self._delta_max}-row top ladder "
                    f"rung; compact() (or raise delta_max_rows / "
                    f"KNN_TPU_DELTA_MAX_ROWS)")
            self._tail_parts.append(v)
            self._tail_id_parts.append(ids_arr)
            self._tail_len += v.shape[0]
            self._live.update(ids_arr.tolist())
            self._snap_cache = None
            tail_len = self._tail_len
            self._lock.notify_all()  # wake the compactor
        obs.gauge(_mn.INDEX_TAIL_ROWS).set(float(tail_len))
        return {"epoch": self.epoch, "tail_rows": tail_len}

    def delete(self, ids) -> dict:
        """Tombstone live ids.  The rows stay physically placed until
        compaction; every search masks them out of the merged candidate
        list, with the certify reserve guaranteeing the masked select
        is still the exact live top-k.  Refuses past the reserve budget
        (:class:`MutationBudgetError`) and on unknown/dead ids
        (``KeyError``)."""
        self._require_mutable("delete")
        ids_arr = np.asarray(ids, dtype=np.int64).reshape(-1)
        with self._lock:
            for i in ids_arr.tolist():
                if i not in self._live:
                    raise KeyError(f"id {i} is not live")
            if len(self._tombstones) + ids_arr.shape[0] > self.budget:
                raise MutationBudgetError(
                    f"tombstone budget exhausted: "
                    f"{len(self._tombstones)} + {ids_arr.shape[0]} "
                    f"exceeds the certify reserve {self.budget} "
                    f"(k_eff={self._main.k} - k={self.k}); compact() "
                    f"to drop the dead rows")
            live_after = (self._base_ids.shape[0] + self._tail_len
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
        obs.gauge(_mn.INDEX_TOMBSTONES).set(float(n_tombs))
        return {"epoch": self.epoch, "tombstones": n_tombs}

    # -- delta-tail device search -----------------------------------------
    def _capacity_for(self, tail_len: int) -> int:
        """Smallest ladder rung holding ``tail_len`` rows.  Rungs
        double from a floor that guarantees every shard can rank
        k + reserve rows, and every rung is a db-shard multiple."""
        floor = max(self._delta_min, self._k_tail * self._db_shards)
        floor = -(-floor // self._db_shards) * self._db_shards
        cap = floor
        while cap < tail_len:
            cap *= 2
        return cap

    def _tail_device(self, snap: _Snapshot) -> dict:
        """The snapshot's tail placed on device at its ladder-rung
        capacity (cached per (epoch, tail_len) — inserts re-place, a
        stable tail is transferred once)."""
        from knn_tpu.ops.pallas_knn import PAD_VAL
        from knn_tpu.parallel.collectives import replicate, shard
        from knn_tpu.parallel.mesh import db_axes

        key = (snap.epoch, snap.tail_len)
        with self._lock:
            tp = self._tail_place
            if tp is not None and tp["key"] == key:
                return tp
        capacity = self._capacity_for(snap.tail_len)
        # at the width the main placement's batches are placed at (whole
        # lane tiles: zero columns past ``dim``, ShardedKNN's docstring)
        arr = np.zeros((capacity, snap.main._placed_width), np.float32)
        arr[snap.tail_len:, : self.dim] = PAD_VAL
        if snap.tail_len:
            arr[: snap.tail_len, : self.dim] = snap.tail
        placed = {
            "key": key,
            "capacity": capacity,
            "tp": shard(arr, self.mesh, db_axes(self.mesh)),
            "nv": replicate(np.asarray([snap.tail_len], np.int32),
                            self.mesh),
        }
        with self._lock:
            self._tail_place = placed
        return placed

    def _dispatch_tail(self, snap: _Snapshot, q_np: np.ndarray
                       ) -> _TailHandle:
        """Async tail search: the host-tier per-sweep program (traced
        valid-row count — ONE compiled executable per (query shape,
        capacity rung), never per tail size) over the snapshot's placed
        tail.  Returns a handle; fetch merges on host."""
        from knn_tpu.parallel.sharded import (
            _hosttier_program,
            _retry_transient,
        )

        dev = self._tail_device(snap)
        prog = _hosttier_program(
            self.mesh, self._k_tail, snap.main.metric, snap.main.merge,
            self._ctor["train_tile"], snap.main._dtype_key,
            dcn_merge=snap.main.dcn_merge)
        qp, n_q = snap.main._place_queries(q_np)
        out = _retry_transient(
            lambda: prog(qp, dev["tp"], dev["nv"]),
            "delta-tail dispatch")
        return _TailHandle(
            out, lambda: prog(qp, dev["tp"], dev["nv"]), n_q,
            snap.n_base)

    # -- merged, masked selection -----------------------------------------
    @staticmethod
    def _merge_filter(snap: _Snapshot, d_parts, p_parts, k: int):
        """Lexicographic (distance, global position) merge of per-part
        candidate lists, tombstones and sentinels masked out, first k
        survivors kept — the same associative order the device merge
        tree runs, so a monotone position remap (compaction, the fresh
        oracle) preserves it."""
        cd = (d_parts[0] if len(d_parts) == 1
              else np.concatenate(d_parts, axis=1))
        cp = (p_parts[0] if len(p_parts) == 1
              else np.concatenate(p_parts, axis=1))
        order = np.lexsort((cp, cd), axis=-1)
        cd = np.take_along_axis(cd, order, axis=-1)
        cp = np.take_along_axis(cp, order, axis=-1)
        ids = snap.ids_of(cp)
        dead = ids < 0
        if snap.tomb_ids.size:
            dead |= np.isin(ids, snap.tomb_ids)
        # stable partition: live candidates keep their merged order
        sel = np.argsort(dead, kind="stable", axis=-1)[:, :k]
        if bool(np.take_along_axis(dead, sel, axis=-1).any()):
            raise RuntimeError(
                "masked merge ran out of live candidates — the certify "
                "reserve no longer covers the tombstone count (index "
                "invariant violated; please report)")
        return (np.take_along_axis(cd, sel, axis=-1),
                np.take_along_axis(ids, sel, axis=-1))

    def search(self, queries, *, k: Optional[int] = None,
               return_sqrt: bool = False):
        """(distances [Q, k] f32, ids [Q, k] int64) of the k nearest
        LIVE rows: the widened main select merged with the delta-tail
        select, tombstones masked at merge time.  ``k`` may only
        shrink below the construction k (the reserve was sized for
        it)."""
        k = self.k if k is None else int(k)
        if not 0 < k <= self.k:
            raise ValueError(
                f"k={k} outside (0, {self.k}] — the certify reserve "
                f"was sized for the construction k")
        snap = self._snapshot()
        if k > snap.live_rows():
            raise ValueError(
                f"k={k} > {snap.live_rows()} live rows")
        q = np.ascontiguousarray(np.asarray(queries, np.float32))
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(
                f"queries must be [N, {self.dim}], got {q.shape}")
        tail_h = (self._dispatch_tail(snap, q)
                  if snap.tail_len else None)
        d_m, i_m = snap.main.search(q)
        d_parts = [np.asarray(d_m)]
        p_parts = [np.asarray(i_m).astype(np.int64)]
        if tail_h is not None:
            d_t, p_t = tail_h.fetch()
            d_parts.append(d_t)
            p_parts.append(p_t)
        d, ids = self._merge_filter(snap, d_parts, p_parts, k)
        if return_sqrt:
            d = np.sqrt(d)
        return d, ids

    def search_certified(self, queries, *, margin: int = 28,
                         selector: str = "approx", **knobs):
        """Certified-exact live top-k: ``(distances_f64, ids, stats)``.

        The main part runs the full PR 3 certified pipeline at the
        widened ``k_eff`` (coarse precision/kernel knobs pass through —
        ``precision=\"int8\"``, ``kernel=\"fused\"``, ...), so its
        candidate list is PROVABLY the exact top-k_eff; the delta tail
        is float64-scanned on host (the tail is small by construction —
        O(Q*T*D) next to the O(Q*N*D) device sweep).  Both parts'
        final distances are float64-refined per pair (ops.refine), the
        merge is lexicographic (distance, position), and tombstones
        mask after it under the reserve guarantee — which is what makes
        the result bitwise-identical to a fresh index built from the
        surviving rows (the pinned mutation oracle)."""
        from knn_tpu.ops.refine import refine_exact

        snap = self._snapshot()
        if self.k > snap.live_rows():
            raise ValueError(
                f"k={self.k} > {snap.live_rows()} live rows")
        q = np.ascontiguousarray(np.asarray(queries, np.float32))
        if q.ndim != 2 or q.shape[1] != self.dim:
            raise ValueError(
                f"queries must be [N, {self.dim}], got {q.shape}")
        knobs.pop("return_distances", None)
        return_sqrt = bool(knobs.pop("return_sqrt", False))
        _, i_m, stats = snap.main.search_certified(
            q, margin=margin, selector=selector,
            return_distances=False, **knobs)
        # float64 per-pair refine of the PROVEN-exact candidate set:
        # deterministic arithmetic, independent of placement shape,
        # coarse precision, and kernel — the oracle anchor
        d64_m, i64_m = refine_exact(
            snap.main._host_train(), q, np.asarray(i_m), snap.k_eff)
        d_parts = [d64_m]
        p_parts = [i64_m]
        if snap.tail_len:
            k_t = min(self._k_tail, snap.tail_len)
            cand = np.broadcast_to(
                np.arange(snap.tail_len, dtype=np.int64),
                (q.shape[0], snap.tail_len))
            d64_t, i64_t = refine_exact(snap.tail, q, cand, k_t)
            d_parts.append(d64_t)
            p_parts.append(i64_t + snap.n_base)
        d, ids = self._merge_filter(snap, d_parts, p_parts, self.k)
        if return_sqrt:
            d = np.sqrt(d)
        stats = dict(stats)
        stats["index"] = {
            "epoch": snap.epoch,
            "k_eff": snap.k_eff,
            "tail_rows": snap.tail_len,
            "tombstones": int(snap.tomb_ids.shape[0]),
            "tail_certified": "host_f64",
        }
        return d, ids, stats

    # -- compaction --------------------------------------------------------
    def compact(self) -> dict:
        """Merge the tail and drop tombstoned rows into a fresh
        placement, then swap it in snapshot-consistently.  The build
        (re-quantize, re-place, re-warm the serving engine) runs OFF
        the serving path; only the final pointer swap takes the index
        lock, so in-flight searches finish on the old epoch and no
        micro-batch ever stalls on the swap (the pinned live-traffic
        proof).  Writes that landed DURING the build carry over: rows
        inserted after the cut stay in the new tail, ids deleted after
        the cut stay tombstoned against the new placement."""
        from knn_tpu.parallel.sharded import ShardedKNN
        from knn_tpu.serving.engine import ServingEngine

        self._require_mutable("compact")
        t0 = time.perf_counter()
        with self._compact_lock:
            snap = self._snapshot()
            tomb_snap = set(snap.tomb_ids.tolist())
            base_host = snap.main._host_train()
            keep_b = (~np.isin(snap.base_ids, snap.tomb_ids)
                      if snap.tomb_ids.size
                      else np.ones(snap.n_base, bool))
            parts = [base_host[keep_b]]
            id_parts = [snap.base_ids[keep_b]]
            dropped = int(snap.n_base - parts[0].shape[0])
            merged = 0
            if snap.tail_len:
                keep_t = (~np.isin(snap.tail_ids, snap.tomb_ids)
                          if snap.tomb_ids.size
                          else np.ones(snap.tail_len, bool))
                parts.append(snap.tail[keep_t])
                id_parts.append(snap.tail_ids[keep_t])
                dropped += int(snap.tail_len - parts[1].shape[0])
                merged = int(parts[1].shape[0])
            new_base = (parts[0] if len(parts) == 1
                        else np.concatenate(parts))
            new_ids = (id_parts[0] if len(id_parts) == 1
                       else np.concatenate(id_parts))
            if new_base.shape[0] < self.k:
                raise MutationBudgetError(
                    f"compaction would leave {new_base.shape[0]} rows "
                    f"< k={self.k}")
            k_eff = self._k_eff_for(new_base.shape[0])
            new_main = ShardedKNN(new_base, mesh=self.mesh, k=k_eff,
                                  **self._ctor)
            new_engine = None
            with self._lock:
                kw = self._engine_kwargs
                old_engine = self._inner_engine
            if kw is not None:
                # pre-warm the replacement engine OFF the serving path:
                # the first post-swap micro-batch must hit a compiled
                # executable, never an inline XLA compile
                new_engine = ServingEngine(new_main, **kw)
                new_engine.warmup(tuple(
                    sorted(getattr(old_engine, "warmed_ops", ()))
                    or ("search",)))
            t_swap = time.perf_counter()
            with self._lock:
                self._main = new_main
                self._base_ids = new_ids
                self._tail_parts = self._tail_parts[
                    snap.tail_parts_count:]
                self._tail_id_parts = self._tail_id_parts[
                    snap.tail_parts_count:]
                self._tail_len = int(sum(p.shape[0]
                                         for p in self._tail_parts))
                self._tombstones = {t for t in self._tombstones
                                    if t not in tomb_snap}
                self._epoch += 1
                if new_engine is not None:
                    self._inner_engine = new_engine
                self._snap_cache = None
                self._tail_place = None
                self._compactions += 1
                epoch = self._epoch
                tail_len = self._tail_len
                n_tombs = len(self._tombstones)
                report = self._last_compaction = {
                    "epoch": epoch,
                    "rows": int(new_base.shape[0]),
                    "rows_dropped": dropped,
                    "tail_rows_merged": merged,
                    "carry_tail_rows": tail_len,
                    "carry_tombstones": n_tombs,
                    "wall_s": round(time.perf_counter() - t0, 4),
                    "swap_s": round(time.perf_counter() - t_swap, 6),
                }
        obs.counter(_mn.INDEX_COMPACTIONS).inc()
        obs.histogram(_mn.INDEX_SWAP_SECONDS).observe(
            report["swap_s"])
        obs.gauge(_mn.INDEX_EPOCH).set(float(epoch))
        obs.gauge(_mn.INDEX_TAIL_ROWS).set(float(tail_len))
        obs.gauge(_mn.INDEX_TOMBSTONES).set(float(n_tombs))
        obs.record_span("index.compact", None, report["wall_s"],
                        epoch=epoch, rows=report["rows"],
                        rows_dropped=dropped, tail_rows_merged=merged,
                        swap_s=report["swap_s"])
        return dict(report)

    def _compact_due(self) -> bool:
        """Caller holds ``self._lock``."""
        if self._compact_tail_rows is not None \
                and self._tail_len >= self._compact_tail_rows:
            return True
        if self._compact_tombstones is not None \
                and len(self._tombstones) >= self._compact_tombstones:
            return True
        return False

    def start_compactor(self, interval_s: Optional[float] = None
                        ) -> None:
        """Start the background compaction thread: compacts whenever a
        threshold (``compact_tail_rows`` / ``compact_tombstones``)
        trips, or every ``interval_s`` (KNN_TPU_COMPACT_INTERVAL_S)
        while there is anything to fold in.  Idempotent; ``close()``
        stops it."""
        interval = (interval_s if interval_s is not None else
                    _env_float("KNN_TPU_COMPACT_INTERVAL_S", None))

        def loop():
            deadline = (None if interval is None
                        else time.monotonic() + interval)
            while True:
                with self._lock:
                    if self._closed:
                        return
                    due = self._compact_due()
                    if not due and deadline is not None \
                            and time.monotonic() >= deadline \
                            and (self._tail_len or self._tombstones):
                        due = True
                    if not due:
                        if deadline is None:
                            # threshold-only config: every state change
                            # notifies the condition, so a bare wait is
                            # free (no idle 20 Hz poll on a long-lived
                            # replica)
                            self._lock.wait()
                        else:
                            self._lock.wait(timeout=max(
                                0.01, min(0.05,
                                          deadline - time.monotonic())))
                        continue
                if deadline is not None:
                    deadline = time.monotonic() + interval
                try:
                    self.compact()
                except Exception as e:  # noqa: BLE001 — keep compacting
                    obs.emit_event("index.compact_error",
                                   error=f"{type(e).__name__}: {e}")
                    with self._lock:
                        # a failing compaction must not spin hot
                        self._lock.wait(timeout=0.25)

        with self._lock:
            if self._compactor_t is not None \
                    and self._compactor_t.is_alive():
                return
            self._closed = False
            self._compactor_t = threading.Thread(
                target=loop, name="knn-index-compactor", daemon=True)
            self._compactor_t.start()

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._lock.notify_all()
            t = self._compactor_t
        if t is not None:
            t.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- serving -----------------------------------------------------------
    def serving_engine(self, **engine_kwargs) -> "MutableServingEngine":
        """A :class:`MutableServingEngine` over this index — the
        QueryQueue-compatible frontend that searches the delta tail
        alongside every bucketed main dispatch and applies writes as a
        first-class op.  Engine kwargs (buckets/min_bucket/max_bucket/
        ...) are remembered so compaction can rebuild and pre-warm the
        replacement engine off the serving path."""
        from knn_tpu.serving.engine import ServingEngine

        with self._lock:
            if self._engine_kwargs is not None:
                raise RuntimeError(
                    "serving_engine() was already called for this "
                    "index")
        inner = ServingEngine(self._main, **engine_kwargs)
        with self._lock:
            self._engine_kwargs = dict(engine_kwargs)
            self._inner_engine = inner
            self._snap_cache = None
        return MutableServingEngine(self)

    # -- observability -----------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            return {
                "epoch": self._epoch,
                "k": self.k,
                "k_eff": self._main.k,
                "reserve": self._reserve,
                "budget": self._main.k - self.k,
                "rows": int(self._base_ids.shape[0]),
                "tail_rows": self._tail_len,
                "tail_capacity": self._capacity_for(self._tail_len),
                "tombstones": len(self._tombstones),
                "live_rows": (self._base_ids.shape[0] + self._tail_len
                              - len(self._tombstones)),
                "compactions": self._compactions,
                "compact_tail_rows": self._compact_tail_rows,
                "compact_tombstones": self._compact_tombstones,
                "compactor_alive": (
                    self._compactor_t is not None
                    and self._compactor_t.is_alive()),
                "metric": self.metric,
                **({"last_compaction": dict(self._last_compaction)}
                   if self._last_compaction else {}),
            }


class _MutablePending:
    """An in-flight index-serving request: the inner engine's bucketed
    main dispatch plus the delta-tail dispatch, merged and masked at
    result time.  The tail outputs are fetched FIRST so the extra
    transfer lands before the inner join span, keeping the request's
    waterfall segments tiling within tolerance."""

    __slots__ = ("_snap", "_pending", "_tail", "_k", "_result")

    def __init__(self, snap: _Snapshot, pending, tail: Optional[
            _TailHandle], k: int):
        self._snap = snap
        self._pending = pending
        self._tail = tail
        self._k = k
        self._result = None

    @property
    def trace_id(self):
        return self._pending.trace_id

    @property
    def tenant(self):
        return self._pending.tenant

    def result(self):
        if self._result is not None:
            return self._result
        tail_parts = None
        if self._tail is not None:
            # fetched BEFORE the inner result so the transfer lands
            # inside the engine request span's wall (the waterfall's
            # attributed device window), never after it
            tail_parts = self._tail.fetch()
        d_m, i_m = self._pending.result()
        t0 = time.perf_counter()
        d_parts = [np.asarray(d_m)]
        p_parts = [np.asarray(i_m).astype(np.int64)]
        if tail_parts is not None:
            d_parts.append(tail_parts[0])
            p_parts.append(tail_parts[1])
        self._result = MutableIndex._merge_filter(
            self._snap, d_parts, p_parts, self._k)
        # the merge/mask happens after the engine request span closed;
        # an extra request-span slice keeps the waterfall segments
        # tiling the member's measured latency (any GIL stall here
        # would otherwise read as an unattributed gap)
        obs.record_span("serving.request", self._pending.trace_id,
                        time.perf_counter() - t0, op="index_merge")
        return self._result


class MutableServingEngine:
    """The serving frontend of a :class:`MutableIndex`: duck-types the
    ``ServingEngine`` surface ``QueryQueue`` drives (``buckets``,
    ``_dim``, ``submit() -> handle``, ``stats()``) while pinning every
    request to one index snapshot — swaps are atomic from a request's
    view — and searching the delta tail alongside each bucketed main
    dispatch (padded to the SAME bucket rung, so tail programs ride the
    ladder too).  Writes enter as a first-class op via
    :meth:`apply_write` (``QueryQueue.submit_write`` routes here)."""

    def __init__(self, index: MutableIndex):
        self.index = index
        self.k = index.k
        self._dim = index.dim

    @property
    def buckets(self):
        return self.index._snapshot().engine.buckets

    @property
    def warmed_ops(self):
        eng = self.index._snapshot().engine
        return getattr(eng, "warmed_ops", set())

    def warmup(self, ops: Sequence[str] = ("search",)) -> dict:
        """AOT-compile the inner engine's buckets AND the delta-tail
        program for every bucket's placed shape at the first ladder
        rung — so neither the first live request nor the first
        post-insert request pays an inline compile."""
        snap = self.index._snapshot()
        counts = snap.engine.warmup(ops)
        warmed = 0
        for b in snap.engine.buckets:
            q = np.zeros((int(b), self._dim), np.float32)
            self.index._dispatch_tail(snap, q).fetch()
            warmed += 1
        counts["tail_buckets"] = warmed
        return counts

    def submit(self, queries, *, op: str = "search",
               trace_id=None, tenant=None) -> _MutablePending:
        if op != "search":
            raise ValueError(
                f"MutableServingEngine serves op='search' only, got "
                f"{op!r} (predict over a mutating corpus is not "
                f"supported yet)")
        t_ent = time.perf_counter()
        q = np.ascontiguousarray(np.asarray(queries, np.float32))
        if q.ndim != 2 or q.shape[1] != self._dim:
            raise ValueError(
                f"queries shape {q.shape} incompatible with database "
                f"dim {self._dim}")
        snap = self.index._snapshot()
        t_pre = time.perf_counter()
        pending = snap.engine.submit(q, op="search",
                                     trace_id=trace_id, tenant=tenant)
        # the wrapper prologue (coerce + snapshot pin) runs BEFORE the
        # inner engine's request clock starts; recorded as an extra
        # request-span slice so a stall here (e.g. GIL pressure from a
        # background compaction compile) stays attributed in the
        # request's waterfall instead of reading as an unattributed gap
        obs.record_span("serving.request", pending.trace_id,
                        t_pre - t_ent, op="index_snapshot")
        tail_h = None
        if snap.tail_len:
            from knn_tpu.serving.buckets import bucket_for

            b = bucket_for(snap.engine.buckets, q.shape[0])
            rows = int(b) if b is not None else q.shape[0]
            if rows > q.shape[0]:
                padded = np.zeros((rows, self._dim), np.float32)
                padded[: q.shape[0]] = q
            else:
                padded = q
            tail_h = self.index._dispatch_tail(snap, padded)
            tail_h.rows = q.shape[0]
        return _MutablePending(snap, pending, tail_h, self.k)

    def search(self, queries, *, return_sqrt: bool = False):
        d, ids = self.submit(queries).result()
        if return_sqrt:
            d = np.sqrt(d)
        return d, ids

    def apply_write(self, kind: str, *, vectors=None, ids=None) -> dict:
        """The write-path op the queue routes (insert / delete)."""
        if kind == "insert":
            return self.index.insert(vectors, ids)
        if kind == "delete":
            return self.index.delete(ids)
        raise ValueError(
            f"unknown write kind {kind!r}; expected insert|delete")

    def stats(self, **kw) -> dict:
        snap = self.index._snapshot()
        try:
            out = snap.engine.stats(**kw)
        except TypeError:
            out = snap.engine.stats()
        out["index"] = self.index.stats()
        return out
