"""Deterministic kernel autotuner: enumerate a bounded knob grid, time
each candidate under ``block_until_ready`` fencing, admit only
candidates whose END RESULT is bitwise-identical to the reference
grouped kernel's, persist the winner (knn_tpu.tuning.cache).

Why a gate per candidate: every knob here changes kernel geometry or
matmul arithmetic, and round 3 proved geometry bugs can be
build-detail-dependent (a compiled-only soundness miss).  The certified
pipeline's contract is that the FINAL (distances, indices) are exact
for any knob set — so a candidate that disagrees bitwise with the
reference configuration's final answer is broken, not merely different,
and must never be eligible to win, no matter how fast it timed.

The public entry points:

- :func:`resolve` — ONE call every knob consumer goes through
  (``ShardedKNN.search_certified``, the serving engine's stats,
  ``pipeline``/``cli``): cached winner -> library
  defaults, with explicit caller overrides beating both.
- :func:`autotune` — run the search for one problem shape and persist
  the winner; a pre-existing cache entry short-circuits to ZERO
  re-timing (``counters()["candidates_timed"]`` pins that in tests and
  in the CLI's JSON output).
- ``python -m knn_tpu.cli tune`` — the command a TPU session runs once
  per shape, replacing a per-session hand search.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from knn_tpu import obs
from knn_tpu.analysis import vmem as _vmem
from knn_tpu.obs import names as _mn
from knn_tpu.tuning.cache import (PROFILES, TuneCache, cache_key,
                                  default_cache_path)

#: the knob names resolve() returns — exactly the kernel-shaping
#: keyword arguments of ShardedKNN.search_certified's pallas selector.
#: Values are the library defaults (None = the ops.pallas_knn
#: module-constant default at the use site), so a cache miss with no
#: overrides reproduces the reference behavior bit for bit.
#: ``block_q=256`` is the r05-proven promotion (docs/PERF.md round-5
#: evidence: bq256 measured 1.2-1.4x the bq128 kernel at the SIFT
#: shape on v5e) — block_q only re-blocks the query grid, the per-row
#: arithmetic is untouched, so results are bitwise-identical to the
#: old default; KERNEL_VERSION=4 re-keys the persisted winner cache so
#: entries measured against bq128 reference runs self-invalidate.
DEFAULT_KNOBS: Dict[str, object] = {
    "kernel": "tiled",
    "tile_n": None,
    "block_q": 256,
    "survivors": None,
    "precision": "bf16x3",
    "final_select": "exact",
    "grid_order": "query_major",
    "final_recall_target": None,
}

#: block_q where ``kernel`` is "streaming" or "fused" and neither the
#: caller nor a cached winner chose one.  Those kernels hold EVERY db
#: tile's candidate block in VMEM at once, so the tiled kernel's 256
#: does not carry over: Mosaic (libtpu 0.0.34, v5e, deviceless) puts
#: streaming bq256 at 126.55 of 128 MiB at SIFT and over the device at
#: GIST/GloVe, and fused bq256 over it everywhere; at 128 both compile
#: at all three benchmark shapes (71.65-86.63 MiB).
FULL_WIDTH_BLOCK_Q = 128

#: env switch for roofline-model candidate pruning in :func:`autotune`
#: — a fraction in (0, 1]: candidates whose MODELED ceiling sits below
#: ``threshold x best modeled ceiling`` are skipped before timing
#: (recorded in the entry's ``pruning`` provenance, never silently).
#: Unset/0 = off (every candidate times, the pre-pruning behavior).
PRUNE_ENV = "KNN_TPU_TUNE_PRUNE"

_counters_lock = threading.Lock()
_COUNTERS = {
    "resolve_calls": 0,      # resolve() invocations
    "cache_hits": 0,         # resolve/autotune served from the cache
    "cache_misses": 0,       # resolve fell back to defaults
    "tune_searches": 0,      # autotune() runs that actually searched
    "candidates_timed": 0,   # candidates built+timed (0 on a warm cache)
    "candidates_gated_out": 0,  # candidates rejected by the bitwise gate
    "candidates_pruned": 0,  # skipped before timing by the roofline model
    "candidates_vmem_refused": 0,  # refused by the VMEM budget gate
}


def counters() -> Dict[str, int]:
    """Snapshot of the module counters — the ``zero re-timing``
    assertion surface (a second tune/resolve pass over a warm cache
    must not move ``candidates_timed``)."""
    with _counters_lock:
        return dict(_COUNTERS)


def reset_counters() -> None:
    with _counters_lock:
        for key in _COUNTERS:
            _COUNTERS[key] = 0


#: module counter -> registry twin: the dict above stays the in-process
#: assertion surface (reset_counters() and all), the registry series are
#: the scrape-able lifetime mirror (never reset by reset_counters)
_OBS_TWIN = {
    "resolve_calls": _mn.TUNING_RESOLVES,
    "cache_hits": _mn.TUNING_CACHE_HITS,
    "cache_misses": _mn.TUNING_CACHE_MISSES,
    "tune_searches": _mn.TUNING_SEARCHES,
    "candidates_timed": _mn.TUNING_CANDIDATES_TIMED,
    "candidates_gated_out": _mn.TUNING_GATE_FAILURES,
    "candidates_pruned": _mn.TUNING_CANDIDATES_PRUNED,
    "candidates_vmem_refused": _mn.TUNING_CANDIDATES_VMEM_REFUSED,
}


def _bump(name: str, by: int = 1) -> None:
    with _counters_lock:
        _COUNTERS[name] += by
    obs.counter(_OBS_TWIN[name]).inc(by)


def _device_kind() -> str:
    import jax

    try:
        return getattr(jax.devices()[0], "device_kind", jax.default_backend())
    except Exception:  # pragma: no cover - backend init failure
        return "unknown"


def resolve_full(
    n: int, d: int, k: int, *, metric: str = "l2",
    dtype: Optional[str] = None, device_kind: Optional[str] = None,
    overrides: Optional[Dict[str, object]] = None,
    cache_path: Optional[str] = None, profile: str = "latency",
) -> Tuple[Dict[str, object], Dict[str, object]]:
    """(knobs, info): the knob set for one problem shape plus its
    provenance.  Precedence: explicit overrides (non-None values) >
    cached winner > ``DEFAULT_KNOBS`` (with ``FULL_WIDTH_BLOCK_Q`` in
    place of its block_q for the streaming and fused kernels).
    ``info`` carries ``source``
    ("cache" | "default"), the cache key/path, and which knobs an
    override pinned — the observability/serving surface.
    ``profile`` selects the tuning regime's cache row (latency =
    serving, throughput = bulk join; see :func:`cache_key`) — a miss
    in either row falls back to the same ``DEFAULT_KNOBS``."""
    _bump("resolve_calls")
    if device_kind is None:
        device_kind = _device_kind()
    key = cache_key(device_kind, n, d, k, metric, dtype, profile)
    cache = TuneCache(cache_path)
    knobs = dict(DEFAULT_KNOBS)
    entry = cache.get(key)
    if entry is not None and isinstance(entry.get("knobs"), dict):
        # unknown keys in a newer cache are dropped, known ones win
        knobs.update({kk: v for kk, v in entry["knobs"].items()
                      if kk in DEFAULT_KNOBS})
        source = "cache"
        _bump("cache_hits")
    else:
        source = "default"
        _bump("cache_misses")
    overridden = []
    for kk, v in (overrides or {}).items():
        if kk not in DEFAULT_KNOBS:
            raise ValueError(f"unknown pallas knob {kk!r}; "
                             f"expected one of {sorted(DEFAULT_KNOBS)}")
        if v is not None:
            knobs[kk] = v
            overridden.append(kk)
    if (knobs["kernel"] in ("streaming", "fused") and source == "default"
            and "block_q" not in overridden):
        knobs["block_q"] = FULL_WIDTH_BLOCK_Q
    info = {
        "source": source,
        "cache_key": key,
        "cache_path": cache.path,
        "profile": profile,
        "overridden": sorted(overridden),
    }
    if source == "cache":
        info["winner_ms"] = entry.get("winner_ms")
        info["measured_at"] = entry.get("measured_at")
        # the winner's roofline verdict rides the resolve (serving
        # stats / statusz render it without re-deriving anything) and
        # publishes to the registry ONCE per (process, config) — a
        # warm-cache hot path must not re-emit per call
        for fld in ("roofline_pct", "bound_class"):
            if entry.get(fld) is not None:
                info[fld] = entry[fld]
        rl_block = entry.get("roofline")
        if isinstance(rl_block, dict):
            from knn_tpu.obs import roofline as _roofline

            label = _roofline.config_label(
                n, d, k, metric=metric, dtype=dtype,
                device_kind=device_kind)
            info["roofline_ceiling_qps"] = rl_block.get("ceiling_qps")
            if not _roofline.was_published(label):
                _roofline.publish(label, rl_block)
    return knobs, info


def resolve(n: int, d: int, k: int, **kwargs) -> Dict[str, object]:
    """The knob set alone — see :func:`resolve_full`."""
    return resolve_full(n, d, k, **kwargs)[0]


def _label(knobs: Dict[str, object]) -> str:
    """Stable candidate label: only the knobs that deviate from the
    defaults, in sorted order ("defaults" when none do)."""
    parts = [f"{kk}={knobs[kk]}" for kk in sorted(DEFAULT_KNOBS)
             if knobs[kk] != DEFAULT_KNOBS[kk]]
    return ",".join(parts) or "defaults"


def knob_grid(level: str = "standard",
              profile: str = "latency") -> List[Dict[str, object]]:
    """The bounded, deterministic candidate grid.

    - ``"quick"``: kernel x grid_order at default geometry, plus the
      approx final select — the cheapest search that still covers both
      db-streaming strategies (CPU-interpret friendly; the CLI default
      off-TPU).
    - ``"standard"``: quick + one-at-a-time deviations of tile_n,
      block_q, and precision around the defaults — including the int8
      MXU arm (~14 candidates — a few minutes of chip time; the
      TPU-session default).  The int8 candidate rides the SAME bitwise
      end-result gate as every other: its certified search must
      reproduce the reference's final answer exactly or it can never
      win, however fast the quantized matmul times.
    - ``"full"``: the bounded product
      tile_n x block_q x grid_order x precision x kernel (~60; the
      projected-winner hunt, r5 VERDICT).  Invalid combinations
      (streaming + db_major) are skipped at enumeration, duplicates
      dropped, order deterministic.

    VMEM: a combination that fits NO known device kind at the headline
    shape (knn_tpu.analysis.vmem — the model the kernel sizes its own
    request from) is dropped at enumeration where that model is
    calibrated against Mosaic's reported need (bf16x3); the
    ``vmem-budget`` checker in ``cli lint`` holds the
    grid to that, and the runtime gate in :func:`autotune` refuses
    over-budget candidates at the REAL shape/device with provenance.
    Arms the model is not calibrated for are never dropped or refused
    on it: Mosaic decides, and a compile refusal is that candidate's
    recorded error.

    ``final_select`` is part of every level (the exact/approx deviation
    at the otherwise-winning geometries): a cached winner's
    final_select is therefore a MEASURED choice, never a default copied
    into the cache — consumers with their own final_select preference
    yield to a cache hit precisely because the hit measured it.

    ``profile`` (:data:`knn_tpu.tuning.cache.PROFILES`) picks the
    tuning regime.  ``"latency"`` (default) is the grid above,
    byte-identical to the pre-profile output.  ``"throughput"`` is the
    bulk kNN-join regime (knn_tpu.join): the same candidates PLUS a
    block_q 512/1024 ladder — at join superblock sizes the query grid
    is deep enough that larger query blocks amortize db-tile reloads a
    latency tune never sees.  The ladder is tiled-kernel only: the
    streaming/fused score blocks alone price block_q x tile_n x 4 B
    over EVERY known device kind's VMEM at block_q >= 512
    (knn_tpu.analysis.vmem at the headline shape; the ``vmem-budget``
    checker sweeps this profile's full grid too, so a calibrated
    fits-nowhere arm added here fails the lint at authoring time).
    """
    if level not in ("quick", "standard", "full"):
        raise ValueError(f"grid level {level!r} not in "
                         f"('quick', 'standard', 'full')")
    if profile not in PROFILES:
        raise ValueError(f"unknown tuning profile {profile!r}; "
                         f"expected one of {PROFILES}")
    out: List[Dict[str, object]] = []
    seen = set()

    def add(**deviations):
        knobs = dict(DEFAULT_KNOBS)
        knobs.update(deviations)
        if (knobs["kernel"] in ("streaming", "fused")
                and knobs["grid_order"] != "query_major"):
            return  # no db grid axis to reorder (ops.pallas_knn refuses)
        if (knobs["kernel"] == "fused"
                and knobs["final_select"] == "approx"):
            return  # the early-out's bitwise contract is an exact select
        if knobs["precision"] == "pq" and knobs["kernel"] == "fused":
            return  # ops.pallas_knn refuses: carry soundness unproven
            # for reconstruction-space scores
        if not _vmem.fits_some_kind(knobs, **_vmem.HEADLINE_SHAPE):
            # a calibrated arm that fits NO known device kind's VMEM at
            # the headline shape: the kernel itself would refuse it
            # everywhere (ops.pallas_knn._vmem_limit_bytes prices with
            # the same model), so timing it can only record an error
            return
        lbl = _label(knobs)
        if lbl not in seen:
            seen.add(lbl)
            out.append(knobs)

    def extend_throughput():
        # the bulk-join regime's large-block arms (tiled only — the
        # streaming/fused ones fit nowhere): block_q deviations alone, their
        # approx-select cross, the tile ladder, and the quantized-db
        # precisions whose smaller streamed bytes pair naturally with
        # deeper query blocks.  Every arm fits at least one device kind
        # at the headline shape (vmem.fits_some_kind; checker-swept).
        for bq in (512, 1024):
            add(block_q=bq)
            add(block_q=bq, final_select="approx")
            add(block_q=bq, tile_n=8192)
            for prec in ("bf16x3f", "int8"):
                add(block_q=bq, precision=prec)

    for kern in ("tiled", "streaming", "fused"):
        for order in ("query_major", "db_major"):
            add(kernel=kern, grid_order=order)
    # the full-width kernels at the block_q they resolve to when nobody
    # picks one (resolve_full): fused fits VMEM at the headline shape
    # only there (its 256 arm is dropped by the cut above), streaming
    # fits at both
    for kern in ("streaming", "fused"):
        add(kernel=kern, block_q=FULL_WIDTH_BLOCK_Q)
    add(final_select="approx")
    if level == "quick":
        if profile == "throughput":
            extend_throughput()
        return out
    for tile in (8192, 32768):
        add(tile_n=tile)
    add(block_q=128)  # the pre-r05 default, kept as the A/B deviation
    add(tile_n=32768)  # the r5-projected winner cross (bq256 is default)
    add(tile_n=32768, final_select="approx")
    for prec in ("bf16x3f", "highest", "int8"):
        add(precision=prec)
    add(precision="int8", kernel="streaming")  # the HBM-bound cross
    # the sub-int8 byte arm (PR 17): pq streams ceil(d/dsub) code
    # bytes — its candidates ride the SAME bitwise end-result gate (the
    # certified fallback repairs every reconstruction-space miss), so
    # an arm whose repaired answer drifts from the reference is
    # ineligible, never a silent winner
    add(precision="pq", kernel="streaming")
    add(precision="pq")
    # the vpu_select_bound attack the fused arm exists for, plus its
    # larger-tile r05-proven cross
    add(precision="int8", kernel="fused")
    add(kernel="fused", tile_n=32768)
    if level == "standard":
        if profile == "throughput":
            extend_throughput()
        return out
    # block_q enumerates EXPLICIT values: None would fall back to the
    # kernel-module default (128) and silently duplicate the 128 point
    # now that the tuning default is 256
    for tile, bq, order, prec, kern in itertools.product(
            (None, 8192, 32768), (256, 128),
            ("query_major", "db_major"),
            ("bf16x3", "bf16x3f", "int8"),
            ("tiled", "streaming", "fused")):
        add(tile_n=tile, block_q=bq, grid_order=order, precision=prec,
            kernel=kern)
        add(tile_n=tile, block_q=bq, grid_order=order, precision=prec,
            kernel=kern, final_select="approx")
    if profile == "throughput":
        extend_throughput()
    return out


def prune_threshold_from_env() -> Optional[float]:
    """The ``KNN_TPU_TUNE_PRUNE`` fraction, or None when pruning is off
    (unset, empty, 0, or unparseable — a typo'd switch must degrade to
    the exhaustive search, never silently prune).  Values above 1 clamp
    to 1.0: the best-modeled candidate is always kept either way."""
    raw = os.environ.get(PRUNE_ENV, "").strip()
    if not raw:
        return None
    try:
        val = float(raw)
    except ValueError:
        return None
    if val <= 0:
        return None
    return min(val, 1.0)


def prune_candidates(
    candidates: Sequence[Dict[str, object]], *, n: int, d: int, k: int,
    nq: int, threshold: float, device_kind: Optional[str] = None,
    backend: Optional[str] = None, margin: int = 28,
) -> Tuple[List[Dict[str, object]], Dict[str, dict], Optional[float]]:
    """Roofline-model candidate pruning for :func:`autotune`:
    ``(kept, pruned, best_ceiling_qps)``.  Each candidate's analytic
    ceiling (knn_tpu.obs.roofline) is computed BEFORE any timing;
    candidates whose ceiling sits below ``threshold x best`` are
    dropped from the timing loop, with their modeled ceiling recorded
    in ``pruned`` so the decision is auditable line by line.

    Guarantees, pinned in tests/test_fused_overlap.py:

    - the best-modeled candidate is ALWAYS kept (its ceiling equals
      ``best``, and ``threshold <= 1``);
    - a candidate the model CANNOT price (an error, a missing ceiling)
      is always kept — a model gap must widen the search, never hide a
      candidate;
    - every pruned record carries ``ceiling_qps < threshold * best``,
      so the property "pruning never hid a winner" is checkable after
      the fact against the pruning-off timings."""
    from knn_tpu.obs import roofline

    models: List[Tuple[Dict[str, object], Optional[dict]]] = []
    for cand in candidates:
        knobs = dict(DEFAULT_KNOBS)
        knobs.update(cand)
        try:
            model = roofline.pallas_cost_model(
                n=n, d=d, k=k, nq=nq, precision=knobs["precision"],
                kernel=knobs["kernel"], grid_order=knobs["grid_order"],
                tile_n=knobs["tile_n"],
                block_q=knobs["block_q"], survivors=knobs["survivors"],
                margin=margin, device_kind=device_kind, backend=backend)
            if not model.get("ceiling_qps"):
                model = None
        except Exception:  # noqa: BLE001 — a model gap never prunes
            model = None
        models.append((cand, model))
    ceilings = [m["ceiling_qps"] for _, m in models if m is not None]
    best = max(ceilings) if ceilings else None
    kept: List[Dict[str, object]] = []
    pruned: Dict[str, dict] = {}
    for cand, model in models:
        if best is None or model is None or \
                model["ceiling_qps"] >= threshold * best:
            kept.append(cand)
            continue
        knobs = dict(DEFAULT_KNOBS)
        knobs.update(cand)
        pruned[_label(knobs)] = {
            "ceiling_qps": model["ceiling_qps"],
            "bound_class": model.get("bound_class"),
            "best_ceiling_qps": best,
            "threshold": threshold,
        }
    return kept, pruned, best


def _quantized_db(db):
    """Placement-style int8 quantization of the timing db — built ONCE
    per autotune() and shared across every int8 candidate: the values
    depend only on the db, and the production path quantizes at
    placement time (ShardedKNN._int8_placement), so charging a per-call
    (or per-candidate) quantize pass to a candidate would mis-time it."""
    import jax.numpy as jnp

    from knn_tpu.ops import quantize as qz

    qr = qz.quantize_rows_np(np.asarray(db, np.float32))
    return (jnp.asarray(qr.values), jnp.asarray(qr.scales),
            jnp.asarray(_row_norms(db)))


def _row_norms(db) -> np.ndarray:
    tn = np.empty(np.asarray(db).shape[0], np.float32)
    for lo in range(0, tn.shape[0], 65536):
        hs = np.asarray(db[lo : lo + 65536], np.float64)
        tn[lo : lo + hs.shape[0]] = (hs ** 2).sum(-1)
    return tn


def _pq_db(db):
    """Shared PQ placement for the pq candidates: train the per-subspace
    codebooks ONCE (deterministic seeded k-means on a 1x1 mesh — the
    codebooks are mesh-independent by construction) and hand the kernel
    its (codes, codebooks) operands."""
    import jax.numpy as jnp

    from knn_tpu.ops import pq as pqm
    from knn_tpu.parallel.mesh import make_mesh

    res = pqm.train_pq(np.asarray(db, np.float32), mesh=make_mesh(1, 1))
    return (jnp.asarray(res.codes), jnp.asarray(res.codebooks))


def _timed_program(m: int, knobs: Dict[str, object], db_int8=None,
                   db_pq=None):
    """The device hot path one candidate is timed on —
    ``local_certified_candidates`` (kernel + final select + rescore);
    it is itself jitted with static knob arguments, so repeated timing
    calls hit the jit cache.  ``db_int8``/``db_pq`` are the
    shared pre-quantized placements for the quantized candidates
    (:func:`_quantized_db` and twins) — only the one matching the
    candidate's precision is threaded through."""
    from knn_tpu.ops.pallas_knn import (
        BLOCK_Q,
        TILE_N,
        local_certified_candidates,
    )

    if knobs["precision"] != "int8":
        db_int8 = None
    if knobs["precision"] != "pq":
        db_pq = None

    def run(q, t):
        return local_certified_candidates(
            q, t, m,
            tile_n=knobs["tile_n"] or TILE_N,
            block_q=knobs["block_q"] or BLOCK_Q,
            survivors=knobs["survivors"],
            precision=knobs["precision"],
            final_select=knobs["final_select"],
            final_recall_target=knobs["final_recall_target"],
            grid_order=knobs["grid_order"],
            kernel=knobs["kernel"],
            db_int8=db_int8,
            db_pq=db_pq,
        )

    return run


def _candidate_roofline(knobs: Dict[str, object], n: int, d: int, k: int,
                        nq: int, ms: float, device_kind: str,
                        backend: str) -> dict:
    """One candidate's roofline attribution (knn_tpu.obs.roofline):
    the analytic ceiling for its knob set on this device kind, the
    measured fraction of it, and the bound class naming the resource
    to attack.  jax-free arithmetic on the timing already taken."""
    from knn_tpu.obs import roofline

    model = roofline.pallas_cost_model(
        n=n, d=d, k=k, nq=nq,
        precision=knobs["precision"], kernel=knobs["kernel"],
        grid_order=knobs["grid_order"],
        tile_n=knobs["tile_n"], block_q=knobs["block_q"],
        survivors=knobs["survivors"],
        device_kind=device_kind, backend=backend)
    return roofline.attribute(model, nq / (ms / 1e3) if ms > 0 else None)


def _search_once(queries, db, k, margin, knobs):
    """Full certified search under one knob set: (d, i) — the bitwise
    gate surface (final answers, the contract every knob must keep)."""
    from knn_tpu.ops.pallas_knn import TILE_N, knn_search_pallas

    d, i, _ = knn_search_pallas(
        queries, db, k, margin=margin,
        tile_n=knobs["tile_n"] or TILE_N,
        precision=knobs["precision"],
        survivors=knobs["survivors"], block_q=knobs["block_q"],
        final_select=knobs["final_select"],
        final_recall_target=knobs["final_recall_target"],
        grid_order=knobs["grid_order"], kernel=knobs["kernel"],
    )
    return d, i


def autotune(
    db, queries, k: int, *, metric: str = "l2", margin: int = 28,
    grid: Optional[Sequence[Dict[str, object]]] = None,
    grid_level: str = "standard", runs: int = 2,
    cache_path: Optional[str] = None, device_kind: Optional[str] = None,
    dtype: Optional[str] = None, force: bool = False,
    prune: Optional[float] = None, profile: str = "latency",
) -> Dict[str, object]:
    """Search the knob grid for ``(db, queries, k, metric)`` and persist
    the winner; returns the cache entry (plus ``"cached": True`` when a
    pre-existing entry short-circuited the search with zero re-timing).

    Per candidate, in deterministic grid order:

    1. **bitwise gate** — the candidate's full certified search must
       reproduce the reference configuration's final (distances,
       indices) arrays EXACTLY (``np.array_equal``); a mismatch marks
       it ineligible forever (``timings_ms[label] = None``) and it can
       never win, however fast.
    2. **fenced timing** — the device hot path
       (``local_certified_candidates``) is warmed once, then timed
       ``runs`` times with ``block_until_ready`` fencing; the mean
       wall ms is the score (JAX dispatch is async — unfenced timing
       measures dispatch, not compute; utils.timing's lesson).

    Candidates that raise (a geometry invalid for this shape) are
    recorded ineligible with the error string, not fatal — the grid is
    allowed to overshoot small problems.

    Every timed candidate also gets a **roofline attribution**
    (knn_tpu.obs.roofline): percent of its analytic ceiling plus the
    bound class naming the binding resource, with the winner's full
    block persisted in the cache entry (``roofline_pct`` /
    ``bound_class`` hoisted) — the tune record reports how far every
    point sits from the hardware, not just who won.  With
    ``KNN_TPU_PROFILE_DIR`` set, one extra fenced run of the winner is
    captured as an XLA device trace (``entry["trace_dir"]``), outside
    every timing.

    **Roofline pruning** (``prune`` arg > ``KNN_TPU_TUNE_PRUNE`` env;
    off by default): before ANY timing, every candidate's analytic
    ceiling is modeled (:func:`prune_candidates`) and candidates below
    ``threshold x best modeled ceiling`` are skipped — on hardware the
    grid's timing cost drops to the model-plausible region.  Every skip
    is recorded in ``entry["pruning"]["pruned"]`` with its modeled
    ceiling (and mirrored as a ``roofline-pruned: ...`` entry in
    ``errors``) so the decision is auditable: a pruned candidate that
    would have won the bitwise+timing gate with pruning off is a test
    failure, not a silent loss (tests/test_fused_overlap.py).

    **VMEM budget gate** (knn_tpu.analysis.vmem; always on when the
    device kind has a VMEM budget — cpu/interpret backends disarm it):
    also before any timing, every candidate's per-launch VMEM footprint
    is priced against the device kind's capacity; over-budget
    candidates of the arm the model is calibrated for
    (``vmem.calibrated``) are REFUSED — they would fail at Mosaic
    compile time on hardware, mid-tune, the worst place to discover it
    — with each refusal recorded in ``entry["vmem"]["refused"]`` and mirrored as a
    ``vmem-refused: ...`` entry in ``errors`` (provenance like roofline
    pruning; the ``vmem-budget`` checker in ``cli lint`` statically
    enforces the same model over the grid).
    """
    import jax

    if metric.lower() not in ("l2", "sql2", "euclidean"):
        raise ValueError(
            f"autotune runs the squared-L2 kernel; metric {metric!r} is "
            f"not in its family (cosine callers tune on unit vectors "
            f"with metric='l2')")
    db = np.asarray(db, dtype=np.float32)
    queries = np.asarray(queries, dtype=np.float32)
    n, d = db.shape
    if device_kind is None:
        device_kind = _device_kind()
    key = cache_key(device_kind, n, d, k, metric, dtype, profile)
    cache = TuneCache(cache_path)
    if not force:
        entry = cache.get(key)
        if entry is not None:
            _bump("cache_hits")
            return {**entry, "cached": True, "cache_key": key,
                    "cache_path": cache.path}

    _bump("tune_searches")
    candidates = (list(grid) if grid is not None
                  else knob_grid(grid_level, profile))
    for c in candidates:
        unknown = set(c) - set(DEFAULT_KNOBS)
        if unknown:
            raise ValueError(f"unknown knobs in grid candidate: {unknown}")

    # reference: the library-default grouped kernel — every candidate
    # must reproduce ITS final answer bitwise to be eligible
    ref_d, ref_i = _search_once(queries, db, k, margin, dict(DEFAULT_KNOBS))

    m = min(k + margin, n - 1)
    qj, tj = np.asarray(queries), np.asarray(db)
    # the quantized candidates' placements, built lazily ONCE each and
    # shared — they depend only on the db, never on the knobs
    shared_int8 = None
    shared_pq = None
    timings: Dict[str, Optional[float]] = {}
    errors: Dict[str, str] = {}
    rooflines: Dict[str, dict] = {}
    backend = jax.default_backend()

    # roofline-model pruning BEFORE any timing (opt-in; see docstring):
    # pre-seeding timings/errors keeps pruned candidates out of the
    # timing loop via its duplicate check while leaving a full audit
    # trail in the entry
    threshold = prune if prune is not None else prune_threshold_from_env()
    pruning_info = None
    if threshold:
        threshold = min(float(threshold), 1.0)
        kept, pruned_rec, best_ceiling = prune_candidates(
            candidates, n=n, d=d, k=k, nq=queries.shape[0],
            threshold=threshold, device_kind=device_kind,
            backend=backend, margin=margin)
        for label, rec in pruned_rec.items():
            timings[label] = None
            errors[label] = (
                f"roofline-pruned: modeled ceiling "
                f"{rec['ceiling_qps']} < {threshold} x best "
                f"{rec['best_ceiling_qps']}")
        if pruned_rec:
            _bump("candidates_pruned", len(pruned_rec))
        pruning_info = {
            "threshold": threshold,
            "best_ceiling_qps": best_ceiling,
            "candidates_modeled": len(candidates),
            "candidates_pruned": len(pruned_rec),
            "pruned": pruned_rec,
        }
        candidates = kept

    # VMEM budget gate BEFORE any timing (knn_tpu.analysis.vmem; always
    # on when the device kind has a budget — cpu/interpret backends have
    # no VMEM and the gate disarms): a candidate whose estimated
    # per-launch footprint exceeds this device kind's VMEM would fail at
    # Mosaic compile time on hardware, mid-tune, so it is refused here
    # with provenance — recorded like roofline pruning (entry["vmem"] +
    # a "vmem-refused: ..." errors line), never silently
    budget_bytes = _vmem.budget_for(device_kind, backend)
    vmem_info = None
    if budget_bytes is not None:
        refused_rec: Dict[str, dict] = {}
        kept_v: List[Dict[str, object]] = []
        for cand in candidates:
            knobs = dict(DEFAULT_KNOBS)
            knobs.update(cand)
            label = _label(knobs)
            if label in timings:
                kept_v.append(cand)  # already recorded (pruned/dup)
                continue
            try:
                verdict = _vmem.check_candidate(
                    knobs, n=n, d=d, k=k, margin=margin,
                    device_kind=device_kind, backend=backend)
            except ValueError:
                kept_v.append(cand)  # unpriceable: never widen-refuse
                continue
            if verdict["fits"] is False:
                timings[label] = None
                errors[label] = (
                    f"vmem-refused: estimated "
                    f"{verdict['estimate_bytes']} bytes/launch > "
                    f"{verdict['budget_bytes']}-byte VMEM budget of "
                    f"{device_kind}")
                refused_rec[label] = {
                    "estimate_bytes": verdict["estimate_bytes"],
                    "budget_bytes": verdict["budget_bytes"],
                }
            else:
                kept_v.append(cand)
        if refused_rec:
            _bump("candidates_vmem_refused", len(refused_rec))
        vmem_info = {
            "device_kind": device_kind,
            "budget_bytes": budget_bytes,
            "candidates_refused": len(refused_rec),
            "refused": refused_rec,
        }
        candidates = kept_v
    best_label, best_ms, best_knobs = None, None, None
    for cand in candidates:
        knobs = dict(DEFAULT_KNOBS)
        knobs.update(cand)
        label = _label(knobs)
        if label in timings:
            continue  # duplicate candidate
        try:
            if knobs != DEFAULT_KNOBS:
                d_c, i_c = _search_once(queries, db, k, margin, knobs)
                if not (np.array_equal(i_c, ref_i)
                        and np.array_equal(d_c, ref_d)):
                    _bump("candidates_gated_out")
                    timings[label] = None
                    errors[label] = "bitwise gate: result != reference"
                    continue
            if knobs["precision"] == "int8" and shared_int8 is None:
                shared_int8 = _quantized_db(db)
            if knobs["precision"] == "pq" and shared_pq is None:
                shared_pq = _pq_db(db)
            prog = _timed_program(m, knobs, db_int8=shared_int8,
                                  db_pq=shared_pq)
            out = prog(qj, tj)
            jax.block_until_ready(out)  # warm: compile outside the clock
            reps = []
            for _ in range(max(1, runs)):
                t0 = time.perf_counter()
                jax.block_until_ready(prog(qj, tj))
                reps.append(time.perf_counter() - t0)
            _bump("candidates_timed")
            ms = float(np.mean(reps)) * 1e3
            timings[label] = round(ms, 3)
            try:
                # percent-of-roofline per candidate (the FULL block,
                # byte/flop term breakdown included): the tune record
                # reports not just WHO won but how far every point sits
                # from its own analytic ceiling and which resource caps
                # it (never fatal — a model gap must not kill a
                # measurement)
                rooflines[label] = _candidate_roofline(
                    knobs, n, d, k, queries.shape[0], ms, device_kind,
                    backend)
            except Exception as e:  # noqa: BLE001 — advisory only
                rooflines[label] = {"error": f"{type(e).__name__}: {e}"}
            if best_ms is None or ms < best_ms:
                best_label, best_ms, best_knobs = label, ms, knobs
        except Exception as e:  # noqa: BLE001 — per-candidate, recorded
            timings[label] = None
            errors[label] = f"{type(e).__name__}: {e}"
    if best_knobs is None:
        raise RuntimeError(
            f"autotune: no eligible candidate for {key} "
            f"(errors: {errors})")
    # the winner's full roofline attribution persists in the cache
    # entry (roofline_pct + bound_class hoisted for cheap reads), so a
    # later warm-cache resolve can surface the verdict — and publish it
    # to the registry — without re-deriving anything
    winner_rl = rooflines.get(best_label)
    if not isinstance(winner_rl, dict) or "ceiling_qps" not in winner_rl:
        winner_rl = None
    # opt-in device trace of the winning program (KNN_TPU_PROFILE_DIR;
    # one extra fenced run OUTSIDE every timing above, so the capture
    # can never skew a persisted measurement)
    trace_dir = None
    from knn_tpu.obs import profiler as _profiler

    if _profiler.profile_dir():
        try:
            prog = _timed_program(m, best_knobs, db_int8=shared_int8,
                                  db_pq=shared_pq)
            with _profiler.device_trace(f"tune_{key}") as tdir:
                jax.block_until_ready(prog(qj, tj))
            trace_dir = tdir
        except Exception:  # noqa: BLE001 — capture must not kill the tune
            pass
    entry = {
        "knobs": best_knobs,
        "winner": best_label,
        "winner_ms": round(best_ms, 3),
        "timings_ms": timings,
        "errors": errors,
        "roofline_per_candidate": rooflines,
        "gate": "bitwise-vs-reference",
        "profile": profile,
        "runs": int(runs),
        "n_queries": int(queries.shape[0]),
        "margin": int(margin),
        "device_kind": device_kind,
        "backend": backend,
        "jax_version": jax.__version__,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if pruning_info is not None:
        entry["pruning"] = pruning_info
    if vmem_info is not None:
        entry["vmem"] = vmem_info
    if winner_rl is not None:
        entry["roofline"] = winner_rl
        entry["roofline_pct"] = winner_rl["roofline_pct"]
        entry["bound_class"] = winner_rl["bound_class"]
    if trace_dir:
        entry["trace_dir"] = trace_dir
    cache.put(key, entry)
    if winner_rl is not None:
        from knn_tpu.obs import roofline as _roofline

        _roofline.publish(
            _roofline.config_label(n, d, k, metric=metric, dtype=dtype,
                                   device_kind=device_kind),
            winner_rl)
    return {**entry, "cached": False, "cache_key": key,
            "cache_path": cache.path}


def ivf_label(cand: Dict[str, int]) -> str:
    """Stable IVF candidate label: ``c{ncentroids}p{nprobe}``."""
    return f"c{cand['ncentroids']}p{cand['nprobe']}"


def ivf_grid(n: int) -> List[Dict[str, int]]:
    """The bounded, deterministic (ncentroids, nprobe) grid for
    :func:`autotune_ivf`: ncentroids at half/default/double of the
    ``round(sqrt(n))`` heuristic (clamped so lists average >= 8 rows),
    nprobe a fraction ladder of each (1/8, 1/4, 1/2, all).  The
    ``nprobe == ncentroids`` arm of every ncentroids is ALWAYS present:
    it must reproduce exact brute force bitwise, anchoring the gate."""
    import math

    base = max(2, int(round(math.sqrt(max(1, int(n))))))
    cap = max(2, int(n) // 8)
    cands: List[Dict[str, int]] = []
    seen = set()
    for cc in (base // 2, base, base * 2):
        cc = max(2, min(int(cc), cap))
        if cc in seen:
            continue
        seen.add(cc)
        for pp in sorted({max(1, cc // 8), max(1, cc // 4),
                          max(1, cc // 2), cc}):
            cands.append({"ncentroids": cc, "nprobe": pp})
    return cands


def autotune_ivf(
    db, queries, k: int, *, mesh, metric: str = "l2", runs: int = 2,
    grid: Optional[Sequence[Dict[str, int]]] = None,
    selector: str = "exact", train_iters: Optional[int] = None,
    seed: Optional[int] = None, device_kind: Optional[str] = None,
) -> Dict[str, object]:
    """Search the IVF (ncentroids, nprobe) grid under the SAME bitwise
    end-result gate as :func:`autotune`: a candidate's certified search
    must reproduce the exact brute-force final (distances, indices)
    EXACTLY (``np.array_equal``) or it is marked ineligible forever —
    the certified fallback makes every sound candidate pass, so a
    mismatch means a broken placement, not a recall tradeoff.  The
    score is mean fenced wall ms over ``runs`` (the IVF search is
    host-orchestrated; wall clock IS its cost), with each candidate's
    probe_fraction / fallback_rate / bytes_streamed_ratio stats
    recorded so the entry shows WHY the winner wins (less bytes) and
    what it paid (fallback repairs).  One index is trained per
    ncentroids and shared across its nprobe ladder — training cost
    never skews the per-candidate timing."""
    from knn_tpu.ivf import IVFIndex
    from knn_tpu.ops.refine import refine_shared_exact

    db = np.asarray(db, dtype=np.float32)
    queries = np.asarray(queries, dtype=np.float32)
    n, d = db.shape
    if device_kind is None:
        device_kind = _device_kind()
    _bump("tune_searches")
    candidates = list(grid) if grid is not None else ivf_grid(n)
    for c in candidates:
        unknown = set(c) - {"ncentroids", "nprobe"}
        if unknown:
            raise ValueError(f"unknown knobs in ivf candidate: {unknown}")

    # reference: exact brute force over the full corpus — the same f64
    # refine anchor IVFIndex.search_certified resolves to, so every
    # sound candidate agrees bitwise by construction
    ref_d, ref_i = refine_shared_exact(
        db, queries, np.arange(n, dtype=np.int64), k, metric=metric)

    timings: Dict[str, Optional[float]] = {}
    errors: Dict[str, str] = {}
    stats_per: Dict[str, dict] = {}
    best_label, best_ms, best_knobs = None, None, None
    by_cc: Dict[int, List[int]] = {}
    for cand in candidates:
        by_cc.setdefault(int(cand["ncentroids"]), []).append(
            int(cand["nprobe"]))
    for cc, probes in sorted(by_cc.items()):
        try:
            index = IVFIndex(db, mesh=mesh, k=k, ncentroids=cc,
                             nprobe=max(probes), metric=metric,
                             train_iters=train_iters, seed=seed)
        except Exception as e:  # noqa: BLE001 — per-arm, recorded
            for pp in probes:
                label = ivf_label({"ncentroids": cc, "nprobe": pp})
                timings[label] = None
                errors[label] = f"{type(e).__name__}: {e}"
            continue
        for pp in sorted(set(probes)):
            label = ivf_label({"ncentroids": cc, "nprobe": pp})
            if label in timings:
                continue  # duplicate candidate
            try:
                d_c, i_c, st = index.search_certified(
                    queries, k=k, nprobe=pp, selector=selector)
                if not (np.array_equal(i_c, ref_i)
                        and np.array_equal(d_c, ref_d)):
                    _bump("candidates_gated_out")
                    timings[label] = None
                    errors[label] = "bitwise gate: result != reference"
                    continue
                reps = []
                for _ in range(max(1, runs)):
                    t0 = time.perf_counter()
                    _, _, st = index.search_certified(
                        queries, k=k, nprobe=pp, selector=selector)
                    reps.append(time.perf_counter() - t0)
                _bump("candidates_timed")
                ms = float(np.mean(reps)) * 1e3
                timings[label] = round(ms, 3)
                stats_per[label] = {
                    kk: st[kk] for kk in
                    ("probe_fraction", "fallback_rate", "recall_at_k",
                     "bytes_streamed_ratio", "certified_queries",
                     "fallback_queries")}
                if best_ms is None or ms < best_ms:
                    best_label, best_ms = label, ms
                    best_knobs = {"ncentroids": cc, "nprobe": pp}
            except Exception as e:  # noqa: BLE001 — per-candidate
                timings[label] = None
                errors[label] = f"{type(e).__name__}: {e}"
    if best_knobs is None:
        raise RuntimeError(
            f"autotune_ivf: no eligible candidate for n={n} d={d} k={k} "
            f"(errors: {errors})")
    return {
        "knobs": best_knobs,
        "winner": best_label,
        "winner_ms": round(best_ms, 3),
        "timings_ms": timings,
        "errors": errors,
        "stats_per_candidate": stats_per,
        "gate": "bitwise-vs-reference",
        "runs": int(runs),
        "n_queries": int(queries.shape[0]),
        "selector": selector,
        "device_kind": device_kind,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }
