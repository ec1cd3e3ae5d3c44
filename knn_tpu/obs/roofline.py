"""Analytic per-config roofline model — the attribution layer behind
every MFU number the repo reports.

``mfu`` alone says "6% of peak" without saying what the hardware
ceiling for *this* config actually is, so nothing in the system could
name the resource binding a given (shape, precision, kernel, geometry)
point — the gap ROADMAP item 1 exists to close.  This module computes,
term by term and jax-free:

- **HBM bytes moved** per sweep: the db operand stream (bf16 hi+lo =
  4 B/elem, the fused ``bf16x3f`` contraction 6 B/elem, int8 1 B/elem,
  f32 4 B/elem — mirroring exactly what ``ops.pallas_knn`` streams),
  the norms/aux block (8 f32 sublane rows; int8 stacks scales under
  norms, 16 rows), the re-fetched query blocks, and the candidate
  output round-trip.  Grid order matters: ``query_major`` (and the
  streaming kernel, inherently query-major) re-streams the full db
  once per query block; ``db_major`` at single-chunk dims streams it
  ONCE per sweep (ops.pallas_knn.GRID_ORDERS).
- **MXU FLOPs**: the distance matmul's *executed* passes (bf16x3 /
  bf16x3f = 3 MXU passes, f32-"highest" = 6, int8 = 1 counted at the
  MXU's int8 rate) beside the *useful* 2·nq·n·d the headline MFU
  divides by.
- **VPU select cost**: ops per score element for the grouped / lane
  in-kernel selects and the XLA ``lax.top_k`` / ApproxTopK paths —
  calibration constants from the measured cost model in docs/PERF.md.

Each term divides by the device's peak (``PEAKS_BY_KIND``, the single
source of truth) to a time; the largest term names the bound class, and the combined
time reflects whether the select can hide in the stream's shadow::

    non-fused / XLA:  ceiling_qps = nq / (max(t_hbm, t_mxu) + t_vpu)
    kernel="fused":   ceiling_qps = nq / max(t_hbm, t_mxu, t_vpu)
    bound_class in {"hbm_bound", "mxu_bound", "vpu_select_bound"}
    roofline_pct = measured_qps / ceiling_qps

The distance matmul overlaps the db stream in every kernel (that IS
the double buffer), but the select runs AFTER each tile's scores
exist — serialized — except in the fused kernel, whose in-loop
carry/early-out select rides the HBM stream (``select_overlapped`` on
the block says which formula applied; MODEL_VERSION 2).  The ceiling
assumes peak-rate execution of every term, so ``roofline_pct <= 1`` up
to peak-table error — a pct near 1 means the config is done and the
*model's* bound must move (different precision, grid order, geometry);
a low pct names implementation slack.  Everything here is pure arithmetic on plain
numbers: the autotuner and the ``cli roofline`` subcommand run it
without importing JAX.

MODEL_VERSION 3 closes the analytic/measured gap: every block consults
the calibration overlay (:mod:`knn_tpu.obs.calibrate`, fed by the
device-trace / host-phase reconciler over :mod:`knn_tpu.obs.traceread`)
— an applied calibration re-times the terms by their measured scale
factors and splits ``ceiling_qps`` (measured) from
``ceiling_qps_analytic``; absent one, the block says
``calibration: {applied: false}`` explicitly.

Derivation, peak-table provenance, how to read ``bound_class``, and
calibration: docs/PERF.md "Roofline model" and
"Calibration & measured ceilings".
"""

from __future__ import annotations

import re
import threading
from typing import Dict, Optional, Tuple

from knn_tpu.analysis import vmem as _vmem
from knn_tpu.analysis import widths as _widths
from knn_tpu.obs import names, registry, trace

#: bump when the model's terms/peaks/output schema change: the tuning
#: cache embeds this in its key (tuning.cache.roofline_token), so
#: persisted winners carrying attributions from an older model
#: self-invalidate instead of republishing a stale verdict.
#: 2 = the select-overlap refinement: non-fused kernels SERIALIZE the
#: select after the stream (``max(t_hbm, t_mxu) + t_vpu``); the fused
#: kernel rides the select in the HBM stream's shadow
#: (``max(t_hbm, t_mxu, t_vpu)``) — so the fused int8/streaming arm's
#: modeled ceiling rises above the non-fused one, which is exactly the
#: gap the in-kernel fused select exists to close.
#: 3 = the CALIBRATED model: every block consults the measured-term
#: calibration overlay (knn_tpu.obs.calibrate, ``KNN_TPU_CALIBRATION``)
#: and gains an explicit ``calibration`` verdict — when a reconciled
#: device measurement covers the block's shape key, the per-term scale
#: factors re-time the terms and ``ceiling_qps`` becomes the MEASURED
#: ceiling beside the untouched ``ceiling_qps_analytic``; when none
#: does, ``calibration: {applied: false}`` says so explicitly (a line
#: can never silently claim calibrated).  The ``estimated`` flag keeps
#: its PR-6 semantics either way: it names the PEAK TABLE's provenance,
#: not the overlay's.
#: 4 = the multi-host DCN merge term: blocks modeled with ``db_hosts >
#: 1`` gain a ``terms.dcn`` entry pricing the cross-host top-k merge
#: volume (parallel.crossover.merge_bytes at the chosen ring/allgather
#: strategy) against the per-host DCN bandwidth — serialized AFTER the
#: per-host compute (a global merge cannot complete before its inputs),
#: so ``ceiling_qps = nq / (combined_compute_time + t_dcn)`` and
#: ``bound_class`` may read ``dcn_bound``.  Single-host blocks are
#: numerically unchanged; the bump re-keys the tuning cache and
#: calibration store so pre-DCN attributions self-invalidate.
#: 5 = the IVF probed-bytes term: ``nprobe``/``ncentroids`` on a block
#: scale every row-proportional term by ``probe_fraction = nprobe /
#: ncentroids`` — a probed search streams and scores only the gathered
#: lists (``expected_probe_fraction × db stream``), which is the whole
#: point of the tier — plus a centroid-scan add-on (the [C, d] table
#: bytes + ``2·nq·C·d`` assign flops) pricing the probe itself, under
#: ``terms.probe``.  Blocks without the knobs are numerically
#: unchanged; probed blocks skip the calibration overlay (no measured
#: entry covers a pruned stream yet — an explicit absent verdict beats
#: mis-scaling) and the bump re-keys the tuning cache and calibration
#: store so pre-IVF attributions self-invalidate.
#: 6 = the sub-int8 byte widths (PR 17): the per-precision width
#: tables move to :mod:`knn_tpu.analysis.widths` (ONE shared home with
#: analysis.vmem / analysis.hbm) and the model prices the new arm —
#: "pq" streams ``ceil(d / dsub)`` code bytes per row,
#: re-fetches the per-query [nq, m·ncodes] f32 LUT per db tile in
#: place of the query blocks, and its executed MXU flops are the
#: one-hot expansion dot the kernel actually runs
#: (``2·nq·n·m·ncodes``) plus the LUT build — honestly mxu-heavy,
#: which is why PQ's win is the byte term and its natural home is the
#: IVF composition (probed blocks gather PQ codes).  The bump re-keys
#: the tuning cache and calibration store so v5 attributions
#: self-invalidate.
#: 7 = the bulk kNN-join model (:func:`join_cost_model`): a joined
#: superblock of S query rows streams the db ONCE per dispatch, so the
#: modeled db HBM bytes PER QUERY fall as 1/S (the amortization the
#: join engine exists for) until another term binds; the block gains a
#: ``terms.h2d`` entry pricing the host->device stream the byte model
#: plans (analysis.hbm.plan_join's winning nesting order) against the
#: host-link bandwidth (H2D_GBPS_* — the PCIe attach, not HBM), and
#: because the engine double-buffers, h2d OVERLAPS device compute:
#: steady-state time is ``max(t_device, t_h2d)`` and ``bound_class``
#: can read the new ``h2d_bound``.  Serving blocks are numerically
#: unchanged; the bump re-keys the tuning cache (rl7) and calibration
#: store (cal7) so v6 attributions self-invalidate.
#: 8 = the tiled kernel never cuts a row tile's columns (PR 46: a tile
#: too large for VMEM at its whole width is cut by ROWS,
#: analysis.vmem.row_blocking), so its query block's mapped index
#: moves with the query block alone and it streams ONCE a query block
#: under ``query_major`` (``terms.hbm.bytes.queries`` = nq·d·elem, not
#: that times the db tiles: 0.98 GB less of 66 at GIST's shape).  The
#: other kernels, ``db_major`` and every other term are numerically
#: unchanged; the bump re-keys the tuning cache (rl8) and calibration
#: store (cal8).
MODEL_VERSION = 8

#: the resources a config can exhaust, in tie-break order (dcn_bound
#: only appears on multi-host blocks, db_hosts > 1; h2d_bound only on
#: join blocks, where the query stream's host link can bind)
BOUND_CLASSES = ("hbm_bound", "mxu_bound", "vpu_select_bound",
                 "dcn_bound", "h2d_bound")

#: per-device-kind peaks (public spec sheets).  ``hbm_gbps`` is
#: the chip's HBM bandwidth in GB/s; ``int8_flops`` the int8 MXU rate
#: (2x bf16 on every announced generation; v7's fp8 4614 TF/s stands in
#: for int8 there); ``vpu_ops`` is the vector-unit element-op rate —
#: ESTIMATED: v5e is anchored at the ~3.9 Tops/s the measured cost
#: model in docs/PERF.md calibrated, other kinds scale by their MXU
#: ratio.  An unknown kind gets no default: peaks_for raises.
PEAKS_BY_KIND: Dict[str, Dict[str, float]] = {
    "TPU v2":      {"bf16_flops": 46e12,   "int8_flops": 92e12,
                    "hbm_gbps": 700.0,  "vpu_ops": 0.9e12},
    "TPU v3":      {"bf16_flops": 123e12,  "int8_flops": 246e12,
                    "hbm_gbps": 900.0,  "vpu_ops": 2.4e12},
    "TPU v4":      {"bf16_flops": 275e12,  "int8_flops": 550e12,
                    "hbm_gbps": 1228.0, "vpu_ops": 5.4e12},
    "TPU v4i":     {"bf16_flops": 138e12,  "int8_flops": 276e12,
                    "hbm_gbps": 614.0,  "vpu_ops": 2.7e12},
    "TPU v5 lite": {"bf16_flops": 197e12,  "int8_flops": 394e12,
                    "hbm_gbps": 819.0,  "vpu_ops": 3.9e12},
    "TPU v5e":     {"bf16_flops": 197e12,  "int8_flops": 394e12,
                    "hbm_gbps": 819.0,  "vpu_ops": 3.9e12},
    "TPU v5":      {"bf16_flops": 459e12,  "int8_flops": 918e12,
                    "hbm_gbps": 2765.0, "vpu_ops": 9.1e12},
    "TPU v5p":     {"bf16_flops": 459e12,  "int8_flops": 918e12,
                    "hbm_gbps": 2765.0, "vpu_ops": 9.1e12},
    "TPU v6 lite": {"bf16_flops": 918e12,  "int8_flops": 1836e12,
                    "hbm_gbps": 1640.0, "vpu_ops": 18.2e12},
    "TPU v6e":     {"bf16_flops": 918e12,  "int8_flops": 1836e12,
                    "hbm_gbps": 1640.0, "vpu_ops": 18.2e12},
    "TPU v6":      {"bf16_flops": 918e12,  "int8_flops": 1836e12,
                    "hbm_gbps": 1640.0, "vpu_ops": 18.2e12},
    "TPU v6p":     {"bf16_flops": 1847e12, "int8_flops": 3694e12,
                    "hbm_gbps": 7370.0, "vpu_ops": 36.6e12},
    # Ironwood: 4614 TFLOP/s fp8 per chip; bf16 assumed half
    "TPU v7":      {"bf16_flops": 2307e12, "int8_flops": 4614e12,
                    "hbm_gbps": 7370.0, "vpu_ops": 45.7e12},
    "TPU v7x":     {"bf16_flops": 2307e12, "int8_flops": 4614e12,
                    "hbm_gbps": 7370.0, "vpu_ops": 45.7e12},
}

#: the generic fallback for CPU backends / unknown device kinds: one
#: modern core's SIMD matmul (~100 GFLOP/s), dual-channel DRAM
#: (~25 GB/s), and a vector-select rate in the same ballpark as the
#: matmul.  Deliberately round numbers — any block computed from them
#: carries ``estimated: true`` and exists so CPU microbench lines stop
#: being attribution-blind, not to be defended to a digit.
GENERIC_CPU_PEAKS: Dict[str, float] = {
    "bf16_flops": 100e9, "int8_flops": 200e9,
    "hbm_gbps": 25.0, "vpu_ops": 50e9, "dcn_gbps": 5.0,
}

#: per-host DCN bandwidth (GB/s) by device kind for the cross-host
#: merge term — ESTIMATED from public inter-slice networking figures
#: (~100-200 Gbps NICs per host on v4+ pods, less on v2/v3); like
#: ``vpu_ops`` these exist to rank configurations and name the bound,
#: not to be defended to a digit.  Kinds absent here fall back to
#: DCN_GBPS_DEFAULT.
DCN_GBPS_BY_KIND: Dict[str, float] = {
    "TPU v2": 12.5, "TPU v3": 12.5,
}
DCN_GBPS_DEFAULT = 25.0


def dcn_gbps_for(device_kind, peaks) -> float:
    """The per-host DCN bandwidth a block's dcn term divides by:
    an explicit ``dcn_gbps`` in a caller-supplied peaks dict wins,
    else the kind table, else the v4+ default."""
    if peaks and "dcn_gbps" in peaks:
        return float(peaks["dcn_gbps"])
    return DCN_GBPS_BY_KIND.get(device_kind or "", DCN_GBPS_DEFAULT)


#: host->device link bandwidth (GB/s) for the join model's h2d query-
#: stream term — the PCIe attach between the host's RAM (where a
#: super-HBM query set lives) and the chip, NOT HBM.  ESTIMATED from
#: public attach generations (gen3 x16 ~16 GB/s on v2/v3 era hosts,
#: gen4+ on later kinds); like ``vpu_ops``/``dcn_gbps`` these rank
#: configurations and name the bound, not defend a digit.  Kinds
#: absent here fall back to H2D_GBPS_DEFAULT.
H2D_GBPS_BY_KIND: Dict[str, float] = {
    "TPU v2": 8.0, "TPU v3": 8.0,
}
H2D_GBPS_DEFAULT = 16.0


def h2d_gbps_for(device_kind, peaks) -> float:
    """The host->device bandwidth a join block's h2d term divides by:
    an explicit ``h2d_gbps`` in a caller-supplied peaks dict wins, else
    the kind table, else the gen4-attach default."""
    if peaks and "h2d_gbps" in peaks:
        return float(peaks["h2d_gbps"])
    return H2D_GBPS_BY_KIND.get(device_kind or "", H2D_GBPS_DEFAULT)

#: db operand stream width per element, by kernel matmul precision —
#: EXACTLY what ops.pallas_knn._bin_candidates builds, living since
#: MODEL_VERSION 6 in the ONE shared width table
#: (:mod:`knn_tpu.analysis.widths`) so the cost model, the VMEM launch
#: budget, and the HBM placement budget can never drift.  These names
#: are VIEWS of that table (``is``-identity, pinned by
#: tests/test_analysis.py); tests/test_roofline.py additionally pins
#: them against the actual operand arrays' nbytes.
DB_ELEM_BYTES = _widths.DB_ELEM_BYTES

#: f32 sublane rows of the per-tile aux block (norms; int8 stacks
#: scales under norms) — ops.pallas_knn's aux_rows
AUX_ROWS = _widths.AUX_ROWS
AUX_ROWS_DEFAULT = _widths.AUX_ROWS_DEFAULT

#: query operand width per element (int8 queries quantize in the
#: XLA prologue and stream as int8 + a [block_q, 128] f32 scale block;
#: pq's query-side operand is the per-query LUT — pq_lut_bytes)
QUERY_ELEM_BYTES = _widths.QUERY_ELEM_BYTES
QUERY_ELEM_BYTES_DEFAULT = _widths.QUERY_ELEM_BYTES_DEFAULT

#: executed MXU passes over the 2·nq·n·d useful flops, by precision:
#: bf16x3/bf16x3f reconstruct the f32 product in three bf16 passes,
#: "highest" is the native six-pass f32 path, int8 is one pass at
#: the int8 MXU rate.  "pq" is nominally one pass but
#: its executed flops are shape-dependent (the one-hot dot's
#: ``m·ncodes`` contraction width) — pallas_cost_model prices that
#: directly.
MXU_PASSES: Dict[str, int] = {
    "bf16x3": 3, "bf16x3f": 3, "highest": 6, "int8": 1, "pq": 1,
}

#: VPU element-ops per score element for the in-kernel select — the
#: measured cost model's calibration (docs/PERF.md: "grouped select
#: ~12 VPU ops x 4.1e9 score elements")
SELECT_OPS = 12.0

#: VPU element-ops per score element for the XLA selectors: a full
#: ``lax.top_k`` over a db-wide row measured ~30x the distance matmul
#: (the "selection-bound" finding the Pallas kernel exists to fix);
#: the hardware ApproxTopK coarse pass plus the count-below compare is
#: far cheaper.  Rough calibration constants — they set a CEILING, and
#: both XLA paths sit well under it.
XLA_SELECT_OPS: Dict[str, float] = {"exact": 32.0, "approx": 12.0}

#: kernel geometry defaults mirrored from ops.pallas_knn (TILE_N /
#: BLOCK_Q / grouped survivors=2) so this module stays jax-free; a
#: test pins them against the kernel module's constants
TILE_N_DEFAULT = 16384
BLOCK_Q_DEFAULT = 128
BIN_W = 128
SURVIVORS_GROUPED_DEFAULT = 2
DIM_CHUNK = _widths.DIM_CHUNK
#: mirror of ops.pallas_knn.MAX_CARRY_DEPTH (pinned by the same test):
#: past ceil((k+margin+2)/128) carry stats per lane the fused kernel
#: DISARMS its early-out and runs the plain serialized streaming path,
#: so the model must stop granting those configs the overlapped ceiling
MAX_CARRY_DEPTH = 8

#: matmul dtype widths for the XLA (non-pallas) selectors
_DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float64": 8}
_DTYPE_PASSES = {"bfloat16": 1, "float32": 6, "float64": 6}

_METRIC_RE = re.compile(r"^knn_qps_.+_n(?P<n>\d+)_d(?P<d>\d+)_k(?P<k>\d+)$")

_lock = threading.Lock()
#: config label -> last published compact attribution (/statusz renders
#: these); bounded so a label-churning process can't grow it forever
_LAST: Dict[str, dict] = {}
_LAST_MAX = 16
#: every label ever published in this process — the publish-once dedup
#: surface (:func:`was_published`).  Deliberately NOT the bounded
#: ``_LAST`` store: eviction there must not re-open a label for
#: re-publication on a warm-cache hot path.  Labels are config shapes,
#: bounded in practice.
_PUBLISHED: set = set()


def peaks_for(device_kind: Optional[str] = None,
              backend: Optional[str] = None) -> Tuple[Dict[str, float], bool]:
    """(peaks, estimated): the device's peak record, or the generic CPU
    estimate with ``estimated=True`` on a cpu backend (and for a
    device-less model call that names no kind at all) — a flagged
    estimate beats an attribution-blind CPU line.  An accelerator whose
    ``device_kind`` is not in :data:`PEAKS_BY_KIND` is an error, not a
    default: a roofline share against somebody else's peaks is wrong
    in a way nothing downstream can see."""
    if backend == "cpu" or (backend is None
                            and device_kind in (None, "", "cpu")):
        return dict(GENERIC_CPU_PEAKS), True
    if device_kind in PEAKS_BY_KIND:
        return dict(PEAKS_BY_KIND[device_kind]), False
    raise ValueError(
        f"device kind {device_kind!r} (backend {backend!r}) is not in "
        f"obs.roofline.PEAKS_BY_KIND; add its peaks (with their source) "
        f"before modeling it")


def _ceil_div(a: int, b: int) -> int:
    return -(-int(a) // int(b))


def db_operand_nbytes(n: int, d: int, precision: str, *,
                      dsub: Optional[int] = None) -> Dict[str, int]:
    """Bytes of the db-side operands ONE full-db stream moves — the
    values array(s) plus the lane-major aux block — matching the arrays
    ``ops.pallas_knn._bin_candidates`` actually builds (the property
    test compares against their ``nbytes``).  The shape-dependent arm
    routes through ``widths.db_row_bytes``: "pq" streams
    ``ceil(d / dsub)`` code bytes per row."""
    return {
        "db_values": int(n) * _widths.db_row_bytes(d, precision,
                                                   dsub=dsub),
        "db_aux": int(n) * _widths.aux_rows_for(precision) * 4,
    }


def _combined(times: Dict[str, float], select_overlapped: bool) -> float:
    # the DCN merge serializes AFTER the per-host compute: a global
    # merge cannot complete before its inputs exist
    t_dcn = times.get("dcn_bound", 0.0)
    compute = {k: v for k, v in times.items() if k != "dcn_bound"}
    if select_overlapped:
        return max(compute.values()) + t_dcn
    return max(compute["hbm_bound"], compute["mxu_bound"]) + \
        compute["vpu_select_bound"] + t_dcn


def _terms_to_verdict(model: dict, nq: int,
                      select_overlapped: bool = False) -> None:
    """Fill ceiling_qps + bound_class from the per-term times.  The
    bound class is the largest term (ties break in BOUND_CLASSES
    order); the ceiling's combined time depends on whether the select
    overlaps the stream: non-fused kernels and the XLA selectors run
    the select AFTER the streamed scores exist —
    ``max(t_hbm, t_mxu) + t_vpu`` — while the fused kernel's in-loop
    select rides the HBM stream's shadow, ``max`` of all three.

    MODEL_VERSION 3: the verdict then consults the calibration overlay
    (:mod:`knn_tpu.obs.calibrate`) — an applied calibration re-times
    every term by its measured scale factor, making ``ceiling_qps``
    the MEASURED ceiling (``ceiling_qps_analytic`` keeps the
    spec-sheet one), and ``bound_class`` names the binding term of the
    CALIBRATED machine.  With no overlay the analytic numbers stand,
    under an explicit ``calibration: {applied: false}``."""
    terms = model["terms"]
    times = {
        "hbm_bound": terms["hbm"]["time_s"],
        "mxu_bound": terms["mxu"]["time_s"],
        "vpu_select_bound": terms["vpu_select"]["time_s"],
    }
    if "dcn" in terms:
        times["dcn_bound"] = terms["dcn"]["time_s"]
    bound = max(times, key=lambda c: (times[c], -BOUND_CLASSES.index(c)))
    t = _combined(times, select_overlapped)
    model["bound_class"] = bound
    model["select_overlapped"] = bool(select_overlapped)
    model["ceiling_qps"] = round(nq / t, 1) if t > 0 else None
    model["ceiling_qps_analytic"] = model["ceiling_qps"]
    model["term_times_s"] = {k: round(v, 6) for k, v in times.items()}
    _consult_calibration(model, nq, times, select_overlapped)


def _consult_calibration(model: dict, nq: int,
                         times: Dict[str, float],
                         select_overlapped: bool) -> None:
    """Overlay the persisted measured-term factors onto this block, if
    the calibration store covers its shape key.  Failure-proof: a
    broken store degrades to the analytic verdict with the reason on
    the block — the model must render even when the overlay cannot."""
    from knn_tpu.obs import calibrate

    if "dcn_bound" in times:
        # multi-host blocks: no calibration entry covers the DCN term
        # yet; an explicit absent verdict beats silently mis-scaling
        # three of four terms
        model["calibration"] = {
            "applied": False,
            "note": "multi-host blocks use the analytic DCN model"}
        return
    if "probe" in model.get("terms", {}):
        # probed (IVF) blocks: every measured entry covers a full-db
        # stream; applying its factors to a pruned stream would claim a
        # measured ceiling for an unmeasured shape
        model["calibration"] = {
            "applied": False,
            "note": "probed blocks use the analytic IVF model"}
        return
    try:
        entry = calibrate.lookup_for_block(model)
    except Exception as e:  # noqa: BLE001 — overlay must not kill the model
        model["calibration"] = {
            "applied": False,
            "error": f"{type(e).__name__}: {e}"}
        return
    if entry is None:
        model["calibration"] = {"applied": False}
        return
    # a factor is a fit AGAINST one combined-time formula; the kernel
    # axis in the store key should make this unreachable, but a
    # hand-edited store must degrade to analytic, never mis-apply
    if "select_overlapped" in entry and \
            bool(entry["select_overlapped"]) != bool(select_overlapped):
        model["calibration"] = {
            "applied": False,
            "error": "entry fit under the other select-overlap formula"}
        return
    factors = entry.get("factors") or {}
    cal_times = {
        "hbm_bound": times["hbm_bound"] * float(factors.get("hbm", 1.0)),
        "mxu_bound": times["mxu_bound"] * float(factors.get("mxu", 1.0)),
        "vpu_select_bound": times["vpu_select_bound"]
        * float(factors.get("vpu_select", 1.0)),
    }
    t = _combined(cal_times, select_overlapped)
    if t <= 0:
        model["calibration"] = {"applied": False,
                                "error": "non-positive calibrated time"}
        return
    model["ceiling_qps"] = round(nq / t, 1)
    model["bound_class"] = max(
        cal_times,
        key=lambda c: (cal_times[c], -BOUND_CLASSES.index(c)))
    model["term_times_calibrated_s"] = {
        k: round(v, 6) for k, v in cal_times.items()}
    model["calibration"] = {
        "applied": True,
        "factors": dict(factors),
        "method": entry.get("method"),
        "source": entry.get("source"),
        "age_s": calibrate.entry_age_s(entry),
        "samples": entry.get("samples"),
        "model_residual_pct": entry.get("model_residual_pct"),
        "term_residual_pct": entry.get("term_residual_pct"),
        "measured_at": entry.get("measured_at"),
        "provenance": entry.get("provenance"),
    }


def _probe_setup(n: int, d: int, nq: int, nprobe: Optional[int],
                 ncentroids: Optional[int]):
    """The MODEL_VERSION-5 IVF pruning substitution: ``(n_eff, probe)``
    where ``n_eff`` is the expected row count a probed search actually
    streams (``ceil(n * nprobe / ncentroids)`` — balanced lists, the
    training objective) and ``probe`` prices the centroid scan the
    pruning costs: the [C, d] f32 table plus the per-query [C] f32
    distances, and ``2·nq·C·d`` assign flops.  Both knobs None → the
    identity ``(n, None)``; exactly one set is a config error."""
    if nprobe is None and ncentroids is None:
        return int(n), None
    if nprobe is None or ncentroids is None:
        raise ValueError("nprobe and ncentroids must be set together")
    cc = max(1, int(ncentroids))
    pp = min(max(1, int(nprobe)), cc)
    n_eff = _ceil_div(int(n) * pp, cc)
    return n_eff, {
        "nprobe": pp,
        "ncentroids": cc,
        "probe_fraction": pp / cc,
        "rows_probed": int(n_eff),
        "centroid_table_bytes": int(cc * d * 4 + nq * cc * 4),
        "assign_flops": 2.0 * nq * cc * d,
    }


def _dcn_term(nq: int, k: int, db_hosts: int, dcn_merge: Optional[str],
              device_kind, peaks) -> Optional[dict]:
    """The MODEL_VERSION-4 cross-host merge term, or None on a
    single-host config: the hierarchical merge's DCN candidate volume
    (parallel.crossover.merge_bytes at the resolved strategy) over the
    per-host DCN bandwidth."""
    hosts = max(1, int(db_hosts))
    if hosts <= 1:
        return None
    from knn_tpu.parallel import crossover

    strategy = dcn_merge or crossover.choose_merge(k, hosts)
    nbytes = crossover.merge_bytes(nq, k, hosts, strategy)
    rate = dcn_gbps_for(device_kind, peaks)
    return {
        "bytes": int(nbytes),
        "strategy": strategy,
        "hosts": hosts,
        "rate_gbps": rate,
        "time_s": nbytes / (rate * 1e9),
    }


def pallas_cost_model(
    *, n: int, d: int, k: int, nq: int,
    precision: Optional[str] = None, kernel: Optional[str] = None,
    grid_order: Optional[str] = None,
    tile_n: Optional[int] = None, block_q: Optional[int] = None,
    survivors: Optional[int] = None, margin: int = 28,
    device_kind: Optional[str] = None, backend: Optional[str] = None,
    num_devices: int = 1, peaks: Optional[Dict[str, float]] = None,
    db_hosts: int = 1, dcn_merge: Optional[str] = None,
    nprobe: Optional[int] = None, ncentroids: Optional[int] = None,
    pq_dsub: Optional[int] = None, pq_ncodes: Optional[int] = None,
) -> dict:
    """The roofline model of one Pallas-selector config (see module
    docstring for the terms).  ``None`` knobs take the library defaults
    the kernel itself would (tile 16384, block_q 128, grouped
    survivors 2).  Sharding is modeled as perfect scaling: each of
    ``num_devices`` devices streams ``n / num_devices`` rows in
    parallel.  ``db_hosts > 1`` adds the cross-host DCN merge term
    (MODEL_VERSION 4): the hierarchical top-k merge ships each host's
    ``[nq, k]`` candidate list over DCN at the ``dcn_merge`` strategy
    (None = the measured crossover pick), serialized after the
    per-host compute.  ``nprobe``/``ncentroids`` (MODEL_VERSION 5)
    scale the streamed rows by the expected probe fraction and add the
    centroid-scan term (``_probe_setup``).  ``pq_dsub``/``pq_ncodes``
    (MODEL_VERSION 6) size the "pq" arm's codebook geometry — ignored
    by every other precision; None takes the widths defaults (4, 256).
    The two knob pairs COMPOSE: a probed pq block streams
    ``probe_fraction × ceil(d/dsub)`` code bytes per row, the two byte
    reductions multiplying."""
    precision = precision or "bf16x3"
    kernel = kernel or "tiled"
    if kernel not in ("tiled", "streaming", "fused"):
        raise ValueError(
            f"kernel {kernel!r} not in ('tiled', 'streaming', 'fused')")
    grid_order = grid_order or "query_major"
    tile = int(tile_n or TILE_N_DEFAULT)
    bq = int(block_q or BLOCK_Q_DEFAULT)
    estimated = False
    if peaks is None:
        peaks, estimated = peaks_for(device_kind, backend)

    n_total = int(n)
    n, probe = _probe_setup(n_total, d, nq, nprobe, ncentroids)
    n_dev = _ceil_div(n, max(1, int(num_devices)))
    tile = min(tile, max(BIN_W, _ceil_div(n_dev, BIN_W) * BIN_W))
    n_tiles = _ceil_div(n_dev, tile)
    q_blocks = _ceil_div(nq, bq)
    surv = int(survivors or SURVIVORS_GROUPED_DEFAULT)
    out_w = surv * BIN_W
    bound_w = BIN_W
    sel_ops = SELECT_OPS

    # --- HBM bytes ------------------------------------------------------
    # db stream passes: query_major (and the inherently query-major
    # streaming/fused kernels) re-stream the full db once per query
    # block; db_major streams it ONCE where a tile is one grid step but
    # degenerates to query_major traffic when the innermost axis (a cut
    # tile's row blocks) cycles between query blocks
    # (ops.pallas_knn.GRID_ORDERS)
    # (the steps of a tile on the modeled device — the tiled kernel's
    # tile is one dim chunk at every width, and cut by rows where it
    # does not fit VMEM whole: a knob set is priced before any data is
    # seen, like ``passes`` below; a kind the VMEM table lacks, a
    # cpu's, is priced as the target's.  Under db_major a cut tile's
    # row blocks cycle with the query blocks as dim chunks did)
    _, row_steps = _vmem.row_blocking(
        _ceil_div(d, DIM_CHUNK) * DIM_CHUNK, tile_n=tile, block_q=bq,
        precision=precision, kernel=kernel, out_w=out_w,
        budget_bytes=_vmem.VMEM_BYTES_BY_KIND.get(device_kind))
    if grid_order == "db_major" and row_steps == 1 and kernel == "tiled":
        db_passes = 1
    else:
        db_passes = q_blocks
    eff_dsub = int(pq_dsub or _widths.PQ_DSUB_DEFAULT)
    eff_ncodes = int(pq_ncodes or _widths.PQ_NCODES_DEFAULT)
    opnd = db_operand_nbytes(n_dev, d, precision, dsub=eff_dsub)
    db_stream = db_passes * opnd["db_values"]
    db_aux = db_passes * opnd["db_aux"]
    # query blocks re-fetch once per db tile (their mapped index cycles
    # with the dim-chunk axis, or under db_major with the query block)
    # except the tiled kernel's under query_major, whose one whole-width
    # block stays put across a query block's tiles and their row
    # blocks; int8 adds the [block_q, 128] f32
    # per-query scale block per cell; pq's query-side operand is the
    # per-query LUT ([nq, m·ncodes] f32), re-fetched per db tile in
    # place of the raw query blocks (the raw queries are consumed ONCE
    # by the XLA LUT prologue)
    q_fetches = (1 if kernel == "tiled" and grid_order == "query_major"
                 else n_tiles)
    if precision == "pq":
        queries_b = q_fetches * _widths.pq_lut_bytes(
            nq, d, dsub=eff_dsub, ncodes=eff_ncodes) + nq * d * 4
    else:
        q_elem = QUERY_ELEM_BYTES.get(precision, QUERY_ELEM_BYTES_DEFAULT)
        queries_b = q_fetches * nq * d * q_elem
    if precision == "int8":
        queries_b += q_fetches * nq * BIN_W * 4
    # candidate outputs: every (query block, db tile) cell writes its
    # disjoint (block_q, out_w) f32+i32 candidates and bound_w bounds
    # exactly once (the streaming kernel flushes the same total width
    # once per query block — identical bytes, fewer launches)
    cand_b = q_blocks * n_tiles * bq * (out_w * 8 + bound_w * 4)
    hbm_total = db_stream + db_aux + queries_b + cand_b
    if probe is not None:
        hbm_total += probe["centroid_table_bytes"]
    t_hbm = hbm_total / (peaks["hbm_gbps"] * 1e9)

    # --- MXU flops ------------------------------------------------------
    useful = 2.0 * nq * n * d
    passes = MXU_PASSES[precision]
    if precision == "pq":
        # the kernel's one dense dot contracts over the one-hot
        # expansion's m·ncodes width (ops.pallas_knn._pq_onehot_qt),
        # not d — plus the per-query LUT build in the XLA prologue.
        # Honest and mxu-heavy: PQ's win is the BYTE term, and the
        # model says so rather than pricing a gather kernel it does
        # not run.
        m_sub = _widths.pq_nsub(d, eff_dsub)
        lut_flops = _widths.pq_lut_flops(nq, d, dsub=eff_dsub,
                                         ncodes=eff_ncodes)
        executed = 2.0 * nq * n * (m_sub * eff_ncodes) + lut_flops
    else:
        executed = useful * passes
    if probe is not None:
        useful += probe["assign_flops"]
        executed += probe["assign_flops"]
    mxu_rate = peaks["int8_flops"] if precision == "int8" \
        else peaks["bf16_flops"]
    # executed flops are per-device work summed over the (perfectly
    # scaled) mesh: each device runs executed/num_devices in parallel
    t_mxu = executed / max(1, int(num_devices)) / mxu_rate

    # --- VPU select -----------------------------------------------------
    vpu_ops = nq * float(n) * sel_ops
    t_vpu = vpu_ops / max(1, int(num_devices)) / peaks["vpu_ops"]

    model = {
        "model_version": MODEL_VERSION,
        "selector": "pallas",
        "device_kind": device_kind,
        "estimated": estimated,
        "peaks": {"hbm_gbps": peaks["hbm_gbps"],
                  "mxu_flops": mxu_rate, "vpu_ops": peaks["vpu_ops"]},
        "config": {
            "n": n_total, "d": int(d), "k": int(k), "nq": int(nq),
            "precision": precision, "kernel": kernel,
            "grid_order": grid_order,
            "tile_n": tile, "block_q": bq, "survivors": surv,
            "margin": int(margin), "num_devices": int(num_devices),
            "db_hosts": max(1, int(db_hosts)),
        },
        "terms": {
            "hbm": {
                "bytes": {
                    "db_stream": int(db_stream), "db_aux": int(db_aux),
                    "queries": int(queries_b),
                    "candidates_out": int(cand_b),
                    "total": int(hbm_total),
                },
                "db_passes": int(db_passes),
                "time_s": t_hbm,
            },
            "mxu": {
                "flops_useful": useful, "flops_executed": executed,
                "passes": passes, "rate_flops": mxu_rate, "time_s": t_mxu,
            },
            "vpu_select": {
                "ops": vpu_ops, "ops_per_elem": sel_ops,
                "rate_ops": peaks["vpu_ops"], "time_s": t_vpu,
            },
        },
    }
    if precision == "pq":
        model["config"]["pq_dsub"] = eff_dsub
        model["config"]["pq_ncodes"] = eff_ncodes
        model["terms"]["mxu"]["pq_onehot_width"] = int(
            _widths.pq_nsub(d, eff_dsub) * eff_ncodes)
        model["terms"]["mxu"]["pq_lut_flops"] = float(lut_flops)
    if probe is not None:
        model["config"]["nprobe"] = probe["nprobe"]
        model["config"]["ncentroids"] = probe["ncentroids"]
        model["config"]["probe_fraction"] = probe["probe_fraction"]
        model["terms"]["probe"] = probe
    dcn = _dcn_term(nq, k, db_hosts, dcn_merge, device_kind, peaks)
    if dcn is not None:
        model["terms"]["dcn"] = dcn
    # the fused kernel's in-loop select rides the HBM stream's shadow
    # (its early-out makes the 12-op calibration an upper bound there —
    # skipped tiles pay ~1 op/elem, unmodelable statically); the
    # non-fused kernels run the select serially after each tile's
    # scores exist.  A fused config whose carry would exceed
    # MAX_CARRY_DEPTH (keep = k+margin+2 past 128*8) DISARMS in the
    # kernel and runs serialized — the model mirrors that, so the
    # pruning gate and `--best` can never rank a disarmed config
    # against a ceiling it cannot reach (the kernel's m-cap can only
    # shrink keep below this estimate, making the disarm call here
    # conservative, never optimistic)
    fused_armed = kernel == "fused" and _ceil_div(
        int(k) + int(margin) + 2, BIN_W) <= MAX_CARRY_DEPTH
    _terms_to_verdict(model, nq, select_overlapped=fused_armed)
    return model


def xla_cost_model(
    *, n: int, d: int, k: int, nq: int, selector: str = "exact",
    dtype: Optional[str] = None, batch: Optional[int] = None,
    margin: int = 28, device_kind: Optional[str] = None,
    backend: Optional[str] = None, num_devices: int = 1,
    peaks: Optional[Dict[str, float]] = None,
    db_hosts: int = 1, dcn_merge: Optional[str] = None,
    nprobe: Optional[int] = None, ncentroids: Optional[int] = None,
) -> dict:
    """Roofline for the XLA selectors: ``exact`` (coarse ``lax.top_k``,
    one db pass) and ``approx`` (ApproxTopK coarse + the count-below
    certificate matmul, two passes).  The db streams once per
    ``batch``-query chunk per pass at the placement dtype's width.
    ``nprobe``/``ncentroids`` apply the MODEL_VERSION-5 IVF pruning
    substitution exactly as in ``pallas_cost_model``."""
    if selector not in ("exact", "approx"):
        raise ValueError(f"xla selector {selector!r} not in "
                         f"('exact', 'approx')")
    dtype = dtype or "float32"
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"dtype {dtype!r} not in {sorted(_DTYPE_BYTES)}")
    bs = int(batch or nq)
    estimated = False
    if peaks is None:
        peaks, estimated = peaks_for(device_kind, backend)

    n_total = int(n)
    n, probe = _probe_setup(n_total, d, nq, nprobe, ncentroids)
    n_dev = _ceil_div(n, max(1, int(num_devices)))
    chunks = _ceil_div(nq, bs)
    passes = 1 if selector == "exact" else 2
    elem = _DTYPE_BYTES[dtype]
    db_stream = chunks * passes * n_dev * d * elem
    db_aux = chunks * passes * n_dev * 4  # f32 row norms
    queries_b = passes * nq * d * 4
    cand_b = passes * nq * min(n, k + margin) * 8
    hbm_total = db_stream + db_aux + queries_b + cand_b
    if probe is not None:
        hbm_total += probe["centroid_table_bytes"]
    t_hbm = hbm_total / (peaks["hbm_gbps"] * 1e9)

    useful = 2.0 * nq * n * d
    executed = useful * passes * _DTYPE_PASSES[dtype]
    if probe is not None:
        useful += probe["assign_flops"]
        executed += probe["assign_flops"]
    t_mxu = executed / max(1, int(num_devices)) / peaks["bf16_flops"]

    sel_ops = XLA_SELECT_OPS[selector]
    vpu_ops = nq * float(n) * sel_ops
    t_vpu = vpu_ops / max(1, int(num_devices)) / peaks["vpu_ops"]

    model = {
        "model_version": MODEL_VERSION,
        "selector": selector,
        "device_kind": device_kind,
        "estimated": estimated,
        "peaks": {"hbm_gbps": peaks["hbm_gbps"],
                  "mxu_flops": peaks["bf16_flops"],
                  "vpu_ops": peaks["vpu_ops"]},
        "config": {
            "n": n_total, "d": int(d), "k": int(k), "nq": int(nq),
            "dtype": dtype, "batch": bs, "passes": passes,
            "margin": int(margin), "num_devices": int(num_devices),
            "db_hosts": max(1, int(db_hosts)),
        },
        "terms": {
            "hbm": {
                "bytes": {
                    "db_stream": int(db_stream), "db_aux": int(db_aux),
                    "queries": int(queries_b),
                    "candidates_out": int(cand_b),
                    "total": int(hbm_total),
                },
                "db_passes": int(chunks * passes),
                "time_s": t_hbm,
            },
            "mxu": {
                "flops_useful": useful, "flops_executed": executed,
                "passes": passes * _DTYPE_PASSES[dtype],
                "rate_flops": peaks["bf16_flops"], "time_s": t_mxu,
            },
            "vpu_select": {
                "ops": vpu_ops, "ops_per_elem": sel_ops,
                "rate_ops": peaks["vpu_ops"], "time_s": t_vpu,
            },
        },
    }
    if probe is not None:
        model["config"]["nprobe"] = probe["nprobe"]
        model["config"]["ncentroids"] = probe["ncentroids"]
        model["config"]["probe_fraction"] = probe["probe_fraction"]
        model["terms"]["probe"] = probe
    dcn = _dcn_term(nq, k, db_hosts, dcn_merge, device_kind, peaks)
    if dcn is not None:
        model["terms"]["dcn"] = dcn
    _terms_to_verdict(model, nq)
    return model


def cost_model(*, selector: str = "pallas", **kwargs) -> dict:
    """One entry point over both model families: ``selector="pallas"``
    takes the kernel knobs, ``"exact"``/``"approx"`` the XLA placement
    dtype + batch."""
    if selector == "pallas":
        return pallas_cost_model(**kwargs)
    return xla_cost_model(selector=selector, **kwargs)


def join_cost_model(
    *, n_a: int, n_b: int, d: int, k: int, superblock_rows: int,
    selector: str = "exact", db_segment_rows: int = 0,
    device_kind: Optional[str] = None, backend: Optional[str] = None,
    num_devices: int = 1, peaks: Optional[Dict[str, float]] = None,
    db_hosts: int = 1, dcn_merge: Optional[str] = None,
    **selector_kwargs,
) -> dict:
    """The MODEL_VERSION-7 bulk kNN-join roofline: ``n_a`` query rows
    joined against an ``n_b``-row corpus in superblocks of
    ``superblock_rows``, per the join engine's execution shape
    (knn_tpu.join.engine).

    The device-side terms are the serving cost model of ONE superblock
    dispatch — ``nq = superblock_rows`` and (for the XLA selectors the
    stream path actually runs) ``batch = superblock_rows``, so the db
    streams ONCE per superblock and the modeled db HBM bytes PER QUERY
    are ``db_bytes / superblock_rows`` — the 1/S amortization, falling
    until ``bound_class`` flips off ``hbm_bound`` to whichever term
    stops shrinking (mxu, usually).  On top, ``terms.h2d`` prices the
    host->device stream :func:`knn_tpu.analysis.hbm.plan_join` plans
    (queries, plus the db segments when B is host-tiered, at the
    winning nesting order) against :func:`h2d_gbps_for`; the engine
    double-buffers, so the steady-state per-superblock time is
    ``max(t_device, t_h2d)`` — an h2d stream slower than compute makes
    the block ``h2d_bound``.  ``ceiling_qps`` is the steady-state JOIN
    throughput in rows of A per second; the analytic verdict stands
    (calibration entries cover serving shapes, so the block carries an
    explicit skip note)."""
    from knn_tpu.analysis import hbm as _hbm

    sb = int(superblock_rows)
    if sb < 1:
        raise ValueError(f"superblock_rows must be >= 1, got {sb}")
    base_kw = dict(
        n=n_b, d=d, k=k, nq=sb, device_kind=device_kind,
        backend=backend, num_devices=num_devices, peaks=peaks,
        db_hosts=db_hosts, dcn_merge=dcn_merge, **selector_kwargs)
    if selector in ("exact", "approx"):
        # one superblock = one chunk: the whole point of the regime
        base_kw.setdefault("batch", sb)
    model = cost_model(selector=selector, **base_kw)
    plan = _hbm.plan_join(n_a, n_b, d, superblock_rows=sb,
                          db_segment_rows=db_segment_rows)
    s = plan["superblocks"]
    h2d_total = plan["h2d_bytes"][plan["order"]]
    rate = h2d_gbps_for(device_kind, peaks)
    per_sb = h2d_total / s
    t_h2d = per_sb / (rate * 1e9)
    # re-derive the device combined time from the ANALYTIC term times
    # (a serving calibration entry fit a different batch shape; the
    # join verdict stays analytic, explicitly)
    times = dict(model["term_times_s"])
    t_dev = _combined(times, model.get("select_overlapped", False))
    t_sb = max(t_dev, t_h2d)
    hbm_b = model["terms"]["hbm"]["bytes"]
    model["terms"]["h2d"] = {
        "bytes": int(per_sb),
        "total_bytes": int(h2d_total),
        "rate_gbps": rate,
        "time_s": t_h2d,
        "overlapped": True,  # double buffering hides the smaller side
    }
    model["join"] = {
        "n_a": int(n_a),
        "superblock_rows": sb,
        "superblocks": int(s),
        "db_segments": int(plan["db_segments"]),
        "order": plan["order"],
        # the amortization headline: db HBM bytes each query costs
        "db_bytes_per_query": (hbm_b["db_stream"] + hbm_b["db_aux"])
        / sb,
        "h2d_bytes_per_query": h2d_total / max(1, int(n_a)),
        "rows_per_s_ceiling": round(sb / t_sb, 1) if t_sb > 0 else None,
    }
    times["h2d_bound"] = t_h2d
    model["bound_class"] = max(
        times, key=lambda c: (times[c], -BOUND_CLASSES.index(c)))
    model["ceiling_qps"] = round(sb / t_sb, 1) if t_sb > 0 else None
    model["ceiling_qps_analytic"] = model["ceiling_qps"]
    model["term_times_s"] = {c: round(v, 6) for c, v in times.items()}
    model.pop("term_times_calibrated_s", None)
    model["calibration"] = {
        "applied": False,
        "note": "join blocks use the analytic h2d model"}
    return model


def attribute(model: dict, measured_qps: Optional[float]) -> dict:
    """The model plus the measured verdict: ``roofline_pct`` =
    measured / ceiling (NOT clamped — a pct > 1 means the peak table or
    a term is wrong, which is a finding, not an error)."""
    out = dict(model)
    if measured_qps is not None and model.get("ceiling_qps"):
        out["measured_qps"] = round(float(measured_qps), 2)
        out["roofline_pct"] = round(
            float(measured_qps) / model["ceiling_qps"], 4)
    else:
        out["measured_qps"] = None
        out["roofline_pct"] = None
    return out


def validate_block(block) -> list:
    """Structural validation of a ``roofline`` block (tuning-cache
    entries, ``cli roofline``).  Returns a list of error strings, empty
    when well-formed.  A shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``roofline`` entry) with
    the legacy error strings byte-identical."""
    from knn_tpu.analysis.artifacts import validate

    return validate("roofline", block, style="legacy")


def config_label(n: int, d: int, k: int, *, metric: str = "l2",
                 dtype: Optional[str] = None,
                 device_kind: Optional[str] = None) -> str:
    """The registry label one attribution publishes under — the tuning
    cache key's shape prefix, so a scraped gauge and a cached winner
    name the same config."""
    kind = device_kind or "unknown"
    return (f"{kind}|n{int(n)}|d{int(d)}|k{int(k)}|{metric.lower()}|"
            f"{dtype or 'float32'}")


def publish(label: str, block: dict) -> None:
    """Export one attribution to the metrics registry + the /statusz
    store.  No-op when telemetry is disabled (``KNN_TPU_OBS=0``) — the
    roofline surface is part of the obs opt-in, like every exporter."""
    if not registry.enabled():
        return
    pct = block.get("roofline_pct")
    if pct is not None:
        registry.gauge(names.ROOFLINE_PCT, config=label).set(float(pct))
    if block.get("ceiling_qps"):
        registry.gauge(names.ROOFLINE_CEILING_QPS, config=label).set(
            float(block["ceiling_qps"]))
    bound = block.get("bound_class")
    if bound in BOUND_CLASSES:
        for cls in BOUND_CLASSES:
            registry.gauge(
                names.ROOFLINE_BOUND, config=label,
                **{"class": cls}).set(1.0 if cls == bound else 0.0)
    registry.counter(names.ROOFLINE_EVALUATIONS).inc()
    cal = block.get("calibration")
    if isinstance(cal, dict):
        from knn_tpu.obs import calibrate

        calibrate.publish(label, cal)
    compact = {
        "roofline_pct": pct,
        "ceiling_qps": block.get("ceiling_qps"),
        "ceiling_qps_analytic": block.get("ceiling_qps_analytic"),
        "bound_class": bound,
        "measured_qps": block.get("measured_qps"),
        "estimated": bool(block.get("estimated")),
        "model_version": block.get("model_version"),
        "calibration_applied": bool(
            cal.get("applied")) if isinstance(cal, dict) else False,
    }
    with _lock:
        _LAST.pop(label, None)
        _LAST[label] = compact
        while len(_LAST) > _LAST_MAX:
            _LAST.pop(next(iter(_LAST)))
        _PUBLISHED.add(label)
    trace.emit_event("roofline.publish", config=label,
                     roofline_pct=pct, bound_class=bound)


def was_published(label: str) -> bool:
    """Whether :func:`publish` ever ran for this label in this process
    (survives the bounded /statusz store's eviction) — the hot-path
    dedup ``tuning.resolve_full`` consults so a warm-cache resolve
    publishes once, not once per call."""
    with _lock:
        return label in _PUBLISHED


def last_reports() -> Dict[str, dict]:
    """The last published attributions, newest last — the /statusz +
    doctor surface (empty when nothing published or obs disabled)."""
    with _lock:
        return {k: dict(v) for k, v in _LAST.items()}


def reset() -> None:
    """Drop the published-attribution store (test isolation)."""
    with _lock:
        _LAST.clear()
        _PUBLISHED.clear()


def block_for_bench_line(rec: dict) -> Optional[dict]:
    """Best-effort attribution of one recorded result line from its own
    fields (metric-name shape, ``pallas_knobs``, ``device_kind``,
    ``device_phase_qps``/``value``).
    Returns None when the line doesn't carry enough to model."""
    m = _METRIC_RE.match(str(rec.get("metric") or ""))
    if not m:
        return None
    n, d, k = (int(m.group(g)) for g in ("n", "d", "k"))
    mode = rec.get("mode")
    device_kind = rec.get("device_kind")
    backend = rec.get("backend")
    devices = int(rec.get("devices") or 1)
    nq = int(rec.get("batch") or 4096)
    ivf = rec.get("ivf") if isinstance(rec.get("ivf"), dict) else {}
    probe_kw = ({"nprobe": int(ivf["nprobe"]),
                 "ncentroids": int(ivf["ncentroids"])}
                if ivf.get("nprobe") and ivf.get("ncentroids") else {})
    try:
        if mode == "certified_pallas":
            knobs = rec.get("pallas_knobs") or {}
            model = pallas_cost_model(
                n=n, d=d, k=k, nq=nq,
                precision=knobs.get("precision") or rec.get("precision"),
                kernel=knobs.get("kernel"),
                grid_order=knobs.get("grid_order"),
                tile_n=knobs.get("tile_n"),
                block_q=knobs.get("block_q"),
                survivors=knobs.get("survivors"),
                margin=int(knobs.get("margin") or 28),
                device_kind=device_kind, backend=backend,
                num_devices=devices, **probe_kw)
            measured = rec.get("device_phase_qps") or rec.get("value")
        elif mode in ("exact", "certified_approx"):
            model = xla_cost_model(
                n=n, d=d, k=k, nq=nq,
                selector="exact" if mode == "exact" else "approx",
                dtype=rec.get("compute_dtype"), batch=rec.get("batch"),
                device_kind=device_kind, backend=backend,
                num_devices=devices, **probe_kw)
            measured = rec.get("value")
        else:
            return None
    except (ValueError, TypeError):
        return None
    return attribute(model, measured)


def render_text(block: dict) -> str:
    """Human-readable rendering of one model/attribution — shared by
    ``cli roofline`` and doctor so both print the same shape."""
    cfg = block.get("config", {})
    lines = []
    head = (f"roofline v{block.get('model_version')} "
            f"[{block.get('selector')}] "
            f"n={cfg.get('n')} d={cfg.get('d')} k={cfg.get('k')} "
            f"nq={cfg.get('nq')}")
    if block.get("selector") == "pallas":
        head += (f" precision={cfg.get('precision')} "
                 f"kernel={cfg.get('kernel')} "
                 f"grid={cfg.get('grid_order')} "
                 f"tile_n={cfg.get('tile_n')} block_q={cfg.get('block_q')}")
    else:
        head += f" dtype={cfg.get('dtype')} batch={cfg.get('batch')}"
    lines.append(head)
    kind = block.get("device_kind") or "generic-cpu"
    est = " (ESTIMATED generic fallback peaks)" if block.get(
        "estimated") else ""
    lines.append(f"device: {kind}{est}")
    terms = block.get("terms", {})
    hb = terms.get("hbm", {})
    by = hb.get("bytes", {})
    lines.append(
        f"  hbm:        {by.get('total', 0) / 1e9:10.3f} GB  "
        f"-> {hb.get('time_s', 0) * 1e3:9.3f} ms   "
        f"(db {by.get('db_stream', 0) / 1e9:.3f} GB x "
        f"{hb.get('db_passes')} passes, aux "
        f"{by.get('db_aux', 0) / 1e9:.3f}, q "
        f"{by.get('queries', 0) / 1e9:.3f}, out "
        f"{by.get('candidates_out', 0) / 1e9:.3f})")
    mx = terms.get("mxu", {})
    lines.append(
        f"  mxu:        {mx.get('flops_executed', 0) / 1e12:10.3f} TFLOP "
        f"-> {mx.get('time_s', 0) * 1e3:9.3f} ms   "
        f"({mx.get('passes')}x passes over "
        f"{mx.get('flops_useful', 0) / 1e12:.3f} useful TFLOP at "
        f"{mx.get('rate_flops', 0) / 1e12:.0f} TF/s)")
    vp = terms.get("vpu_select", {})
    lines.append(
        f"  vpu_select: {vp.get('ops', 0) / 1e9:10.3f} Gops  "
        f"-> {vp.get('time_s', 0) * 1e3:9.3f} ms   "
        f"({vp.get('ops_per_elem')} ops/elem at "
        f"{vp.get('rate_ops', 0) / 1e12:.1f} Tops/s)")
    dc = terms.get("dcn")
    if dc:
        lines.append(
            f"  dcn:        {dc.get('bytes', 0) / 1e6:10.3f} MB  "
            f"-> {dc.get('time_s', 0) * 1e3:9.3f} ms   "
            f"({dc.get('hosts')} hosts, {dc.get('strategy')} merge at "
            f"{dc.get('rate_gbps')} GB/s)")
    pr = terms.get("probe")
    if pr:
        lines.append(
            f"  probed:     {pr.get('rows_probed', 0) / 1e6:10.3f} Mrow "
            f"of {(cfg.get('n') or 0) / 1e6:.3f} M    "
            f"(nprobe {pr.get('nprobe')}/{pr.get('ncentroids')} lists = "
            f"{pr.get('probe_fraction', 0):.4f} of db bytes, centroid "
            f"scan {pr.get('centroid_table_bytes', 0) / 1e6:.3f} MB)")
    overlap = (" select overlapped" if block.get("select_overlapped")
               else "")
    cal = block.get("calibration")
    if isinstance(cal, dict) and cal.get("applied"):
        lines.append(
            f"ceiling: {block.get('ceiling_qps')} q/s CALIBRATED "
            f"({block.get('bound_class')}{overlap}; analytic "
            f"{block.get('ceiling_qps_analytic')} q/s, model off by "
            f"{cal.get('model_residual_pct')}%, source "
            f"{cal.get('source')}, age {cal.get('age_s')}s)")
    else:
        err = (f", overlay error: {cal['error']}"
               if isinstance(cal, dict) and cal.get("error") else "")
        lines.append(f"ceiling: {block.get('ceiling_qps')} q/s "
                     f"({block.get('bound_class')}{overlap}) "
                     f"[calibration: absent{err}]")
    if block.get("roofline_pct") is not None:
        lines.append(f"measured: {block.get('measured_qps')} q/s = "
                     f"{block['roofline_pct'] * 100:.1f}% of roofline")
    return "\n".join(lines) + "\n"
