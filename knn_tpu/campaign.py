"""Measured-ceiling campaign harness — ROADMAP open item 1 as a
push-button, regression-gated loop (``cli campaign`` /
``scripts/measured_ceiling_campaign.py``).

One campaign ARM = one kernel configuration (precision × db-streaming
strategy).  Per arm the harness runs the same seven stages the roadmap
describes by hand, in order, each one recorded in the arm's artifact:

1. **gates** — arm the on-hardware env gates (bench mode/knob
   overrides, live ``KNN_TPU_TUNE_PRUNE`` roofline pruning, the
   ``KNN_TPU_PROFILE_DIR`` trace capture, the ``KNN_TPU_CALIBRATION``
   store).  Rehearse mode records the gate set without flipping
   hardware-only ones.
2. **tune** — autotune the arm's pinned knobs (roofline + VMEM pruning
   live) and persist the winner.
3. **bench** — a fenced timed sweep at the winner knobs; the
   host-phase ``device_s`` measurement every later stage reconciles
   against.
4. **capture** — one extra traced run under the profiler
   (:mod:`knn_tpu.obs.profiler`), parsed back by
   :mod:`knn_tpu.obs.traceread`; rehearse additionally parses the
   checked-in trace fixture so the device-trace path is exercised
   deterministically on CPU.
5. **reconcile** — decompose the measured device time against the
   analytic roofline terms (:func:`knn_tpu.obs.calibrate.reconcile`).
6. **calibrate** — persist the per-term factors to the calibration
   store; re-render the roofline block and require
   ``calibration.applied`` with the calibrated ceiling reproducing the
   measured q/s inside the stated tolerance.
7. **curate** — validate the arm's artifact (roofline block,
   calibration field, campaign block — the same validators
   ``refresh_bench_artifacts.py`` refuses on), stamp provenance
   (commit, round), attach the sentinel verdict, and write ONE JSONL
   artifact per arm (atomic tmp+rename).

``--rehearse`` runs the identical loop on CPU against tiny synthetic
shapes and host-phase timings — tier-1 exercises every stage without a
TPU (tests/test_calibrate.py pins the loop end-to-end).  The real mode
shells out to ``bench.py`` per arm with the gates flipped, so a
hardware session is ``cli campaign --round N`` and nothing else.

Env knobs (``KNN_TPU_CAMPAIGN_*``; declared in the switch catalog):
``KNN_TPU_CAMPAIGN_DIR`` (artifact directory), ``KNN_TPU_CAMPAIGN_ARMS``
(comma list of arm names), ``KNN_TPU_CAMPAIGN_ROUND`` (round stamp).
Campaign runbook: docs/PERF.md "Calibration & measured ceilings".
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

from knn_tpu.obs import calibrate, names, profiler, registry
from knn_tpu.obs import roofline as _rl
from knn_tpu.obs import traceread

#: artifact output directory (default: artifacts/campaign under cwd)
DIR_ENV = "KNN_TPU_CAMPAIGN_DIR"
#: comma list of arm names overriding the default ladder
ARMS_ENV = "KNN_TPU_CAMPAIGN_ARMS"
#: measurement-round stamp carried into artifact provenance
ROUND_ENV = "KNN_TPU_CAMPAIGN_ROUND"

#: campaign artifact schema version (calibrate.validate_campaign_block)
CAMPAIGN_VERSION = 1

#: stage names, in execution order (the stage counter's label values)
STAGES = ("gates", "tune", "bench", "capture", "reconcile",
          "calibrate", "curate")

#: named arms: the knob pins a campaign sweeps.  The default hardware
#: ladder is the roadmap's r06 target list; rehearse defaults to the
#: cheapest arm so tier-1 stays fast.
ARM_KNOBS: Dict[str, Dict[str, object]] = {
    "bf16x3_tiled": {"precision": "bf16x3", "kernel": "tiled"},
    "bf16x3_streaming": {"precision": "bf16x3", "kernel": "streaming"},
    "int8_streaming": {"precision": "int8", "kernel": "streaming"},
    "int8_fused": {"precision": "int8", "kernel": "fused"},
    # the bulk-join throughput regime (knn_tpu.join / PERF.md "Bulk
    # kNN-join"): the tuning profile's block_q-512 ladder point, tiled
    # because the deeper query blocks fit no other kernel's VMEM
    # (tuning.knob_grid(profile="throughput"))
    "join_bq512": {"precision": "bf16x3", "kernel": "tiled",
                   "block_q": 512},
}
DEFAULT_ARMS = ("bf16x3_tiled", "bf16x3_streaming", "int8_streaming",
                "int8_fused", "join_bq512")
DEFAULT_REHEARSE_ARMS = ("bf16x3_tiled",)

#: rehearse problem shape: big enough for a non-degenerate kernel
#: geometry, small enough for tier-1
REHEARSE_SHAPE = dict(n=2048, d=32, k=5, nq=64)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def campaign_dir() -> str:
    return os.environ.get(DIR_ENV) or os.path.join(
        "artifacts", "campaign")


def arms_from_env() -> Optional[List[str]]:
    spec = os.environ.get(ARMS_ENV)
    if not spec:
        return None
    arms = [a.strip() for a in spec.split(",") if a.strip()]
    for a in arms:
        if a not in ARM_KNOBS:
            raise ValueError(f"{ARMS_ENV} names unknown arm {a!r}; "
                             f"expected one of {sorted(ARM_KNOBS)}")
    return arms or None


def round_from_env() -> Optional[int]:
    raw = os.environ.get(ROUND_ENV)
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError as e:
        raise ValueError(f"{ROUND_ENV}={raw!r} is not an int") from e


def _head_commit(repo: str) -> str:
    try:
        r = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                           cwd=repo, capture_output=True, text=True,
                           timeout=10)
        return r.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — provenance, not a gate
        return "unknown"


def _stage(log: List[dict], name: str, status: str, **detail) -> dict:
    """Record one stage outcome (and count it) — every stage of every
    arm lands in the artifact, errors included."""
    rec = {"stage": name, "status": status, **detail}
    log.append(rec)
    if registry.enabled():
        registry.counter(names.CAMPAIGN_STAGES, stage=name).inc()
    return rec


def _knobs_for_model(knobs: Dict[str, object]) -> Dict[str, object]:
    """The cost-model-relevant subset of a resolved knob dict."""
    return {
        "precision": knobs.get("precision"),
        "kernel": knobs.get("kernel"),
        "grid_order": knobs.get("grid_order"),
        "tile_n": knobs.get("tile_n"),
        "block_q": knobs.get("block_q"),
        "survivors": knobs.get("survivors"),
    }


def _write_artifact(out_dir: str, fname: str, line: dict) -> str:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, fname)
    fd, tmp = tempfile.mkstemp(dir=out_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            f.write(json.dumps(line, sort_keys=True) + "\n")
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path


def default_trace_fixture() -> Optional[str]:
    """The checked-in minimal device trace rehearse parses so the
    trace-reader path runs deterministically on CPU."""
    path = os.path.join(_REPO, "tests", "fixtures",
                        "minimal.trace.json.gz")
    return path if os.path.exists(path) else None


def _rehearse_arm(arm: str, *, out_dir: str, shape: Dict[str, int],
                  seed: int, round_no: Optional[int],
                  trace_fixture: Optional[str], grid_level: str,
                  verbose: bool) -> dict:
    """One rehearse arm: the full stage loop on CPU with host-phase
    timings (module docstring)."""
    import numpy as np

    from knn_tpu import tuning
    from knn_tpu.ops.pallas_knn import knn_search_pallas

    n, d, k, nq = (shape[f] for f in ("n", "d", "k", "nq"))
    stages: List[dict] = []
    log = (lambda msg: print(f"[{arm}] {msg}", file=sys.stderr)) \
        if verbose else (lambda msg: None)

    # 1. gates — rehearse records the gate set without flipping the
    # hardware-only ones (there is no hardware to flip)
    store = calibrate.store_path() or os.path.join(
        out_dir, "calibration.json")
    _stage(stages, "gates", "ok", rehearse_note=(
        "CPU rehearsal: on-hardware bench gates stay down; tune "
        "pruning, trace capture, and the calibration store are live"),
        calibration_store=store)

    # 2. tune — the arm's pinned knobs through the real autotuner
    # (bitwise gate, fenced timing, roofline attribution, VMEM refusal,
    # roofline pruning all live), tiny grid so tier-1 stays fast
    log("tune ...")
    rng = np.random.default_rng(seed)
    db = (rng.random((n, d)) * 128.0).astype(np.float32)
    queries = (rng.random((max(nq, 8), d)) * 128.0).astype(np.float32)
    arm_knobs = dict(ARM_KNOBS[arm])
    tile = max(128, (n // 8) // 128 * 128)
    grid = [dict(arm_knobs, tile_n=tile),
            dict(arm_knobs, tile_n=tile * 2)]
    tune_cache = os.path.join(out_dir, "tune_cache.json")
    try:
        entry = tuning.autotune(
            db, queries[:8], k, grid=grid, runs=1,
            cache_path=tune_cache, prune=0.25)
        knobs = {**tuning.DEFAULT_KNOBS, **arm_knobs,
                 **{kk: v for kk, v in entry["knobs"].items()
                    if kk in tuning.DEFAULT_KNOBS}}
        _stage(stages, "tune", "ok", winner=entry.get("winner"),
               winner_ms=entry.get("winner_ms"),
               candidates=len(entry.get("timings_ms") or {}),
               pruned=len(entry.get("pruning") or {}),
               cache_path=tune_cache)
    except Exception as e:  # noqa: BLE001 — recorded, arm continues on pins
        knobs = {**tuning.DEFAULT_KNOBS, **arm_knobs, "tile_n": tile}
        _stage(stages, "tune", "error",
               error=f"{type(e).__name__}: {e}")

    # 3. bench — fenced timed sweep at the winner knobs: the host-phase
    # device_s sample the reconciler consumes
    log("bench ...")
    kw = dict(
        precision=knobs["precision"], kernel=knobs["kernel"],
        tile_n=knobs["tile_n"] or tile,
        survivors=knobs["survivors"], block_q=knobs["block_q"],
        final_select=knobs["final_select"],
        final_recall_target=knobs["final_recall_target"],
        grid_order=knobs["grid_order"])
    q = queries[:nq]
    knn_search_pallas(q, db, k, **kw)  # warm/compile
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        knn_search_pallas(q, db, k, **kw)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    phase = {"device_s": round(best, 6),
             "device_qps": round(nq / best, 2)}
    _stage(stages, "bench", "ok", **phase)

    # 4. capture — a real (CPU) profiler capture of one extra run,
    # plus the checked-in fixture parse proving the device-trace path
    log("capture ...")
    section = f"campaign_{arm}"
    capture_detail: Dict[str, object] = {}
    try:
        with profiler.device_trace(
                section, base_dir=os.path.join(out_dir, "traces")):
            knn_search_pallas(q, db, k, **kw)
        parsed = traceread.read_section(
            os.path.join(out_dir, "traces"), section)
        capture_detail["live_capture"] = {
            "kernel_events": parsed["kernel_events"],
            "device_busy_s": parsed["device_busy_s"],
            "device_tracks_matched": parsed["device_tracks_matched"],
        }
        cap_status = "ok"
    except Exception as e:  # noqa: BLE001 — a CPU runtime may write no trace
        capture_detail["live_capture_error"] = \
            f"{type(e).__name__}: {e}"
        cap_status = "error"
    if trace_fixture:
        fx = traceread.summarize_events(
            traceread.read_trace_events(trace_fixture))
        capture_detail["fixture"] = {
            "path": trace_fixture,
            "kernel_events": fx["kernel_events"],
            "device_busy_s": fx["device_busy_s"],
            "device_tracks_matched": fx["device_tracks_matched"],
        }
        cap_status = "ok"
    _stage(stages, "capture", cap_status, **capture_detail)

    # 5. reconcile — decompose the measured device time against the
    # analytic terms
    log("reconcile ...")
    model_kw = _knobs_for_model(knobs)
    model_kw["tile_n"] = model_kw["tile_n"] or tile
    block = _rl.pallas_cost_model(n=n, d=d, k=k, nq=nq,
                                  backend="cpu", **model_kw)
    measured = traceread.sample_from_phases(phase, nq=nq)
    entry = calibrate.reconcile(block, measured, provenance={
        "config_label": _rl.config_label(n, d, k),
        "commit": _head_commit(_REPO),
        "round": round_no, "arm": arm, "rehearse": True})
    _stage(stages, "reconcile", "ok",
           factors=entry["factors"], method=entry["method"],
           model_residual_pct=entry["model_residual_pct"],
           source=entry["source"])

    # 6. calibrate — persist, re-render, and require the calibrated
    # ceiling to reproduce the measured qps inside the stated tolerance
    log("calibrate ...")
    key = calibrate.key_for_block(block)
    calibrate.put(key, entry, path=store)
    prev = os.environ.get(calibrate.CAL_ENV)
    os.environ[calibrate.CAL_ENV] = store
    try:
        block2 = _rl.pallas_cost_model(n=n, d=d, k=k, nq=nq,
                                       backend="cpu", **model_kw)
        att = _rl.attribute(block2, phase["device_qps"])
    finally:
        if prev is None:
            os.environ.pop(calibrate.CAL_ENV, None)
        else:
            os.environ[calibrate.CAL_ENV] = prev
    applied = bool(att.get("calibration", {}).get("applied"))
    resid = (abs(att["ceiling_qps"] - phase["device_qps"])
             / phase["device_qps"] * 100.0
             if att.get("ceiling_qps") else None)
    within = (applied and resid is not None
              and resid <= calibrate.RESIDUAL_TOLERANCE_PCT)
    _stage(stages, "calibrate", "ok" if within else "error",
           store=store, key=key, applied=applied,
           ceiling_qps=att.get("ceiling_qps"),
           measured_qps=phase["device_qps"],
           reconstruction_residual_pct=(round(resid, 3)
                                        if resid is not None else None),
           tolerance_pct=calibrate.RESIDUAL_TOLERANCE_PCT)

    # 7. curate — validate with the refresher's own validators and
    # write one artifact line per arm
    log("curate ...")
    campaign_block = {
        "campaign_version": CAMPAIGN_VERSION, "arm": arm,
        "round": round_no, "rehearse": True, "stages": stages,
    }
    line = {
        "metric": f"knn_qps_rehearse_n{n}_d{d}_k{k}",
        "value": phase["device_qps"],
        "unit": "queries/s",
        "mode": "campaign_rehearse",
        "backend": "cpu",
        "device_kind": None,
        "device_phase_qps": phase["device_qps"],
        "pallas_knobs": knobs,
        "roofline": att,
        "roofline_pct": att.get("roofline_pct"),
        "bound_class": att.get("bound_class"),
        "model_residual_pct": entry["model_residual_pct"],
        "campaign": campaign_block,
        "measured_round": round_no if round_no is not None else 0,
        "measured_at_commit": _head_commit(_REPO),
    }
    errors = (_rl.validate_block(att)
              + calibrate.validate_calibration(att.get("calibration"))
              + calibrate.validate_campaign_block(campaign_block))
    try:
        from knn_tpu.obs import sentinel

        line["sentinel"] = sentinel.verdict_for_line(
            line, repo_dir=_REPO)
    except Exception as e:  # noqa: BLE001 — verdict must not kill the arm
        line["sentinel"] = {"verdict": "error",
                            "error": f"{type(e).__name__}: {e}"}
    fname = (f"campaign_r{round_no:02d}_{arm}.jsonl"
             if round_no is not None else f"campaign_{arm}.jsonl")
    path = os.path.join(out_dir, fname)
    ok = not errors and within
    # the curate record rides INSIDE the artifact (stages is the same
    # list campaign_block holds), so it must land before the write
    _stage(stages, "curate", "ok" if ok else "error",
           artifact=path, validation_errors=errors)
    _write_artifact(out_dir, fname, line)
    if registry.enabled():
        registry.counter(names.CAMPAIGN_ARMS,
                         status="ok" if ok else "error").inc()
    return {"arm": arm, "ok": ok, "artifact": path, "line": line,
            "errors": errors}


def _bench_shape(env: Dict[str, str]) -> Dict[str, object]:
    """The (n, dim, k, metric, dtype) the ``bench.py`` subprocess will
    sweep, derived exactly the way bench derives it (its CONFIGS table
    + the KNN_BENCH_{CONFIG,N,DIM,K,METRIC} overrides in ``env``) — the
    tune stage must pin the SAME shape, or its persisted winner lands
    under a cache key the bench's resolve never reads."""
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import bench  # light import: env parsing only, no backend init

    cfg = dict(bench.CONFIGS[env.get("KNN_BENCH_CONFIG", "sift1m")])
    return {
        "n": int(env.get("KNN_BENCH_N", cfg["n"])),
        "dim": int(env.get("KNN_BENCH_DIM", cfg["dim"])),
        "k": int(env.get("KNN_BENCH_K", cfg["k"])),
        "metric": env.get("KNN_BENCH_METRIC", cfg["metric"]),
        "dtype": cfg["dtype"],
    }


def _hardware_arm(arm: str, *, out_dir: str, round_no: Optional[int],
                  grid_level: str, verbose: bool) -> dict:
    """One hardware arm: gates flipped via env, `cli tune` + `bench.py`
    as subprocesses, the captured device trace (preferred) or the
    line's phase breakdown reconciled, factors persisted, the emitted
    bench line (now carrying a calibrated roofline block) appended to
    tpu_bench_lines.jsonl for refresh_bench_artifacts.py to curate."""
    stages: List[dict] = []
    store = calibrate.store_path() or os.path.join(
        out_dir, "calibration.json")
    traces = os.path.join(out_dir, "traces")
    knobs = ARM_KNOBS[arm]
    env = {
        **os.environ,
        "KNN_BENCH_MODES": "certified_pallas",
        "KNN_BENCH_PALLAS_PRECISION": str(knobs["precision"]),
        "KNN_BENCH_PALLAS_KERNEL": str(knobs["kernel"]),
        "KNN_TPU_TUNE_PRUNE": os.environ.get(
            "KNN_TPU_TUNE_PRUNE", "0.5"),
        "KNN_TPU_PROFILE_DIR": traces,
        "KNN_TPU_CALIBRATION": store,
    }
    _stage(stages, "gates", "ok", arm_env={
        k: env[k] for k in ("KNN_BENCH_MODES",
                            "KNN_BENCH_PALLAS_PRECISION",
                            "KNN_BENCH_PALLAS_KERNEL",
                            "KNN_TPU_TUNE_PRUNE", "KNN_TPU_PROFILE_DIR",
                            "KNN_TPU_CALIBRATION")})

    def run(cmd, stage_name, timeout):
        # One process per chip: on this path the parent never
        # initialises a JAX backend — its imports (obs.profiler,
        # obs.calibrate, obs.traceread, bench's env parsing) stay off
        # JAX until called into it, and nothing here does — so each
        # child is the only process that takes the chip, and
        # subprocess.run returns before the next one starts.
        t0 = time.perf_counter()
        r = subprocess.run(cmd, cwd=_REPO, env=env,
                           capture_output=True, text=True,
                           timeout=timeout)
        dur = round(time.perf_counter() - t0, 1)
        if r.returncode != 0:
            _stage(stages, stage_name, "error", cmd=cmd, dur_s=dur,
                   stderr_tail=r.stderr.splitlines()[-5:])
            raise RuntimeError(f"{stage_name} failed (rc "
                               f"{r.returncode})")
        return r, dur

    line = None
    try:
        # tune the shape the bench will sweep — any other shape's
        # winner lands under a cache key bench's resolve never reads.
        # The grid spans every arm's precision/kernel (the bench env
        # pins the arm as explicit overrides; tile/block resolve from
        # the winner), and the warm cache makes arms 2..N zero-retime.
        shape = _bench_shape(env)
        if shape["metric"] in ("l2", "sql2", "euclidean"):
            r, dur = run(
                [sys.executable, "-m", "knn_tpu.cli", "tune",
                 "--n", str(shape["n"]), "--dim", str(shape["dim"]),
                 "--k", str(shape["k"]), "--metric",
                 str(shape["metric"]), "--grid", grid_level,
                 "--dtype", str(shape["dtype"])], "tune", 3600)
            _stage(stages, "tune", "ok", dur_s=dur, **shape)
        else:
            # cli tune has no arm for this metric (e.g. cosine rides
            # the l2 unit-vector equivalence at placement) — bench
            # resolves defaults; recorded, never silently dropped
            _stage(stages, "tune", "skipped",
                   reason=f"cli tune does not take metric "
                          f"{shape['metric']!r}", **shape)
        r, dur = run([sys.executable, "bench.py"], "bench", 7200)
        for out_line in reversed(r.stdout.splitlines()):
            out_line = out_line.strip()
            if out_line.startswith("{"):
                line = json.loads(out_line)
                break
        if line is None:
            raise RuntimeError("bench emitted no JSON line")
        _stage(stages, "bench", "ok", dur_s=dur,
               value=line.get("value"),
               device_phase_qps=line.get("device_phase_qps"))

        sel = (line.get("selectors") or {}).get(
            "certified_pallas") or {}
        pb = sel.get("phase_breakdown") or {}
        nq = int(line.get("batch") or 4096)
        measured = None
        try:
            measured = traceread.sample_from_trace(
                traces, "certified_pallas", nq=nq)
            _stage(stages, "capture", "ok", **{
                k: measured[k] for k in ("device_s", "kernel_events",
                                         "device_tracks_matched")})
        except Exception as e:  # noqa: BLE001 — host phases are the fallback source
            _stage(stages, "capture", "error",
                   error=f"{type(e).__name__}: {e}")
        if measured is None or not measured.get("device_tracks_matched"):
            measured = traceread.sample_from_phases(pb, nq=nq)
        model_kw = _knobs_for_model(line.get("pallas_knobs") or knobs)
        cfg = line.get("metric", "")
        m = _rl._METRIC_RE.match(cfg)
        if not m:
            raise RuntimeError(f"bench line metric {cfg!r} unparseable")
        n, d, k = (int(m.group(g)) for g in ("n", "d", "k"))
        block = _rl.pallas_cost_model(
            n=n, d=d, k=k, nq=nq, device_kind=line.get("device_kind"),
            backend=line.get("backend"),
            num_devices=int(line.get("devices") or 1), **model_kw)
        entry = calibrate.reconcile(block, measured, provenance={
            "config_label": _rl.config_label(
                n, d, k, device_kind=line.get("device_kind")),
            "commit": line.get("measured_at_commit")
            or _head_commit(_REPO),
            "round": round_no, "arm": arm, "rehearse": False})
        _stage(stages, "reconcile", "ok", factors=entry["factors"],
               method=entry["method"],
               model_residual_pct=entry["model_residual_pct"],
               source=entry["source"])
        calibrate.put(calibrate.key_for_block(block), entry, path=store)
        prev = os.environ.get(calibrate.CAL_ENV)
        os.environ[calibrate.CAL_ENV] = store
        try:
            block2 = _rl.pallas_cost_model(
                n=n, d=d, k=k, nq=nq,
                device_kind=line.get("device_kind"),
                backend=line.get("backend"),
                num_devices=int(line.get("devices") or 1), **model_kw)
            att = _rl.attribute(block2, measured["qps"])
        finally:
            if prev is None:
                os.environ.pop(calibrate.CAL_ENV, None)
            else:
                os.environ[calibrate.CAL_ENV] = prev
        applied = bool(att.get("calibration", {}).get("applied"))
        _stage(stages, "calibrate", "ok" if applied else "error",
               store=store, applied=applied,
               ceiling_qps=att.get("ceiling_qps"))
    except Exception as e:  # noqa: BLE001 — arm aborts, campaign continues
        # any stage can fail on hardware (no trace written AND no
        # phase device_s -> TraceReadError; a torn measurement ->
        # reconcile's sane-clamp ValueError); record it on the arm and
        # let the remaining arms run
        if registry.enabled():
            registry.counter(names.CAMPAIGN_ARMS, status="error").inc()
        return {"arm": arm, "ok": False, "line": line,
                "errors": [f"{type(e).__name__}: {e}"],
                "stages": stages}
    campaign_block = {
        "campaign_version": CAMPAIGN_VERSION, "arm": arm,
        "round": round_no, "rehearse": False, "stages": stages,
    }
    line = dict(line, roofline=att,
                roofline_pct=att.get("roofline_pct"),
                bound_class=att.get("bound_class"),
                model_residual_pct=entry["model_residual_pct"],
                campaign=campaign_block)
    errors = (_rl.validate_block(att)
              + calibrate.validate_calibration(att.get("calibration"))
              + calibrate.validate_campaign_block(campaign_block))
    fname = (f"campaign_r{round_no:02d}_{arm}.jsonl"
             if round_no is not None else f"campaign_{arm}.jsonl")
    path = os.path.join(out_dir, fname)
    ok = applied and not errors
    # the curate record rides INSIDE the artifact (stages is the same
    # list campaign_block holds), so it must land before the write
    _stage(stages, "curate", "ok" if ok else "error", artifact=path,
           validation_errors=errors)
    _write_artifact(out_dir, fname, line)
    if not errors:
        # feed the curated pipeline: refresh_bench_artifacts.py reads
        # session lines from tpu_bench_lines.jsonl (and validates the
        # calibration/campaign blocks before curating them)
        with open(os.path.join(_REPO, "tpu_bench_lines.jsonl"),
                  "a") as f:
            f.write(json.dumps(line, sort_keys=True) + "\n")
    if registry.enabled():
        registry.counter(names.CAMPAIGN_ARMS,
                         status="ok" if ok else "error").inc()
    return {"arm": arm, "ok": ok, "artifact": path, "line": line,
            "errors": errors}


def run_campaign(
    *, rehearse: bool = False, arms: Optional[Sequence[str]] = None,
    out_dir: Optional[str] = None, round_no: Optional[int] = None,
    seed: int = 0, shape: Optional[Dict[str, int]] = None,
    trace_fixture: Optional[str] = None, grid_level: str = "quick",
    verbose: bool = False,
) -> dict:
    """Run the campaign over ``arms`` and return the summary artifact
    (per-arm outcomes + where each JSONL landed).  See module
    docstring for the stage loop."""
    arms = list(arms or arms_from_env()
                or (DEFAULT_REHEARSE_ARMS if rehearse
                    else DEFAULT_ARMS))
    for a in arms:
        if a not in ARM_KNOBS:
            raise ValueError(f"unknown arm {a!r}; expected one of "
                             f"{sorted(ARM_KNOBS)}")
    out_dir = out_dir or campaign_dir()
    os.makedirs(out_dir, exist_ok=True)
    if round_no is None:
        round_no = round_from_env()
    results = []
    for arm in arms:
        if rehearse:
            results.append(_rehearse_arm(
                arm, out_dir=out_dir,
                shape=dict(REHEARSE_SHAPE, **(shape or {})),
                seed=seed, round_no=round_no,
                trace_fixture=(trace_fixture
                               or default_trace_fixture()),
                grid_level=grid_level, verbose=verbose))
        else:
            results.append(_hardware_arm(
                arm, out_dir=out_dir, round_no=round_no,
                grid_level=grid_level, verbose=verbose))
    return {
        "campaign_version": CAMPAIGN_VERSION,
        "rehearse": bool(rehearse),
        "round": round_no,
        "out_dir": out_dir,
        "arms": [{"arm": r["arm"], "ok": r["ok"],
                  "errors": r.get("errors"),
                  "artifact": r.get("artifact")} for r in results],
        "ok": all(r["ok"] for r in results),
        "results": results,
    }
