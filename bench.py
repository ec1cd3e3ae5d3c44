#!/usr/bin/env python
"""Benchmark: brute-force KNN queries/sec at SIFT1M shape (1M x 128, k=100 —
BASELINE.json config 3) on a TPU: ``--chips N`` devices (default 1), db-sharded.
A run that finds no TPU fails, unless KNN_BENCH_PLATFORM=cpu asked for the CPU
backend (the CPU tests do).

Prints EXACTLY ONE JSON line:
  {"metric": ..., "value": <q/s>, "unit": "queries/s", "vs_baseline": <x>, ...}
On any failure (including backend init) it still prints one JSON line, with
an "error" field, so the driver always gets a parseable record.  The exit code
is non-zero when the backend is not the one asked for, when the compiled
soundness gate fails, or when any requested mode fails — the line may still be
printed first.

Three measured configurations (the ``selectors`` table in the JSON):

- ``exact``           coarse top-(K+margin) via lax.top_k + float64 host
                      refinement — the selection-bound baseline path.
- ``certified_approx``  the flagship: hardware ApproxTopK coarse pass +
                      float64 refine + count-below certificate + exact
                      fallback (ops.certified).  Exact by construction.
- ``certified_pallas``  same pipeline with the fused Pallas distance+bin-min
                      kernel (ops.pallas_knn) as the coarse pass.

``value`` is the best configuration whose recall@K against the float64 CPU
oracle is 1.0.  Protocol follows the reference report (PDF p.12 §4.2):
each configuration is timed KNN_BENCH_RUNS (default 5) times after a
warmup sweep; mean/std/min are reported.  MFU relates measured q/s to the
matmul FLOPs actually executed (2*N*D per query per database pass) against
the chip's peak — the "fast, not merely correct" check.  Beside it, every
selector entry (and the line top-level, for the winner) carries a
``roofline`` block (knn_tpu.obs.roofline): the analytic per-config ceiling
q/s from the HBM/MXU/VPU cost model, the measured ``roofline_pct``, and
the ``bound_class`` naming the resource to attack — attribution, where
MFU alone is only a ratio.

``vs_baseline`` divides by the reference-style CPU brute force: the native
C++ backend (knn_tpu/native, the reference program's semantics with
std::thread standing in for its MPI ranks) timed on a query subsample of
the SAME database.  The reference's own published numbers are MNIST-shaped
and machine-specific (BASELINE.md); an in-situ CPU measurement is the
honest denominator.

Env overrides:
  KNN_BENCH_CONFIG   sift1m (default) | glove | gist1m   (BASELINE configs 3/4/5)
  KNN_BENCH_MODES    comma list from {exact,certified_approx,
                     certified_pallas,serving,knee,multihost,mutation,
                     ivf,join,quality,fleet}; ``join`` is the opt-in
                     bulk kNN-join line (knn_tpu.join: double-buffered
                     superblock stream vs looped serving on the same
                     placement; KNN_BENCH_JOIN_ROWS/_SUPERBLOCK/_DEPTH
                     shape it); ``quality`` is the opt-in shadow-audit
                     replay (knn_tpu.obs.audit at rate 1.0:
                     KNN_BENCH_QUALITY_REQUESTS requests re-scored
                     against the f64 exact oracle); ``fleet`` is the
                     opt-in cross-host telemetry merge
                     (knn_tpu.obs.fleet over KNN_TPU_FLEET_MEMBERS, or
                     this process's own snapshot as a one-member
                     fleet)
  KNN_BENCH_RUNS     timed repetitions per mode (default 5)
  KNN_BENCH_N, KNN_BENCH_DIM, KNN_BENCH_K, KNN_BENCH_NQ, KNN_BENCH_BATCH,
  KNN_BENCH_TILE, KNN_BENCH_CPU_QUERIES, KNN_BENCH_MARGIN,
  KNN_BENCH_DTYPE    (bfloat16 | float32; default per config)
  KNN_BENCH_PEAK_FLOPS    override the per-chip peak used for MFU
  KNN_BENCH_PLATFORM      the JAX platform to run on; unset means TPU, and a
                          run that finds another backend fails.  "cpu" is
                          what the CPU tests ask for
  KNN_BENCH_TRACE         write a jax.profiler trace of one extra per-mode
                          run under this directory (TensorBoard-viewable;
                          the --trace-dir flag is equivalent; the ambient
                          KNN_TPU_PROFILE_DIR gate of knn_tpu.obs.profiler
                          also opens this capture when telemetry is on)
  KNN_BENCH_PALLAS_KERNEL tiled | streaming (db-streaming strategy);
                          unset pallas knobs resolve through the
                          knn_tpu.tuning winner cache (see
                          KNN_BENCH_TUNE_CACHE / `knn_tpu.cli tune`)
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np


def _parse_args(argv=None):
    """The bench's (tiny) flag surface — unknown args are ignored so the
    driver's bare ``python bench.py`` invocation stays untouched."""
    p = argparse.ArgumentParser(
        prog="bench.py",
        description="KNN throughput bench; prints exactly one JSON line",
    )
    p.add_argument(
        "--chips", type=int, default=1, metavar="N",
        help="devices to measure on (db-sharded 1xN mesh over the first N "
        "devices; default 1).  Never inferred from what the host exposes: "
        "the same command must measure the same mesh on a 1-chip and a "
        "4-chip host",
    )
    p.add_argument(
        "--trace-dir", default=os.environ.get("KNN_BENCH_TRACE"),
        metavar="DIR",
        help="capture a jax.profiler trace artifact "
        "(obs.profiler.device_trace, TensorBoard-loadable) of one extra "
        "per-mode run under DIR, "
        "alongside the bench JSON; equivalent to KNN_BENCH_TRACE",
    )
    args, _ = p.parse_known_args(argv)
    return args


ARGS = _parse_args()


def _env_int(name, default):
    return int(os.environ.get(name, default))


def _env_opt_int(name):
    return int(os.environ[name]) if name in os.environ else None


#: BASELINE.json configs 3/4/5.  ``certifiable`` = the certificate
#: machinery applies: l2 natively, cosine via the library's unit-vector
#: l2 equivalence (ShardedKNN normalizes rows at placement).  L1 would
#: not be (no squared-L2-style bound).
CONFIGS = {
    "sift1m": dict(n=1_000_000, dim=128, k=100, metric="l2", dtype="bfloat16"),
    "glove": dict(n=1_183_514, dim=300, k=50, metric="cosine", dtype="bfloat16"),
    "gist1m": dict(n=1_000_000, dim=960, k=100, metric="l2", dtype="bfloat16"),
}

try:
    CONFIG = os.environ.get("KNN_BENCH_CONFIG", "sift1m")
    _cfg = CONFIGS[CONFIG]
    N = _env_int("KNN_BENCH_N", _cfg["n"])
    DIM = _env_int("KNN_BENCH_DIM", _cfg["dim"])
    K = _env_int("KNN_BENCH_K", _cfg["k"])
    METRIC = os.environ.get("KNN_BENCH_METRIC", _cfg["metric"])
    NQ = _env_int("KNN_BENCH_NQ", 4096)
    BATCH = _env_int("KNN_BENCH_BATCH", 512)  # sweep winner on v5e (2026-07)
    TILE = _env_int("KNN_BENCH_TILE", 131_072)
    #: 256 queries (VERDICT r2 item 7): ~40 s of CPU once per round buys a
    #: 4x larger denominator sample; cpu_queries + per-query time stay in
    #: the JSON so the claim is auditable.
    CPU_QUERIES = _env_int("KNN_BENCH_CPU_QUERIES", 256)
    #: pallas kernel knob OVERRIDES.  Unset env = None = resolve through
    #: knn_tpu.tuning (the persisted autotuner winner for this exact
    #: (device_kind, n, d, k, metric, dtype) when one exists, else the
    #: library defaults); a SET env var always wins over both — the same
    #: precedence ShardedKNN.search_certified applies, so the bench and
    #: the library can never run different knobs for the same request.
    PALLAS_PRECISION = os.environ.get("KNN_BENCH_PALLAS_PRECISION")
    PALLAS_TILE = _env_opt_int("KNN_BENCH_PALLAS_TILE")
    PALLAS_SURVIVORS = _env_opt_int("KNN_BENCH_PALLAS_SURVIVORS")
    PALLAS_BLOCK_Q = _env_opt_int("KNN_BENCH_PALLAS_BLOCK_Q")
    PALLAS_FINAL = os.environ.get("KNN_BENCH_PALLAS_FINAL")
    #: grid iteration order (ops.pallas_knn.GRID_ORDERS): "db_major"
    #: streams each db tile once per sweep instead of once per query
    #: block (r5 cost model); opt-in pending the hardware gate + A/B
    PALLAS_GRID = os.environ.get("KNN_BENCH_PALLAS_GRID")
    #: db-streaming strategy (ops.pallas_knn.KERNELS): "tiled" | the
    #: one-launch double-buffered "streaming"
    PALLAS_KERNEL = os.environ.get("KNN_BENCH_PALLAS_KERNEL")
    #: autotuner cache file override (KNN_TPU_TUNE_CACHE also works)
    TUNE_CACHE = os.environ.get("KNN_BENCH_TUNE_CACHE")
    #: recall target of the one-pass path's final ApproxTopK (None =
    #: library default 0.999); misses surface as fallbacks, never
    #: as unsound certificates
    PALLAS_FINAL_RT = (float(os.environ["KNN_BENCH_PALLAS_FINAL_RT"])
                       if "KNN_BENCH_PALLAS_FINAL_RT" in os.environ else None)
    #: pallas sweep batch size (0/unset = one full-size batch); smaller
    #: batches pipeline the d2h transfer under later batches' compute
    PALLAS_BATCH = _env_int("KNN_BENCH_PALLAS_BATCH", 0) or None
    #: certified_approx calibration (2026-07-30: rt=0.9999 zeroed the
    #: genuine ApproxTopK misses; the adaptive gap threshold handles
    #: the rest, and the wider margin feeds its gap search)
    APPROX_RT = float(os.environ.get("KNN_BENCH_APPROX_RT", "0.9999"))
    APPROX_MARGIN = _env_int("KNN_BENCH_APPROX_MARGIN", 128)
    DTYPE = os.environ.get("KNN_BENCH_DTYPE", _cfg["dtype"])
    RUNS = _env_int("KNN_BENCH_RUNS", 5)
    #: Coarse pass fetches K + MARGIN candidates; float64 refinement
    #: re-selects the true top-K among them (ops.refine); the certificate
    #: (ops.certified) then proves no true neighbor was missed, or falls back.
    MARGIN = _env_int("KNN_BENCH_MARGIN", 28)
    #: ``serving`` mode trace: request count, in-flight dispatch-ahead
    #: window, and the bucket ladder's floor (ladder tops out at BATCH)
    SERVING_REQUESTS = _env_int("KNN_BENCH_SERVING_REQUESTS", 48)
    SERVING_DEPTH = _env_int("KNN_BENCH_SERVING_DEPTH", 2)
    SERVING_MIN_BUCKET = _env_opt_int("KNN_BENCH_SERVING_MIN_BUCKET")
    #: measure telemetry overhead (knn_tpu.obs): replay the serving
    #: trace twice — registry disabled, then enabled — and report
    #: obs_overhead_pct = (qps_off - qps_on) / qps_off * 100.  Opt-in:
    #: the double replay costs a second trace of chip time.
    OBS_OVERHEAD = os.environ.get("KNN_BENCH_OBS_OVERHEAD", "0") == "1"
    #: ``knee`` mode (knn_tpu.loadgen): open-loop stepped-rate sweep
    #: through the micro-batching queue, locating the
    #: latency-vs-throughput knee.  Opt-in via KNN_BENCH_MODES=..,knee
    #: (each rate step costs KNEE_STEP_S wall seconds).  Unset
    #: KNEE_RATES = a ladder of KNEE_FRACTIONS x a measured closed-loop
    #: anchor rate.
    KNEE_RATES = [float(x) for x in os.environ.get(
        "KNN_BENCH_KNEE_RATES", "").split(",") if x.strip()]
    KNEE_STEP_S = float(os.environ.get("KNN_BENCH_KNEE_STEP_S", "1.0"))
    KNEE_SLO_MS = float(os.environ.get("KNN_BENCH_KNEE_SLO_MS", "100"))
    KNEE_TENANTS = os.environ.get("KNN_BENCH_KNEE_TENANTS", "default:1")
    KNEE_SEED = _env_int("KNN_BENCH_KNEE_SEED", 0)

    #: multi-host serving measurement (hierarchical merge + host-RAM
    #: tier).  Opt-in via KNN_BENCH_MODES=..,multihost
    MULTIHOST_HOSTS = _env_int("KNN_BENCH_MULTIHOST_HOSTS", 2)
    MULTIHOST_SWEEPS = _env_int("KNN_BENCH_MULTIHOST_SWEEPS", 4)

    #: ``mutation`` mode (knn_tpu.index + knn_tpu.loadgen): live mixed
    #: read+write traffic against a MutableIndex-backed serving stack
    #: across background compaction swaps.  Opt-in via
    #: KNN_BENCH_MODES=..,mutation (docs/INDEX.md)
    MUTATION_RATE = float(os.environ.get(
        "KNN_BENCH_MUTATION_RATE", "200"))
    MUTATION_SECONDS = float(os.environ.get(
        "KNN_BENCH_MUTATION_SECONDS", "2.0"))
    MUTATION_WRITE_FRACTION = float(os.environ.get(
        "KNN_BENCH_MUTATION_WRITE_FRACTION", "0.15"))

    #: ``join`` mode (knn_tpu.join): offline bulk kNN-join of a
    #: host-resident query set against the placed corpus through the
    #: double-buffered superblock stream, beside a looped-serving
    #: baseline on the SAME placement — the amortization claim as one
    #: line.  Opt-in via KNN_BENCH_MODES=..,join.  JOIN_ROWS=0 sizes
    #: the query set from NQ/BATCH; JOIN_SUPERBLOCK=0 defers to the
    #: engine's resolution ladder (KNN_TPU_JOIN_* applies there too).
    JOIN_ROWS = _env_int("KNN_BENCH_JOIN_ROWS", 0)
    JOIN_SUPERBLOCK = _env_int("KNN_BENCH_JOIN_SUPERBLOCK", 0)
    JOIN_DEPTH = _env_int("KNN_BENCH_JOIN_DEPTH", 2)

    #: ``quality`` mode (knn_tpu.obs.audit): a short serving replay
    #: with the shadow audit sampler forced to rate 1.0, so EVERY
    #: request's served top-k is re-scored against the f64 exact
    #: oracle on the audit worker thread.  Opt-in via
    #: KNN_BENCH_MODES=..,quality; each request pays one host-side
    #: oracle scan over the full corpus, so the count stays small.
    QUALITY_REQUESTS = _env_int("KNN_BENCH_QUALITY_REQUESTS", 8)
except Exception as _e:  # bad env: the one-JSON-line contract still holds
    print(json.dumps({
        "metric": "knn_qps_config", "value": None, "unit": "queries/s",
        "vs_baseline": None, "error": f"config: {_e!r}",
    }))
    sys.exit(1)

#: bf16 MXU peak FLOP/s by device kind — a VIEW over the roofline
#: module's full peak table (knn_tpu.obs.roofline.PEAKS_BY_KIND, the
#: single source of truth, which additionally carries HBM GB/s and the
#: int8 MXU / VPU rates the per-config cost model divides by).  MFU is
#: an *estimate* — the denominator assumes bf16 peak even for f32 runs.
#: An unknown kind yields mfu=null WITH an explicit mfu_reason (below),
#: never a silently-wrong default; the guarded import keeps the
#: one-JSON-line contract even if the package is broken.
def _load_peak_by_kind():
    try:
        from knn_tpu.obs.roofline import bf16_peak_by_kind

        return bf16_peak_by_kind()
    except Exception:  # noqa: BLE001 — an empty table = mfu_reason, not a crash
        return {}


_PEAK_BY_KIND = _load_peak_by_kind()


_GIT_COMMIT_MEMO = [False]  # False = not probed yet (None = no repo)


def _git_commit():
    """Short git HEAD stamped into every emitted line, so a session
    measurement carries its own code provenance into curation
    (scripts/refresh_bench_artifacts.py's measured_at_commit).  Probed
    once per process — _emit may run several times (error paths)."""
    if _GIT_COMMIT_MEMO[0] is not False:
        return _GIT_COMMIT_MEMO[0]
    import subprocess

    try:
        r = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        _GIT_COMMIT_MEMO[0] = r.stdout.strip() or None
    except Exception:
        _GIT_COMMIT_MEMO[0] = None
    return _GIT_COMMIT_MEMO[0]


def _emit(obj):
    commit = _git_commit()
    if commit and "measured_at_commit" not in obj:
        obj = {**obj, "measured_at_commit": commit}
    print(json.dumps(obj))
    sys.stdout.flush()


def _vlog(msg):
    """Stage progress on stderr when KNN_BENCH_VERBOSE=1 — the bench's
    stdout carries exactly one JSON line, so diagnosing a hang (stale
    device claim, slow remote compile) needs a side channel."""
    if os.environ.get("KNN_BENCH_VERBOSE") == "1":
        print(f"[bench +{time.monotonic() - _T0:.0f}s] {msg}",
              file=sys.stderr, flush=True)


_T0 = time.monotonic()


def _fail(stage, err, **extra):
    _emit({
        "metric": f"knn_qps_{CONFIG}_n{N}_d{DIM}_k{K}",
        "value": None,
        "unit": "queries/s",
        "vs_baseline": None,
        "error": f"{stage}: {err}",
        **extra,
    })
    sys.exit(1)


def _init_backend():
    """``import jax; jax.devices()`` in THIS process — no probe child
    (a child that touches JAX takes the chip from its parent), no
    watchdog, no fallback.  The backend must be a TPU unless
    KNN_BENCH_PLATFORM names another one."""
    import jax

    want = os.environ.get("KNN_BENCH_PLATFORM")
    if want:
        jax.config.update("jax_platforms", want)
    try:
        jax.devices()
    except Exception as e:  # noqa: BLE001 — reported on the JSON line
        _fail("backend_init", repr(e))
    backend = jax.default_backend()
    if backend != (want or "tpu"):
        _fail("backend_init",
              f"JAX found backend {backend!r}, not {want or 'tpu'!r}; a "
              f"benchmark does not fall back (KNN_BENCH_PLATFORM=cpu asks "
              f"for the CPU backend)")
    from knn_tpu.utils.compat import enable_compile_cache

    enable_compile_cache()
    return jax


def recall_at_k(pred_idx: np.ndarray, true_idx: np.ndarray) -> float:
    hits = 0
    for p, t in zip(pred_idx, true_idx):
        hits += len(set(p.tolist()) & set(t.tolist()))
    return hits / true_idx.size


#: the baseline is deterministic (fixed-seed data, same binary, same
#: machine), and at gist shape it costs ~12 min — cache it on disk so a
#: device-holding bench run doesn't re-burn that time.  The cache file
#: is a generated file git does not commit: the JSON line names it
#: (cpu_baseline_cache_file) whenever it stood in for a measurement,
#: and says why (cpu_baseline_error) whenever there is no baseline.
#: KNN_BENCH_CPU_CACHE=0 forces a fresh measurement.
def _cpu_baseline(db, sub):
    """Native C++ brute force (reference semantics) on the subsample:
    (qps, mean per-query seconds, exact f64 top-K indices, note) — the
    first three None when there is no baseline; ``note`` is what the
    JSON line must say about it (the cache file that stood in, or the
    error)."""
    cache = None
    if os.environ.get("KNN_BENCH_CPU_CACHE", "1") != "0":
        cache = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            f".bench_cpu_{CONFIG}_{METRIC}_n{N}_d{DIM}_k{K}_q{len(sub)}.npz",
        )
        if os.path.exists(cache):
            try:
                z = np.load(cache)
                return (float(z["qps"]), float(z["per_q"]), z["idx"],
                        {"cpu_baseline_cache_file": os.path.basename(cache)})
            except (OSError, KeyError, ValueError) as e:
                _vlog(f"cpu baseline cache unreadable, re-measuring: {e!r}")
    try:
        from knn_tpu import native

        t0 = time.perf_counter()
        _, idx = native.knn_search(db, sub, K, METRIC, num_threads=8)
        elapsed = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 — no toolchain / build failure
        return None, None, None, {
            "cpu_baseline_error": f"{type(e).__name__}: {e}"}
    qps, per_q = len(sub) / elapsed, elapsed / len(sub)
    if cache:
        try:
            np.savez(cache, qps=qps, per_q=per_q, idx=idx)
        except OSError as e:
            _vlog(f"cpu baseline cache not written: {e!r}")
    return qps, per_q, idx, {}


def main() -> None:
    _vlog("init backend ...")
    jax = _init_backend()
    dev = jax.devices()[0]
    backend = jax.default_backend()

    # peak FLOPs for MFU: env override > known device kind > None (a v5e
    # default on an unknown/CPU backend would yield a meaningless MFU).
    # When peak is unknowable, mfu_reason says WHY the line's mfu fields
    # are null — "cpu backend" (no MXU peak to relate to) vs "unknown
    # device kind" (extend the table / set KNN_BENCH_PEAK_FLOPS) — so
    # sentinel baselines can key on MFU exactly where it exists
    mfu_reason = None
    if "KNN_BENCH_PEAK_FLOPS" in os.environ:
        peak = float(os.environ["KNN_BENCH_PEAK_FLOPS"])
    else:
        peak = _PEAK_BY_KIND.get(getattr(dev, "device_kind", ""))
        if peak is None:
            mfu_reason = (
                "cpu backend: no MXU peak to relate measured FLOPs to"
                if backend == "cpu" else
                f"unknown device kind "
                f"{getattr(dev, 'device_kind', str(dev))!r}: not in "
                f"_PEAK_BY_KIND and KNN_BENCH_PEAK_FLOPS unset")

    from knn_tpu.ops.refine import refine_exact
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.parallel.sharded import ShardedKNN

    rng = np.random.default_rng(0)
    db = (rng.random(size=(N, DIM)) * 128.0).astype(np.float32)
    queries = (rng.random(size=(NQ, DIM)) * 128.0).astype(np.float32)
    sub = queries[:CPU_QUERIES]

    _vlog(f"data generated ({N}x{DIM}); CPU baseline on {CPU_QUERIES} queries ...")
    cpu_qps, cpu_per_q_s, oracle_idx, cpu_note = _cpu_baseline(db, sub)
    _vlog(f"cpu baseline done: {cpu_qps and round(cpu_qps, 2)} q/s")

    metric_label = METRIC
    if METRIC == "cosine":
        # the library handles cosine natively now: ShardedKNN normalizes
        # the db rows at placement and search_certified runs the whole
        # certified-exact machinery on unit vectors (the round-3 harness
        # did this normalization trick itself; VERDICT r3 item 4 moved it
        # into the library).  The CPU oracle above ranked true cosine on
        # the raw data, so the recall check validates the equivalence
        # end-to-end.
        metric_label = "cosine (certified via unit-vector l2)"

    global DTYPE
    if oracle_idx is None and "KNN_BENCH_DTYPE" not in os.environ:
        # no oracle to verify bf16 recall against -> stay conservative for
        # the exact (margin-heuristic) path; certified modes re-verify
        # themselves either way
        DTYPE = "float32"

    # the chip count is asked for, never inferred: the same command
    # measures the same mesh on a 1-chip and a 4-chip host
    if ARGS.chips > len(jax.devices()):
        _fail("backend_init", f"--chips {ARGS.chips} asked for, "
              f"{len(jax.devices())} device(s) found")
    mesh = make_mesh(1, ARGS.chips, devices=jax.devices()[:ARGS.chips])
    tile = min(TILE, N)
    coarse_k = min(K + MARGIN, N)
    certifiable = METRIC in ("l2", "sql2", "euclidean", "cosine")

    # Default sweep: certified_approx stays OFF the accelerator loop — it
    # decided nothing in two rounds of hardware data (1,071 q/s vs exact's
    # 2,168, 2026-07-30); it remains fully covered on CPU (tests + this
    # default) and reachable anywhere via KNN_BENCH_MODES.
    # ``serving`` rides along by default: it reuses the placement and its
    # trace is tiny next to the timed sweeps, but it is the only line that
    # measures the variable-batch-size traffic pattern (sustained q/s +
    # tail latency through the bucketed engine)
    if not certifiable:
        default_modes = "exact,serving"
    elif backend == "cpu":
        default_modes = "exact,certified_approx,certified_pallas,serving"
    else:
        default_modes = "exact,certified_pallas,serving"
    modes = os.environ.get("KNN_BENCH_MODES", default_modes).split(",")

    # ONE device placement of the (padded) database, shared by every mode:
    # the exact path fetches k+margin via search(k=...), the certified
    # paths use their own cached programs on the same placement.
    def build(dtype):
        return ShardedKNN(db, mesh=mesh, k=K, metric=METRIC,
                          train_tile=tile, compute_dtype=dtype)

    _vlog("placing database on device ...")
    prog = build(DTYPE)
    if DTYPE == "bfloat16" and oracle_idx is not None:
        # recall-gate the dtype before committing to the full measurement:
        # bf16 matmuls that misrank past the margin can't be repaired on
        # the non-certified path, so demote to float32 (certified modes
        # self-repair either way, but the headline must stay exact)
        _vlog("bf16 recall gate ...")
        _, ci = prog.search(sub, k=coarse_k)
        _, ri = refine_exact(db, sub, np.asarray(ci), K, METRIC)
        if recall_at_k(ri, oracle_idx) < 1.0:
            DTYPE = "float32"
            del prog  # free the bf16 placement before the rebuild
            prog = build(DTYPE)

    # resolve the pallas knobs ONCE (after the dtype demotion so the key
    # matches the placement search_certified will see): env overrides >
    # persisted autotuner winner (`python -m knn_tpu.cli tune`) > library
    # defaults.  One exception preserved from two rounds of measurement:
    # on a cache MISS with no env pin, the bench keeps its historical
    # "approx" final (the 2026-07-30 measured winner)
    # instead of the library's "exact" default; a cache HIT carries a
    # MEASURED final_select (tuning.knob_grid searches it at every
    # level), so the winner rightly takes precedence then.
    from knn_tpu import tuning

    KNOBS, TUNE_INFO = tuning.resolve_full(
        N, DIM, K, metric="l2" if METRIC == "cosine" else METRIC,
        dtype=DTYPE, cache_path=TUNE_CACHE,
        overrides=dict(
            tile_n=PALLAS_TILE, block_q=PALLAS_BLOCK_Q,
            survivors=PALLAS_SURVIVORS, precision=PALLAS_PRECISION,
            final_select=PALLAS_FINAL,
            grid_order=PALLAS_GRID, final_recall_target=PALLAS_FINAL_RT,
            kernel=PALLAS_KERNEL,
        ),
    )
    if TUNE_INFO["source"] == "default" and "final_select" not in \
            TUNE_INFO["overridden"]:
        KNOBS["final_select"] = "approx"
    _vlog(f"pallas knobs ({TUNE_INFO['source']}): {KNOBS}")

    def batches(qs):
        for lo in range(0, qs.shape[0], BATCH):
            chunk = qs[lo : lo + BATCH]
            pad = BATCH - chunk.shape[0]
            yield lo, np.pad(chunk, ((0, pad), (0, 0))) if pad else chunk, pad

    def sweep_exact(qs):
        """Coarse device top-(K+margin), f64 host refine overlapped with the
        next batches' device work.  Returns (idx [Q,K], stats=None)."""
        coarse = [(lo, prog.search(chunk, k=coarse_k), pad)
                  for lo, chunk, pad in batches(qs)]
        out = []
        for lo, (d, i), pad in coarse:
            i = np.asarray(i)
            if pad:
                i = i[:-pad]
            out.append(refine_exact(db, qs[lo : lo + i.shape[0]], i, K, METRIC)[1])
        return np.concatenate(out), None

    def sweep_certified(selector, return_distances=True):
        def run(qs):
            if selector == "pallas":
                # ONE device pass; PALLAS_BATCH pipelines the d2h
                # transfer of batch b under the device compute of the
                # batches behind it (None = one big batch+transfer).
                # The resolved KNOBS pass as explicit values, so the
                # library-side resolve is a no-op re-statement of them.
                _, i, st = prog.search_certified(
                    qs, margin=MARGIN, selector=selector,
                    batch_size=PALLAS_BATCH,
                    return_distances=return_distances,
                    **KNOBS,
                )
                return i, st
            # counted path: all coarse selects dispatch up front, host
            # refine overlaps later batches' device work (sharded.py)
            _, i, st = prog.search_certified(
                qs, margin=APPROX_MARGIN if selector == "approx" else MARGIN,
                selector=selector, batch_size=BATCH,
                recall_target=APPROX_RT,
            )
            return i, st
        return run

    def sweep_serving():
        """Variable-batch-size trace through the shape-bucketed serving
        engine (knn_tpu.serving): log-uniform request sizes in [1, BATCH]
        replayed with a bounded dispatch-ahead window.  Reports SUSTAINED
        q/s and p50/p95/p99 request latency — the traffic-pattern number
        the single-shot sweeps above cannot measure — plus the compile
        accounting that proves the bucket ladder bounded the XLA compile
        count."""
        from knn_tpu.serving.engine import ServingEngine

        min_bucket = SERVING_MIN_BUCKET or max(1, BATCH // 32)
        eng = ServingEngine(prog, min_bucket=min_bucket, max_bucket=BATCH)
        t0 = time.perf_counter()
        eng.warmup()
        warm_s = time.perf_counter() - t0
        t_rng = np.random.default_rng(42)
        sizes = np.exp(
            t_rng.uniform(0.0, np.log(BATCH), size=SERVING_REQUESTS)
        ).astype(np.int64).clip(1, BATCH)
        reqs = []
        for s in sizes:
            lo = int(t_rng.integers(0, max(1, NQ - int(s))))
            reqs.append(queries[lo : lo + int(s)])
        _, report = eng.replay(reqs, depth=SERVING_DEPTH)
        obs_overhead = None
        if OBS_OVERHEAD:
            # A/B the SAME trace with telemetry off, then on — fresh
            # engines so neither run inherits the other's counters;
            # warmup() keeps compiles out of both replay windows.  The
            # ambient registry state is restored afterwards (env-driven).
            from knn_tpu import obs as _obs

            qps = {}
            for on in (False, True):
                _obs.reset(enabled=on)
                e2 = ServingEngine(
                    prog, min_bucket=min_bucket, max_bucket=BATCH)
                e2.warmup()
                # one untimed replay first: each arm's executables pay
                # their first-execution costs OUTSIDE the timed window,
                # or the off-first ordering reads as phantom overhead;
                # then best-of-3 per arm — replay jitter dwarfs the
                # per-event cost, so the comparison needs the noise
                # floor pushed down, not one sample
                e2.replay(reqs, depth=SERVING_DEPTH)
                best = None
                for _ in range(3):
                    _, rep2 = e2.replay(reqs, depth=SERVING_DEPTH)
                    if rep2["sustained_qps"] is not None:
                        best = max(best or 0.0, rep2["sustained_qps"])
                qps[on] = best
            _obs.reset()
            if qps[False] and qps[True]:
                obs_overhead = round(
                    (qps[False] - qps[True]) / qps[False] * 100.0, 3)
        # worst recent requests' trace ids (histogram exemplars via
        # engine stats): the replay's tail percentiles become
        # joinable against spans/waterfalls (cli waterfall) when an
        # obs log or postmortem bundle was captured alongside
        slowest_ids = [e.get("trace_id")
                       for e in report.get("slowest_requests") or []
                       if e.get("trace_id")][:5]
        return {
            "sustained_qps": report["sustained_qps"],
            "latency_ms": report["latency_ms"],
            **({"slowest_trace_ids": slowest_ids} if slowest_ids else {}),
            # telemetry overhead on this trace (None = not measured; set
            # KNN_BENCH_OBS_OVERHEAD=1): negative values are replay
            # noise — the honest reading is "below noise floor"
            **({"obs_overhead_pct": obs_overhead}
               if obs_overhead is not None else {}),
            "trace_requests": report["requests"],
            "trace_queries": report["total_queries"],
            "trace_wall_s": report["wall_s"],
            "dispatch_depth": SERVING_DEPTH,
            "warmup_s": round(warm_s, 4),
            "bucket_ladder": report["buckets"],
            "compile_count": report["compile_count"],
            "executables": report["executables"],
            "per_bucket_dispatches": report["per_bucket_dispatches"],
            # which kernel knobs a certified path on this placement
            # would resolve (persisted winner vs defaults)
            "tuning": report.get("tuning"),
        }

    def sweep_knee():
        """Open-loop stepped-rate sweep (knn_tpu.loadgen) through the
        micro-batching queue: the latency-vs-throughput knee as a
        curated artifact (rate steps, admitted p50/p95/p99, shed
        fraction, detected knee q/s).  Admission control participates
        when the KNN_TPU_ADMISSION_* env knobs are set — the brownout
        configuration — and stays off otherwise, measuring the raw
        engine.  Request rates are REQUESTS/s (mixed batch sizes, like
        real traffic), anchored on a short closed-loop probe when
        KNN_BENCH_KNEE_RATES is unset."""
        from knn_tpu import loadgen
        from knn_tpu.serving.admission import AdmissionConfig
        from knn_tpu.serving.engine import ServingEngine
        from knn_tpu.serving.queue import QueryQueue

        min_bucket = SERVING_MIN_BUCKET or max(1, BATCH // 32)
        eng = ServingEngine(prog, min_bucket=min_bucket, max_bucket=BATCH)
        eng.warmup()
        admission = AdmissionConfig.from_env()
        tenants = tuple(
            loadgen.TenantSpec(t.name, weight=t.weight,
                               priority=t.priority,
                               batch_sizes=(1, 2, 4, 8))
            for t in loadgen.parse_tenants(KNEE_TENANTS))
        base = loadgen.WorkloadSpec(
            rate_qps=1.0, duration_s=KNEE_STEP_S, seed=KNEE_SEED,
            tenants=tenants)
        rates = KNEE_RATES
        anchor = None
        if not rates:
            # closed-loop anchor probe (admission-free queue): the
            # default ladder brackets the knee around it
            with QueryQueue(eng, max_wait_ms=2.0) as q0:
                anchor = loadgen.closed_loop_anchor(q0, queries)
            rates = loadgen.rates_around(anchor)

        def make_queue():
            return QueryQueue(eng, max_wait_ms=2.0, admission=admission)

        block = loadgen.knee_sweep(
            make_queue, base, rates, queries=queries,
            slo_p99_ms=KNEE_SLO_MS)
        return {
            "loadgen_knee": block,
            "knee_qps": block["knee_qps"],
            "slo_p99_ms": KNEE_SLO_MS,
            "anchor_req_qps": (round(anchor, 2)
                               if anchor is not None else None),
            "admission_enabled": admission is not None,
            "rates": [float(r) for r in rates],
            "tenants": KNEE_TENANTS,
        }

    def sweep_mutation():
        """Opt-in mixed read+write traffic proof (knn_tpu.index): a
        MutableIndex-backed serving stack (bucketed engine + delta
        tail + micro-batching queue) driven by a seeded open-loop
        schedule whose tenants carry a write stream, with background
        compaction thresholds sized so the run crosses >= 2 snapshot
        swaps.  Emits the validated ``mutation`` artifact block
        (knn_tpu.index.artifact) — admitted-read p99 beside write
        counts, compactions, and SLO breach transitions."""
        from knn_tpu import loadgen, obs
        from knn_tpu.index.mutable import MutableIndex
        from knn_tpu.obs import names as _mn
        from knn_tpu.serving.queue import QueryQueue

        # cap the index's own placement: the mutation line measures
        # swap behavior under traffic, not raw scan throughput (the
        # timed modes own that), and compaction re-places the corpus
        # once per swap
        n_idx = min(N, 131072)
        mix_frac = max(0.0, min(1.0, MUTATION_WRITE_FRACTION))
        insert_frac = round(mix_frac * 2 / 3, 4)
        delete_frac = round(mix_frac / 3, 4)
        expected_inserts = MUTATION_RATE * MUTATION_SECONDS * insert_frac
        idx = MutableIndex(
            db[:n_idx], mesh=mesh, k=K, metric="l2",
            train_tile=tile,
            # ~2 threshold crossings over the run, floor of 8 so tiny
            # smoke runs still swap at least once
            compact_tail_rows=max(8, int(expected_inserts / 2.5) or 8))
        eng = idx.serving_engine(
            min_bucket=SERVING_MIN_BUCKET or max(1, BATCH // 32),
            max_bucket=BATCH)
        eng.warmup()
        idx.start_compactor()
        # one write-only tenant at weight = the requested mix: overall
        # write share == mix_frac for any fraction in (0, 1)
        tenants = (
            loadgen.TenantSpec("readers", weight=1.0 - mix_frac,
                               batch_sizes=(1, 2, 4, 8)),
            loadgen.TenantSpec("writers", weight=mix_frac,
                               batch_sizes=(1,),
                               insert_fraction=round(2 / 3, 4),
                               delete_fraction=round(1 / 3, 4)),
        ) if mix_frac else (
            loadgen.TenantSpec("readers", batch_sizes=(1, 2, 4, 8)),)
        spec = loadgen.WorkloadSpec(
            rate_qps=MUTATION_RATE, duration_s=MUTATION_SECONDS,
            seed=KNEE_SEED, tenants=tenants)
        def _breach_total():
            if not obs.enabled():
                return 0
            return sum(s["value"] for s in obs.snapshot().get(
                _mn.SLO_BREACH_TRANSITIONS, {}).get("series", []))

        breach0 = _breach_total()
        try:
            with QueryQueue(eng, max_wait_ms=2.0) as q:
                rep = loadgen.run_workload(
                    q, loadgen.generate(spec), queries=queries)
        finally:
            idx.close()
        breach1 = _breach_total()
        st = idx.stats()
        lat = rep.get("latency_ms") or {}
        swap_hist = (obs.histogram(_mn.INDEX_SWAP_SECONDS).summary()
                     if obs.enabled() else None) or {}
        block = {
            "mutation_version": 1,
            "write_mix": {"insert_fraction": insert_frac,
                          "delete_fraction": delete_frac},
            "rate_qps": MUTATION_RATE,
            "duration_s": MUTATION_SECONDS,
            "index_rows": n_idx,
            "admitted_p99_ms": lat.get("p99"),
            "admitted_p50_ms": lat.get("p50"),
            "achieved_qps": rep.get("achieved_qps"),
            "compactions": int(st["compactions"]),
            "epoch": int(st["epoch"]),
            "swap_seconds_max": swap_hist.get("max"),
            "reads": {"offered": rep["offered"], "ok": rep["ok"],
                      "rejected": rep["rejected"],
                      "shed": rep["shed"], "errors": rep["errors"]},
            "writes": dict(rep.get("writes") or {}),
            "slo_breach_transitions": int(breach1 - breach0),
        }
        from knn_tpu.index.artifact import validate_mutation_block

        errs = validate_mutation_block(block)
        if errs:
            block["validation_errors"] = errs
        return {"mutation": block,
                "mutation_admitted_p99_ms": lat.get("p99")}

    def sweep_ivf():
        """Opt-in IVF tier measurement (knn_tpu.ivf): train the
        list-major placement, run the certified probed search over the
        full query set, and emit the validated ``ivf`` artifact block —
        recall_at_k / probe_fraction / fallback_rate /
        bytes_streamed_ratio beside the probed qps.  Every run also
        re-asserts the exactness anchor on a sub-batch: the
        nprobe=ncentroids arm must reproduce exact brute force bitwise,
        or the block carries the mismatch as its error instead of a
        lying rate.  ncentroids/nprobe come from the KNN_TPU_IVF_*
        switch family (index defaults: round(sqrt(n)), ncentroids/4)."""
        from knn_tpu.ivf import IVFIndex
        from knn_tpu.ivf.artifact import IVF_VERSION, validate_ivf_block
        from knn_tpu.ops.refine import refine_shared_exact

        # cap the trained placement like mutation mode: this line
        # measures the pruning tradeoff, not raw scan throughput
        n_idx = min(N, 131072)
        idx = IVFIndex(db[:n_idx], mesh=mesh, k=K, metric="l2",
                       train_tile=tile)
        ist = idx.stats()
        idx.search_certified(queries[:BATCH])  # warm/compile off-clock
        times = []
        stats = None
        for _ in range(RUNS):
            t0 = time.perf_counter()
            _, _, stats = idx.search_certified(queries)
            times.append(time.perf_counter() - t0)
        qps = round(NQ / float(np.mean(times)), 2)
        anchor_err = None
        try:
            aq = queries[: min(BATCH, 256)]
            d_all, i_all, _ = idx.search_certified(
                aq, nprobe=ist["ncentroids"])
            d_ref, i_ref = refine_shared_exact(
                db[:n_idx], aq, np.arange(n_idx, dtype=np.int64), K)
            if not (np.array_equal(i_all, i_ref)
                    and np.array_equal(d_all, d_ref)):
                anchor_err = ("exactness anchor: nprobe=ncentroids "
                              "!= brute force bitwise")
        except Exception as e:  # noqa: BLE001 — recorded, never fatal
            anchor_err = f"exactness anchor: {type(e).__name__}: {e}"
        block = {
            "ivf_version": IVF_VERSION,
            "ncentroids": int(stats["ncentroids"]),
            "nprobe": int(stats["nprobe"]),
            "queries": int(stats["queries"]),
            "k": int(stats["k"]),
            "probe_fraction": stats["probe_fraction"],
            "recall_at_k": stats["recall_at_k"],
            "fallback_rate": stats["fallback_rate"],
            "bytes_streamed_ratio": stats["bytes_streamed_ratio"],
            "qps": qps,
            "selector": stats["selector"],
            "fallback_queries": int(stats["fallback_queries"]),
            "certified_queries": int(stats["certified_queries"]),
            "genuine_misses": int(stats["genuine_misses"]),
            "epoch": int(ist["epoch"]),
            "compactions": int(ist["compactions"]),
        }
        if anchor_err:
            block["error"] = anchor_err
        errs = validate_ivf_block(block)
        if errs:
            block["validation_errors"] = errs
        return {"ivf": block}

    def sweep_multihost():
        """Multi-host serving measurement, two arms on one line:

        (a) the HIERARCHICAL placement — a make_host_mesh fold of the
        available devices into (query, host, chip), per-chip candidates
        reduced per-host over the ICI db axis then globally over the
        host axis at the crossover-resolved strategies — timed against
        the flat-mesh placement's own numbers elsewhere on the line
        (results are bitwise-identical; tests pin that, the bench
        measures the merge-tree overhead);

        (b) the HOST-RAM shard tier — the same corpus forced through a
        budget sized for ~KNN_BENCH_MULTIHOST_SWEEPS sweeps, streaming
        segment-by-segment with dispatch-ahead — per-sweep walls show
        whether the stream held flat.

        The entry's roofline block models the cluster: ``db_hosts``
        hosts and the MODEL_VERSION-4 DCN merge term, validated by the
        artifact refresher like every roofline block."""
        from knn_tpu.analysis import hbm as _hbm
        from knn_tpu.obs import roofline as _rl
        from knn_tpu.parallel import crossover as _xover
        from knn_tpu.parallel.mesh import make_host_mesh

        hosts = MULTIHOST_HOSTS
        ndev = len(jax.devices())
        if ndev % hosts:
            raise RuntimeError(
                f"{ndev} devices not divisible by "
                f"KNN_BENCH_MULTIHOST_HOSTS={hosts}")
        per_host = ndev // hosts
        chips = 2 if per_host % 2 == 0 else 1
        qs = per_host // chips
        mesh_h = make_host_mesh(qs, hosts, chips)
        prog_h = ShardedKNN(db, mesh=mesh_h, k=K, metric=METRIC,
                            train_tile=tile)
        nq_run = min(NQ, BATCH)
        qb = queries[:nq_run]
        np.asarray(prog_h.search(qb)[0])  # warm, BLOCKED (async dispatch)
        times = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            d, _ = prog_h.search(qb)
            np.asarray(d)
            times.append(time.perf_counter() - t0)
        qps_h = nq_run / float(np.mean(times))

        # host-RAM tier: budget sized so the corpus takes ~MULTIHOST_SWEEPS
        # sweeps (per-host share), streamed through the flat mesh
        rows_padded = -(-N // (len(mesh.devices.ravel()))) * len(
            mesh.devices.ravel())
        total_b = _hbm.placement_bytes(rows_padded, DIM)
        budget = max(1, -(-total_b // (hosts * MULTIHOST_SWEEPS)))
        # the budget is derived from a byte model that rounds differently
        # than ShardedKNN's own accounting; halve until the tier really
        # engages so the arm can never silently measure a resident
        # placement as a "stream"
        prog_t, ht = None, None
        for _ in range(4):
            prog_t = ShardedKNN(db, mesh=mesh_h, k=K, metric=METRIC,
                                train_tile=tile, hbm_budget_bytes=budget)
            ht = prog_t.hosttier_stats()
            if ht is not None:
                break
            budget = max(1, budget // 2)
        if ht is None:
            raise RuntimeError(
                f"host-RAM tier never engaged down to budget={budget} B "
                f"for n={N}, d={DIM}; shrink KNN_BENCH_MULTIHOST_SWEEPS")
        np.asarray(prog_t.search(qb)[0])  # warm, blocked
        t0 = time.perf_counter()
        d, _ = prog_t.search(qb)
        np.asarray(d)
        tier_wall = time.perf_counter() - t0
        ht = prog_t.hosttier_stats()
        last = ht.get("last_search") or {}

        block = {
            "hosts": hosts,
            "chips_per_host": chips,
            "merge": {
                "intra": {"strategy": prog_h.merge,
                          "source": prog_h.merge_source},
                "dcn": {"strategy": prog_h.dcn_merge,
                        "source": prog_h.dcn_merge_source},
            },
            "dcn_merge_bytes": _xover.merge_bytes(
                nq_run, K, hosts, prog_h.dcn_merge),
            "hosttier": {
                "sweeps": int(last.get("sweeps") or ht["sweeps"]),
                "budget_bytes": int(ht["budget_bytes"]),
                "segment_rows": int(ht["segment_rows"]),
                "bytes_per_sweep": int(ht["bytes_per_sweep"]),
                "sweep_walls_s": last.get("sweep_walls_s"),
                "qps": round(nq_run / tier_wall, 2),
            },
        }
        model = _rl.xla_cost_model(
            n=N, d=DIM, k=K, nq=nq_run, selector="exact",
            dtype="float32", batch=nq_run,
            device_kind=getattr(dev, "device_kind", ""), backend=backend,
            num_devices=ndev, db_hosts=hosts,
            dcn_merge=prog_h.dcn_merge)
        return {
            "multihost": block,
            "qps_mean": round(qps_h, 2),
            "qps_std": round(float(np.std(nq_run / np.asarray(times))), 2),
            # a topology line can be the published mode only when it ran
            # alone; it carries no MFU of its own
            "mfu": None,
            "roofline": _rl.attribute(model, qps_h),
        }

    def sweep_join():
        """Opt-in bulk kNN-join measurement (knn_tpu.join): every row
        of a host-resident query set A joined against the placed corpus
        through the double-buffered superblock stream, then the SAME
        rows pushed through a looped, per-block-synchronous serving
        loop on the same placement — the amortization claim
        (rows/s + overlap_ratio vs baseline_rows_per_s) as one
        validated ``join`` artifact block.  rows_per_s hoists to the
        line as ``join_rows_per_s`` via the schema catalog."""
        from knn_tpu.join import knn_join
        from knn_tpu.join.artifact import validate_join_block
        from knn_tpu.obs import roofline as _rl

        rows = JOIN_ROWS or max(NQ, 4 * BATCH)
        reps = -(-rows // NQ)
        qa = np.tile(queries, (reps, 1))[:rows] if reps > 1 \
            else queries[:rows]
        sb = JOIN_SUPERBLOCK or None
        # warm run compiles the stream program (and fixes the resolved
        # superblock for the baseline), then RUNS timed joins
        d_j, i_j, jstats = knn_join(prog, qa, mode="stream",
                                    superblock_rows=sb, depth=JOIN_DEPTH)
        sb_rows = int(jstats["superblock_rows"])
        walls, overlaps = [], []
        for _ in range(RUNS):
            _, _, jstats = knn_join(prog, qa, mode="stream",
                                    superblock_rows=sb_rows,
                                    depth=JOIN_DEPTH)
            walls.append(jstats["wall_s"])
            overlaps.append(jstats["overlap_ratio"])
        wall = float(np.mean(walls))
        rows_per_s = round(rows / wall, 2)

        # looped-serving baseline: the same superblocks through
        # prog.search, every block's result fetched before the next
        # dispatch — the pre-join serving pattern (no dispatch-ahead),
        # so the delta IS the overlap machinery
        def pad_to(chunk):
            pad = sb_rows - chunk.shape[0]
            return np.pad(chunk, ((0, pad), (0, 0))) if pad else chunk

        np.asarray(prog.search(pad_to(qa[:sb_rows]))[0])  # warm, blocked
        base_walls = []
        for _ in range(RUNS):
            t0 = time.perf_counter()
            for lo in range(0, rows, sb_rows):
                d_b, _ = prog.search(pad_to(qa[lo:lo + sb_rows]))
                np.asarray(d_b)  # block: serving fetches per request
            base_walls.append(time.perf_counter() - t0)
        baseline = round(rows / float(np.mean(base_walls)), 2)

        block = {
            "join_version": _join_version(),
            "mode": jstats["mode"],
            "rows": int(jstats["rows"]),
            "k": int(jstats["k"]),
            "superblock_rows": sb_rows,
            "depth": int(jstats["depth"]),
            "order": jstats["order"],
            "superblocks": int(jstats["superblocks"]),
            "db_segments": int(jstats["db_segments"]),
            "dispatches": int(jstats["dispatches"]),
            "rows_per_s": rows_per_s,
            "overlap_ratio": overlaps[-1],
            "wall_s": round(wall, 4),
            "plan": jstats["plan"],
            "baseline_rows_per_s": baseline,
            "speedup_vs_serving": (round(rows_per_s / baseline, 3)
                                   if baseline else None),
        }
        errs = validate_join_block(block)
        if errs:
            block["validation_errors"] = errs
        entry = {"join": block}
        try:
            # the MODEL_VERSION-7 amortized-db-bytes model for this
            # exact join shape: terms.h2d + the join sub-block, the
            # analytic rows/s ceiling the measured rate is judged by
            model = _rl.join_cost_model(
                n_a=rows, n_b=N, d=DIM, k=K, superblock_rows=sb_rows,
                selector="exact",
                db_segment_rows=int(jstats["plan"].get(
                    "db_segment_rows", 0)),
                device_kind=getattr(dev, "device_kind", ""),
                backend=backend,
                num_devices=len(mesh.devices.ravel()))
            entry["roofline"] = _rl.attribute(model, rows_per_s)
        except Exception as e:  # noqa: BLE001 — advisory only
            entry["roofline"] = {"error": f"{type(e).__name__}: {e}"}
        return entry

    def _join_version():
        from knn_tpu.join.artifact import JOIN_VERSION

        return JOIN_VERSION

    def sweep_quality():
        """Opt-in shadow-audit quality measurement (knn_tpu.obs.audit):
        a short serving replay with the audit sampler forced to rate
        1.0, so every request's served top-k is re-scored off the
        serving path against the f64 exact oracle over the full placed
        corpus.  The block is the audited quality ledger — recall@k,
        rank displacement, distance error — as one validated
        ``quality`` artifact block; audit_recall_at_k hoists to the
        line via the schema catalog."""
        from knn_tpu import obs as _obs
        from knn_tpu.obs import audit as _audit
        from knn_tpu.obs import names as _names
        from knn_tpu.serving.engine import ServingEngine

        if not _obs.enabled():
            return {"quality": {
                "error": "telemetry disabled (KNN_TPU_OBS=0): the "
                         "audit sampler cannot arm"}}
        saved = {k: os.environ.get(k)
                 for k in (_audit.AUDIT_RATE_ENV,
                           _audit.AUDIT_BUDGET_ENV)}
        os.environ[_audit.AUDIT_RATE_ENV] = "1.0"
        os.environ.pop(_audit.AUDIT_BUDGET_ENV, None)
        _audit.reset_auditor()
        t0 = time.perf_counter()
        try:
            min_bucket = SERVING_MIN_BUCKET or max(1, BATCH // 32)
            eng = ServingEngine(prog, min_bucket=min_bucket,
                                max_bucket=BATCH)
            eng.warmup()
            rng_q = np.random.default_rng(1234)
            handles = []
            for _ in range(QUALITY_REQUESTS):
                s = int(rng_q.integers(1, BATCH + 1))
                lo = int(rng_q.integers(0, max(1, NQ - s)))
                handles.append(eng.submit(queries[lo:lo + s]))
            for h in handles:
                h.result()
            aud = _audit.get_auditor()
            drained = aud.drain(timeout=120.0)
            summ = aud.summary()
            disp = _obs.histogram(_names.AUDIT_RANK_DISPLACEMENT,
                                  tenant="-").summary()
            derr = _obs.histogram(_names.AUDIT_DISTANCE_ERROR,
                                  tenant="-").summary()
            recall = _obs.histogram(_names.AUDIT_RECALL,
                                    tenant="-").summary()
            block = {
                "quality_version": _audit.QUALITY_VERSION,
                "audit_rate": summ["rate"],
                "audit_sampled_requests": summ["sampled_requests"],
                "audit_replayed_queries": summ["replayed_queries"],
                "audit_deficient_queries": summ["deficient_queries"],
                "audit_dropped_records":
                    int(sum(summ["dropped"].values())),
                "audit_recall_at_k":
                    (round(float(recall["mean"]), 6)
                     if recall.get("window") else None),
                "audit_rank_displacement_p99":
                    (round(float(disp["p99"]), 4)
                     if disp.get("window") else None),
                "audit_distance_rel_error_p99":
                    (round(float(derr["p99"]), 8)
                     if derr.get("window") else None),
                "wall_s": round(time.perf_counter() - t0, 4),
            }
            if not drained:
                block["error"] = ("audit drain timed out with "
                                  "replays still pending")
            return {"quality": block}
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            _audit.reset_auditor()

    def sweep_fleet():
        """Opt-in fleet-plane measurement (knn_tpu.obs.fleet): merge
        the fleet's telemetry and emit the validated ``fleet`` artifact
        block.  With ``KNN_TPU_FLEET_MEMBERS`` set it collects the
        live endpoints; otherwise it snapshots THIS process and merges
        the one-member fleet — the offline proof that the collect ->
        merge -> block pipeline holds on every bench host."""
        import tempfile as _tempfile

        from knn_tpu import obs as _obs
        from knn_tpu.obs import fleet as _fleet

        t0 = time.perf_counter()
        if not _obs.enabled():
            block = _fleet.artifact_block(_fleet.live_fleet_report())
        elif _fleet.fleet_members():
            block = _fleet.artifact_block(_fleet.fleet_report())
        else:
            with _tempfile.TemporaryDirectory() as d:
                _obs.write_json_snapshot(
                    os.path.join(d, "self.json"))
                block = _fleet.artifact_block(
                    _fleet.fleet_report(snapshot_dir=d))
        block["wall_s"] = round(time.perf_counter() - t0, 4)
        return {"fleet": block}

    def roofline_for_mode(mode, entry):
        """The selector's ``roofline`` block (knn_tpu.obs.roofline):
        analytic ceiling q/s + bound class for the config this mode
        actually ran, attributed against its device-phase rate where
        one was measured (the harness-independent number) else the
        end-to-end mean.  On a cpu/unknown device the model falls back
        to the generic-CPU peaks with ``estimated: true`` — a flagged
        estimate beats an attribution-blind line.  Failure-proof: a
        model gap degrades to an error field, never kills the line."""
        from knn_tpu.obs import roofline as _rl

        common = dict(n=N, d=DIM, k=K,
                      device_kind=getattr(dev, "device_kind", ""),
                      backend=backend,
                      num_devices=len(mesh.devices.ravel()))
        pb = entry.get("phase_breakdown") or {}
        if mode == "certified_pallas":
            pq_kw = {}
            if KNOBS["precision"] == "pq":
                # price the pq arm at the geometry the placement
                # actually trained (env-tunable), not the module default
                try:
                    plq = prog._pq_placement()
                    pq_kw = dict(pq_dsub=int(plq["dsub"]),
                                 pq_ncodes=int(plq["ncodes"]))
                except Exception:  # noqa: BLE001 — advisory pricing only
                    pass
            model = _rl.pallas_cost_model(
                nq=NQ, precision=KNOBS["precision"],
                kernel=KNOBS["kernel"], grid_order=KNOBS["grid_order"],
                tile_n=KNOBS["tile_n"],
                block_q=KNOBS["block_q"], survivors=KNOBS["survivors"],
                margin=MARGIN, **pq_kw, **common)
            measured = pb.get("device_qps") or entry.get("qps_mean")
        elif mode == "serving":
            # the bucketed engine dispatches the exact-search program;
            # max_bucket chunks bound its db passes — an optimistic
            # ceiling for the variable-batch trace
            model = _rl.xla_cost_model(
                nq=int(entry.get("trace_queries") or NQ),
                selector="exact", dtype=DTYPE, batch=BATCH, **common)
            measured = entry.get("sustained_qps")
        else:
            model = _rl.xla_cost_model(
                nq=NQ, selector="exact" if mode == "exact" else "approx",
                dtype=DTYPE, batch=BATCH,
                margin=MARGIN if mode == "exact" else APPROX_MARGIN,
                **common)
            measured = pb.get("device_qps") or entry.get("qps_mean")
        att = _rl.attribute(model, measured)
        # e2e attribution beside the device-phase one, where they differ
        if measured and entry.get("qps_mean") and \
                measured != entry["qps_mean"] and att.get("ceiling_qps"):
            att["roofline_pct_e2e"] = round(
                entry["qps_mean"] / att["ceiling_qps"], 4)
        return att

    sweeps = {
        "exact": sweep_exact,
        "certified_approx": sweep_certified("approx"),
        "certified_pallas": sweep_certified("pallas"),
    }
    #: database passes per query: coarse matmul, + the certificate's
    #: count-below matmul for the counted certified mode (fallback
    #: excluded — it is rare, per-run stats record it).  The pallas
    #: kernel self-certifies: ONE pass.
    passes = {"exact": 1, "certified_approx": 2, "certified_pallas": 1}

    def phase_breakdown_pallas():
        """Where a certified_pallas sweep's wall time goes (VERDICT r2
        missing item 4): device compute vs device->host transfer vs host
        rank-correction, measured on the full query set with the already-
        compiled program, plus the host link's D2H bandwidth."""
        from knn_tpu.ops.refine import rank_correct_runs

        import jax as _jax

        from knn_tpu.parallel.sharded import DB_AXIS, unpack_certified

        # the same program+geometry the timed sweep ran (ONE source of
        # truth: ShardedKNN._pallas_setup, fed the same resolved KNOBS)
        pp, m, w, _ = prog._pallas_setup(
            MARGIN, KNOBS["tile_n"], KNOBS["precision"],
            survivors=KNOBS["survivors"], block_q=KNOBS["block_q"],
            final_select=KNOBS["final_select"],
            final_recall_target=KNOBS["final_recall_target"],
            grid_order=KNOBS["grid_order"], kernel=KNOBS["kernel"],
            batch_rows=queries.shape[0],
        )
        pb_queries = queries
        if METRIC == "cosine":
            # the pallas program computes l2 against the unit-normalized
            # placed db; search_certified normalizes queries internally,
            # so this timing probe must feed it the same normalized form
            from knn_tpu.parallel.sharded import _row_normalize_f64

            pb_queries = _row_normalize_f64(queries)
        t0 = time.perf_counter()
        qp, _ = prog._place_queries(pb_queries)
        _jax.block_until_ready(qp)
        h2d = time.perf_counter() - t0
        # the operand tail is precision-shaped (int8: the quantized
        # placement; f32: the scalar norm bound, and at the default
        # precision the resident row operands the setup above resolved:
        # ask after it) — ONE home,
        # ShardedKNN._pallas_operands, so this probe can never call the
        # program with the wrong arity
        ops_tail = prog._pallas_operands(KNOBS["precision"])
        out = pp(qp, prog._tp, *ops_tail)
        _jax.block_until_ready(out)  # warm/compiled
        t0 = time.perf_counter()
        out = pp(qp, prog._tp, *ops_tail)
        _jax.block_until_ready(out)  # device-only time, no transfer
        dev = time.perf_counter() - t0
        t0 = time.perf_counter()
        # the sweep's fetch: ONE packed array
        packed = np.asarray(out)
        xfer = time.perf_counter() - t0
        gi, tight, badf, dk = unpack_certified(packed[:NQ], K, w, True)
        t0 = time.perf_counter()
        # the certified space's arrays: for cosine that is the unit-
        # normalized pair (the rows as placed; prog's host copy is the
        # rows as given since PR 43)
        rank_correct_runs(gi, tight, K, pb_queries, prog._placed_host(),
                          d32k=dk.astype(np.float64))
        host = time.perf_counter() - t0
        mb = packed.nbytes / 1e6
        # kernel launch accounting (ONE home for the arithmetic:
        # ops.pallas_knn): the tiled grid re-launches its pipelined body
        # once per train tile; the streaming kernel is one launch per
        # (batch, shard) whose in-kernel DMA loop covers every tile
        from knn_tpu.ops.pallas_knn import (
            TILE_N as _TILE_N,
            effective_tile,
            kernel_launches_per_batch,
        )

        shard_rows = prog._tp.shape[0] // prog.mesh.shape[DB_AXIS]
        eff = effective_tile(
            shard_rows, KNOBS["tile_n"] or _TILE_N,
            KNOBS["survivors"], m + 2)
        return {
            "kernel": KNOBS["kernel"],
            "db_tiles_per_shard": -(-shard_rows // eff),
            "kernel_launches_per_batch_shard": kernel_launches_per_batch(
                KNOBS["kernel"], shard_rows, eff),
            "h2d_queries_s": round(h2d, 4),
            "device_s": round(dev, 4),
            "device_qps": round(NQ / dev, 1),
            "d2h_transfer_s": round(xfer, 4),
            "d2h_mb": round(mb, 2),
            "d2h_mbps": round(mb / xfer, 1) if xfer > 0 else None,
            "host_rank_correct_s": round(host, 4),
        }

    def phase_breakdown_counted(mode):
        """Device-phase rate for the counted selectors (VERDICT r4 item
        6: ``mfu_device`` for EVERY selector, not just the pallas
        winner): the coarse select program alone for ``exact``, coarse +
        count-below for ``certified_approx`` — no host refine, no result
        transfer.  Measured at the SWEEP's batch shape (BATCH queries):
        both timed sweeps dispatch BATCH-sized device programs, so this
        is a compile-cache hit and the rate describes the geometry the
        sweep actually ran — an NQ-shaped probe would silently pay a
        fresh compile AND measure a different batch."""
        import jax as _jax

        from knn_tpu.parallel.sharded import (
            DB_AXIS,
            _count_program,
            _knn_program,
            _row_normalize_f64,
        )

        qb = queries[:BATCH]
        if qb.shape[0] < BATCH:  # one compiled shape, like the sweeps
            qb = np.pad(qb, ((0, BATCH - qb.shape[0]), (0, 0)))
        shard_rows = prog._tp.shape[0] // prog.mesh.shape[DB_AXIS]
        if mode == "exact":
            coarse = _knn_program(
                prog.mesh, coarse_k, METRIC, prog.merge, prog.n_train,
                prog.train_tile, prog._dtype_key)
            qp, _ = prog._place_queries(qb)
            launches = [lambda: coarse(qp, prog._tp)]
        else:
            qn = _row_normalize_f64(qb) if METRIC == "cosine" else qb
            cert_metric = "l2" if METRIC == "cosine" else METRIC
            m_c = min(K + APPROX_MARGIN, prog.n_train, shard_rows)
            coarse = _knn_program(
                prog.mesh, m_c, cert_metric, prog.merge, prog.n_train,
                prog.train_tile, prog._dtype_key, "approx",
                recall_target=APPROX_RT)
            count = _count_program(prog.mesh, prog.n_train, prog.train_tile)
            qp, _ = prog._place_queries(qn)
            # threshold values don't change the count pass's FLOPs
            thr = np.zeros(qp.shape[0], np.float32)
            launches = [lambda: coarse(qp, prog._tp),
                        lambda: count(qp, prog._tp, thr)]
        dev = 0.0
        for launch in launches:
            _jax.block_until_ready(launch())  # warm (a sweep cache hit)
            t0 = time.perf_counter()
            _jax.block_until_ready(launch())
            dev += time.perf_counter() - t0
        return {"device_s": round(dev, 4),
                "device_batch": BATCH,
                "device_qps": round(BATCH / dev, 1)}

    def soundness_gate():
        """Small-scale compiled certified search vs the float64 oracle —
        embedded so a bare ``python bench.py`` artifact carries its own
        soundness verdict.  ~20 s once per run at 128-dim configs,
        scaling ~linearly with dim (the host float64 oracle dominates);
        KNN_BENCH_GATE=0 skips."""
        from knn_tpu.ops.certified import host_exact_knn
        from knn_tpu.ops.pallas_knn import TILE_N as TILE_N_DEFAULT
        from knn_tpu.ops.pallas_knn import knn_search_pallas

        g_rng = np.random.default_rng(7)
        # gate at the CONFIG's full dim: dim > DIM_CHUNK takes the
        # kernel's multi-chunk scratch-accumulation path (gist's 960),
        # which a 128-dim gate would never exercise — and the round-3
        # lesson is that soundness failures are build-detail dependent
        g_db = g_rng.random((100_000, DIM), dtype=np.float32) * 128
        # tie pressure: duplicate rows + a near-tie pileup exercise the
        # lexicographic rank correction and the near-tie mask in the
        # compiled build (a different failure class than the round-3
        # bounds-accumulation miss)
        g_db[50_000:50_050] = g_db[:50]
        g_db[70_000:70_020] = g_db[100] + 1e-3
        g_q = g_rng.random((24, DIM), dtype=np.float32) * 128
        g_q[0] = g_db[100] + 5e-4  # lands inside the pileup
        # a query ON a duplicated pair forces EXACT ties across distant
        # db tiles (rows 0 and 50_000 live ~3 tiles apart at the default
        # geometry) into the top-k — the cross-tile lexicographic merge
        # path a same-tile pileup alone never reaches
        g_q[1] = g_db[0] + 5e-4
        g_k = min(K, 100)
        _, oracle = host_exact_knn(g_db, g_q, g_k)
        # gate the SAME kernel configuration the sweeps run (precision,
        # geometry, final select, db-streaming strategy) — the round-3
        # failure was build-detail dependent, so checking a different
        # program proves nothing
        _, idx, g_stats = knn_search_pallas(
            g_q, g_db, g_k, precision=KNOBS["precision"],
            tile_n=KNOBS["tile_n"] or TILE_N_DEFAULT,
            survivors=KNOBS["survivors"], block_q=KNOBS["block_q"],
            final_select=KNOBS["final_select"],
            final_recall_target=KNOBS["final_recall_target"],
            grid_order=KNOBS["grid_order"], kernel=KNOBS["kernel"],
        )
        return {
            "pallas_gate_ok": bool((idx == oracle).all()),
            "gate_queries": int(g_q.shape[0]),
            "gate_rows": int(g_db.shape[0]),
            "gate_stats": g_stats,
        }

    gate = None
    if (os.environ.get("KNN_BENCH_GATE", "1") != "0"
            and backend not in ("cpu",)
            and "certified_pallas" in modes):
        try:
            _vlog("compiled soundness gate ...")
            gate = soundness_gate()
            _vlog(f"gate: {gate['pallas_gate_ok']}")
        except Exception as e:  # noqa: BLE001 — recorded on the line; the exit code reports it
            gate = {"pallas_gate_ok": None,
                    "gate_error": f"{type(e).__name__}: {e}"}

    trace_dir = ARGS.trace_dir
    results = {}
    for mode in modes:
        entry = {}
        if mode == "serving":
            # trace replay, not a fixed-shape timed sweep: its entry
            # carries sustained_qps + latency percentiles instead of
            # qps_mean, and never competes for the headline number
            try:
                entry = sweep_serving()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            if "error" not in entry:
                try:
                    entry["roofline"] = roofline_for_mode(mode, entry)
                except Exception as e:  # noqa: BLE001 — advisory only
                    entry["roofline"] = {
                        "error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "knee":
            # open-loop saturation sweep: like serving, a traffic-shape
            # measurement, never a headline-number competitor
            try:
                entry = sweep_knee()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "mutation":
            # live mixed read+write traffic across compaction swaps: a
            # traffic-shape measurement, never a headline competitor
            try:
                entry = sweep_mutation()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "ivf":
            # probed-tier tradeoff measurement (bytes saved vs fallback
            # repairs): a pruning-shape line, never a headline competitor
            try:
                entry = sweep_ivf()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "multihost":
            # hierarchical-merge + host-RAM tier measurement: a
            # topology-shape line, never a headline-number competitor
            try:
                entry = sweep_multihost()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "join":
            # bulk kNN-join throughput (rows/s, not q/s): an offline
            # batch-shape line, never a headline-number competitor
            try:
                entry = sweep_join()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "quality":
            # shadow-audit quality replay: a correctness ledger, never
            # a throughput competitor
            try:
                entry = sweep_quality()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        if mode == "fleet":
            # cross-host telemetry merge: an observability ledger,
            # never a throughput competitor
            try:
                entry = sweep_fleet()
            except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
                entry = {"error": f"{type(e).__name__}: {e}"}
            results[mode] = entry
            continue
        try:
            fn = sweeps[mode]
            _vlog(f"mode {mode}: recall check + warm ...")
            if oracle_idx is not None:
                idx_sub, _ = fn(sub)  # also compiles every program involved
                entry["recall_at_k"] = recall_at_k(idx_sub, oracle_idx)
            # warm the exact shapes the timed runs use: the pallas mode
            # runs ONE full-size batch (different program shape than the
            # BATCH-sized pipeline), so it must warm on the full set or
            # run 1 silently pays its compile
            fn(queries if mode == "certified_pallas" else queries[:BATCH])
            times = []
            stats = None
            _vlog(f"mode {mode}: timed runs ...")
            for _ in range(RUNS):
                t0 = time.perf_counter()
                _, stats = fn(queries)
                times.append(time.perf_counter() - t0)
            _vlog(f"mode {mode}: done ({round(NQ / float(np.mean(times)), 1)} q/s)")
            # one extra instrumented run, OUTSIDE the timed stats —
            # profiler overhead must not skew the headline numbers.
            # obs.profiler wraps jax.profiler.trace, so the artifact is
            # the on-chip XLA trace, TensorBoard-loadable from
            # <dir>/<mode>; gated by --trace-dir/KNN_BENCH_TRACE (this
            # explicit flag) or the ambient KNN_TPU_PROFILE_DIR
            from knn_tpu.obs import profiler as _profiler

            with _profiler.device_trace(mode, base_dir=trace_dir) as tdir:
                if tdir is not None:
                    t0 = time.perf_counter()
                    fn(queries)
                    entry["traced_run_s"] = round(time.perf_counter() - t0, 4)
                    entry["trace_dir"] = tdir
            times = np.asarray(times)
            qps = NQ / times
            flops = 2.0 * NQ * N * DIM * passes[mode]
            entry.update({
                "qps_mean": round(float(qps.mean()), 2),
                "qps_std": round(float(qps.std()), 2),
                "qps_best": round(float(qps.max()), 2),
                "time_mean_s": round(float(times.mean()), 4),
                "runs": RUNS,
                "mfu": (None if peak is None
                        else round(flops / float(times.mean()) / peak, 4)),
            })
            if stats is not None:
                entry["certified_stats"] = stats
            if mode in ("exact", "certified_approx"):
                pb = phase_breakdown_counted(mode)
                entry["phase_breakdown"] = pb
                if peak is not None and pb.get("device_s"):
                    # the probe ran device_batch queries, not NQ
                    bflops = (2.0 * pb["device_batch"] * N * DIM
                              * passes[mode])
                    entry["mfu_device"] = round(
                        bflops / pb["device_s"] / peak, 4)
            if mode == "certified_pallas":
                pb = phase_breakdown_pallas()
                entry["phase_breakdown"] = pb
                if peak is not None and pb.get("device_s"):
                    # MFU of the device phase alone — what the chip does,
                    # net of the host transfers
                    entry["mfu_device"] = round(
                        flops / pb["device_s"] / peak, 4
                    )
                # label-only consumers (the reference's actual workload:
                # predicted labels) skip the distance transfer
                lo_fn = sweep_certified("pallas", return_distances=False)
                lo_fn(queries)  # warm the distance-free fetch path
                lo_times = []
                for _ in range(min(RUNS, 3)):
                    t0 = time.perf_counter()
                    lo_fn(queries)
                    lo_times.append(time.perf_counter() - t0)
                entry["qps_labels_only"] = round(
                    NQ / float(np.mean(lo_times)), 2
                )
        except Exception as e:  # noqa: BLE001 — one bad mode must not kill the line
            entry["error"] = f"{type(e).__name__}: {e}"
        if "qps_mean" in entry:
            # percent-of-roofline attribution beside mfu/mfu_device on
            # EVERY measured selector line — the named gap the kernel
            # campaign attacks per config
            try:
                entry["roofline"] = roofline_for_mode(mode, entry)
            except Exception as e:  # noqa: BLE001 — advisory only
                entry["roofline"] = {"error": f"{type(e).__name__}: {e}"}
        results[mode] = entry

    def _ok(m):
        e = results.get(m, {})
        if "qps_mean" not in e:
            return False
        r = e.get("recall_at_k")
        if r is None:
            # no oracle: certified modes are exact by construction, but the
            # exact path's margin heuristic is unverified -> not headline
            return m.startswith("certified")
        return r == 1.0

    ranked = sorted((m for m in results if _ok(m)),
                    key=lambda m: -results[m]["qps_mean"])
    recall_flag = {}
    if not ranked:
        # no mode with verified exactness; publish the fastest measured one
        # honestly flagged rather than nothing.  Distinguish "no oracle to
        # check against" from "checked and missed neighbors".
        ranked = sorted((m for m in results if "qps_mean" in results[m]),
                        key=lambda m: -results[m]["qps_mean"])
        if ranked:
            r = results[ranked[0]].get("recall_at_k")
            recall_flag = (
                {"recall_unverified": True} if r is None
                else {"recall_below_one": True}
            )
    if not ranked:
        _fail("all_modes", {m: results[m].get("error", "?") for m in results},
              selectors=results, backend=backend)
    best = ranked[0]
    qps = results[best]["qps_mean"]
    # vs_baseline from the SAME rounded fields the JSON carries, so the
    # artifact is internally reproducible (round-2 advisor finding)
    cpu_qps_r = round(cpu_qps, 2) if cpu_qps else None

    # the chip's own rate, net of the host<->device transfers.  Hoisted
    # from the WINNING mode's phase breakdown (every selector carries one since r4), so
    # the sentinel's device_phase_qps baseline judges device-phase
    # regressions separately from end-to-end qps on every line — not
    # only when certified_pallas wins; the pallas breakdown remains the
    # fallback for lines whose winner has no device probe
    dev_qps = (results.get(best, {})
               .get("phase_breakdown", {}).get("device_qps")
               or results.get("certified_pallas", {})
               .get("phase_breakdown", {}).get("device_qps"))
    # the winning mode's roofline verdict rides top-level: the full
    # block for readers, plus hoisted roofline_pct/bound_class so the
    # sentinel's curated-field baselines and the artifact refresher
    # read them flat.  Lines whose mfu is null (cpu backend / unknown
    # device kind) still get a block — computed from the generic-CPU
    # fallback peaks and flagged roofline_estimated — so CPU microbench
    # lines stop being attribution-blind.
    rl_top = results[best].get("roofline")
    if not isinstance(rl_top, dict) or "ceiling_qps" not in rl_top:
        try:
            rl_top = roofline_for_mode(best, results[best])
        except Exception as e:  # noqa: BLE001 — advisory only
            rl_top = {"error": f"{type(e).__name__}: {e}"}
    rl_fields = {"roofline": rl_top}
    # quantization provenance: precision rides top-level on EVERY line so
    # the precision-ladder A/B lines (int8 / pq vs the f32 family)
    # are self-describing and the artifact refresher can curate them
    # separately per arm; quantized lines add the certified bound's worst
    # case over this query set and the scales dtype (the reproducibility
    # trio the ISSUE names), and pq lines carry their codebook geometry
    quant_prov = {"precision": KNOBS["precision"]}
    if KNOBS["precision"] == "int8":
        try:
            from knn_tpu.ops import quantize as _qz

            plq = prog._int8_placement()
            qb_prov = queries
            if METRIC == "cosine":
                from knn_tpu.parallel.sharded import _row_normalize_f64

                qb_prov = _row_normalize_f64(queries)
            eps = _qz.score_error_bound(
                qb_prov, plq["stats"], offset=plq["offset"])
            quant_prov["quant_bound_max"] = float(np.max(eps))
            quant_prov["quant_scales_dtype"] = "float32"
        except Exception as e:  # noqa: BLE001 — provenance must not kill the line
            quant_prov["quant_bound_error"] = f"{type(e).__name__}: {e}"
    elif KNOBS["precision"] == "pq":
        # pq lines additionally carry the cataloged "pq" artifact block
        # (knn_tpu.analysis.artifacts): codebook geometry + the
        # certified bound's worst case, validated/swept like every
        # other bench block
        try:
            from knn_tpu.analysis import widths as _widths
            from knn_tpu.ops import pq as _pqm
            from knn_tpu.ops.pq_artifact import PQ_VERSION

            plq = prog._pq_placement()
            qb_prov = queries
            if METRIC == "cosine":
                from knn_tpu.parallel.sharded import _row_normalize_f64

                qb_prov = _row_normalize_f64(queries)
            eps = _pqm.score_error_bound_pq(qb_prov, plq["stats"])
            quant_prov["quant_bound_max"] = float(np.max(eps))
            quant_prov["quant_scales_dtype"] = "float32"
            nsub = _widths.pq_nsub(DIM, int(plq["dsub"]))
            quant_prov["pq"] = {
                "pq_version": PQ_VERSION,
                "dsub": int(plq["dsub"]),
                "ncodes": int(plq["ncodes"]),
                "nsub": nsub,
                "lut_bytes": _widths.pq_lut_bytes(
                    int(qb_prov.shape[0]), DIM, dsub=int(plq["dsub"]),
                    ncodes=int(plq["ncodes"])),
                "bound_max": float(np.max(eps)),
                "queries": int(qb_prov.shape[0]),
            }
        except Exception as e:  # noqa: BLE001 — provenance must not kill the line
            quant_prov["quant_bound_error"] = f"{type(e).__name__}: {e}"
            quant_prov.setdefault("pq", {})["error"] = (
                f"{type(e).__name__}: {e}")
    line = {
        "metric": f"knn_qps_{CONFIG}_n{N}_d{DIM}_k{K}",
        "value": qps,
        "unit": "queries/s",
        "vs_baseline": round(qps / cpu_qps_r, 2) if cpu_qps_r else None,
        "mode": best,
        "device_phase_qps": dev_qps,
        # the variable-batch-size traffic numbers (serving mode): hoisted
        # so the sustained rate + tail latency are readable without
        # digging into the selectors table
        **({
            "serving_sustained_qps": results["serving"].get("sustained_qps"),
            "serving_latency_ms": results["serving"].get("latency_ms"),
            # rides top-level only when measured (KNN_BENCH_OBS_OVERHEAD):
            # the refresher curates it with the line, stale-guard and all
            **({"obs_overhead_pct":
                results["serving"]["obs_overhead_pct"]}
               if "obs_overhead_pct" in results["serving"] else {}),
        } if results.get("serving", {}).get("sustained_qps") else {}),
        # the measured latency-vs-throughput knee (opt-in knee mode):
        # the block rides the line; knee_qps is hoisted by the
        # catalog-driven loop below
        **({"loadgen_knee": results["knee"]["loadgen_knee"]}
           if results.get("knee", {}).get("loadgen_knee") else {}),
        # the mixed read+write traffic proof (opt-in mutation mode):
        # block on the line, admitted p99 hoisted below
        **({"mutation": results["mutation"]["mutation"]}
           if results.get("mutation", {}).get("mutation") else {}),
        # the probed-tier tradeoff (opt-in ivf mode): block on the
        # line; ivf_qps + recall hoist via the catalog loop below
        **({"ivf": results["ivf"]["ivf"]}
           if results.get("ivf", {}).get("ivf") else {}),
        # the multi-host topology measurement (opt-in multihost mode):
        # block + the mode entry's own qps (not a block field); the
        # host-tier sweep count hoists below
        **({
            "multihost": results["multihost"]["multihost"],
            "multihost_qps": results["multihost"].get("qps_mean"),
        } if results.get("multihost", {}).get("multihost") else {}),
        # the bulk kNN-join measurement (opt-in join mode): block on
        # the line; rows_per_s hoists below as join_rows_per_s
        **({"join": results["join"]["join"]}
           if results.get("join", {}).get("join") else {}),
        # the shadow-audit quality ledger (opt-in quality mode): block
        # on the line; audit_recall_at_k hoists via the catalog loop
        **({"quality": results["quality"]["quality"]}
           if results.get("quality", {}).get("quality") else {}),
        **(gate or {}),
        "recall_at_k": results[best].get("recall_at_k"),
        **recall_flag,
        "compute_dtype": DTYPE,
        "metric_fn": metric_label,
        "runs": RUNS,
        "qps_std": results[best]["qps_std"],
        "mfu": results[best]["mfu"],
        # explicit null-MFU provenance (unknown device kind vs cpu
        # backend) so baseline curation can key on MFU where it exists
        **({"mfu_reason": mfu_reason} if mfu_reason else {}),
        "peak_flops_assumed": peak,
        **rl_fields,
        "selectors": results,
        "cpu_baseline_qps": cpu_qps_r,
        "cpu_baseline_cached": "cpu_baseline_cache_file" in cpu_note,
        **cpu_note,
        "cpu_queries": CPU_QUERIES,
        "cpu_per_query_s": round(cpu_per_q_s, 4) if cpu_per_q_s else None,
        "devices": len(mesh.devices.ravel()),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "backend": backend,
        # the winning mode's actual batch: the pallas path runs ONE
        # full-size batch (sweep_certified passes batch_size=None)
        "batch": NQ if best == "certified_pallas" else BATCH,
        "train_tile": tile,
        # the EFFECTIVE pallas/approx tuning knobs, so a curated artifact
        # line is reproducible from the line itself (ADVICE r2+r3); the
        # tuning block records where each run's knobs came from
        # (persisted autotuner winner vs defaults vs env overrides)
        "pallas_knobs": {**KNOBS, "batch": PALLAS_BATCH, "margin": MARGIN},
        **quant_prov,
        "tuning": TUNE_INFO,
        "approx_knobs": {"recall_target": APPROX_RT,
                         "margin": APPROX_MARGIN},
    }
    # table-driven hoists over the artifact-schema catalog
    # (knn_tpu.analysis.artifacts): every cataloged block riding this
    # line contributes its declared top-level keys — roofline_pct/
    # bound_class/roofline_estimated off the winning mode's roofline
    # block, model_residual_pct off an applied calibration overlay,
    # knee_qps, mutation_admitted_p99_ms, hosttier_sweeps,
    # join_rows_per_s — so the
    # sentinel's curated-field baselines and the artifact refresher
    # read them flat.  One loop instead of one stanza per block; a new
    # bench block hoists by declaring, not by editing this file.
    from knn_tpu.analysis.artifacts import apply_scope_hoists

    apply_scope_hoists(line, scope="bench")
    # perf-regression sentinel verdict (knn_tpu.obs.sentinel): this
    # line judged against the robust baseline of its own history —
    # advisory on the line itself (check_tier1 --strict is the gate);
    # jax-free and failure-proof, it can never break the one-JSON-line
    # contract
    try:
        from knn_tpu.obs import sentinel as _sentinel

        line["sentinel"] = _sentinel.verdict_for_line(
            line, repo_dir=os.path.dirname(os.path.abspath(__file__)))
    except Exception as e:  # noqa: BLE001 — verdict must not kill the line
        line["sentinel"] = {"verdict": "error",
                            "error": f"{type(e).__name__}: {e}"}
    _emit(line)
    # the line is out; now the exit code says whether to believe it: a
    # requested mode that failed, or a compiled soundness gate that did
    # not come back green, is a failed run
    failed = sorted(m for m, e in results.items()
                    if isinstance(e, dict) and "error" in e)
    if gate is not None and gate.get("pallas_gate_ok") is not True:
        failed.append("soundness_gate")
    if failed:
        print(f"bench.py: FAILED {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the driver needs one JSON line, always
        _fail("run", f"{type(e).__name__}: {e}",
              tb=traceback.format_exc(limit=3).splitlines()[-3:])
