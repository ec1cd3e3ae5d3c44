"""The env-switch catalog — ONE jax-free home for every ``KNN_TPU_*`` /
``KNN_BENCH_*`` environment switch the repo reads.

The metric catalog (knn_tpu.obs.names) proved the pattern: declare every
name centrally, lint source/docs/tests against the declaration, and an
undeclared name can never ship half-wired.  Switches had no such home —
PR 9 left ~65 switch literals scattered over bench/serving/obs/tuning
with only 13 isolated by ``tests/conftest.py``, so an ambient developer
shell could silently steer most of the suite.  This catalog closes
that: every switch is declared here with its consumer, kind, and doc
location, ``tests/conftest.py`` GENERATES its isolation list from
:func:`isolation_names` (never hand-listed again), and the
``switch-lockstep`` checker (knn_tpu.analysis.check_switches) enforces

1. every switch-shaped string literal in source is declared here (or
   is a declared family prefix),
2. every declared switch appears in the docs (``docs/*.md`` or
   ``README.md``),
3. every declared switch is actually consumed by source (no phantom
   rows; ``reserved`` families exempt),
4. ``tests/conftest.py`` derives its isolation from this catalog.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

#: the shape every switch name (and family prefix) must have; the
#: checker also uses it to find switch-shaped literals in source
SWITCH_RE = re.compile(r"^KNN_(TPU|BENCH)_[A-Z0-9_]*$")


@dataclasses.dataclass(frozen=True)
class Switch:
    """One declared environment switch.

    ``isolate=True`` (the default) means an ambient value steers
    behavior tests assume defaulted, so conftest must scrub it from the
    environment before the suite runs.  ``family=True`` declares a
    PREFIX (name ends with ``_``): source may hold the prefix literal
    (``env.startswith(...)`` scans) and conftest scrubs every ambient
    variable under it.  ``reserved=True`` exempts a family from the
    must-be-consumed check (namespace held for isolation only)."""

    name: str
    kind: str  # "flag" | "int" | "float" | "str" | "path" | "spec"
    consumer: str  # module that reads it
    doc: str  # the doc file its row lives in
    description: str
    isolate: bool = True
    family: bool = False
    reserved: bool = False


def _s(name, kind, consumer, doc, description, **kw) -> Switch:
    return Switch(name, kind, consumer, doc, description, **kw)


#: every declared switch, grouped by owner subsystem.  Descriptions are
#: one-liners; the doc file carries the full story.
_OBS = "docs/OBSERVABILITY.md"
_PERF = "docs/PERF.md"
_SERVING = "docs/serving.md"
_INDEX = "docs/INDEX.md"

SWITCHES: Tuple[Switch, ...] = (
    # --- root namespaces (prefix scans + conftest scrubbing) -----------
    _s("KNN_TPU_", "family", "knn_tpu/obs/blackbox.py", _OBS,
       "Root library-switch namespace: the flight recorder captures "
       "every member into postmortem bundles, and conftest scrubs any "
       "ambient member before the suite runs.", family=True,
       reserved=True),
    _s("KNN_BENCH_", "family", "bench.py", _PERF,
       "Root bench-switch namespace (same capture/scrub contract).",
       family=True, reserved=True),
    # --- telemetry / obs (knn_tpu.obs) ---------------------------------
    _s("KNN_TPU_OBS", "flag", "knn_tpu/obs/registry.py", _OBS,
       "0/false/off disables the telemetry subsystem (default on)."),
    _s("KNN_TPU_OBS_LOG", "path", "knn_tpu/obs/trace.py", _OBS,
       "JSONL sink for structured events (spans, alerts)."),
    _s("KNN_TPU_OBS_LOG_MAX_BYTES", "int", "knn_tpu/obs/trace.py", _OBS,
       "Rotation cap for the JSONL sink (default 64 MiB)."),
    _s("KNN_TPU_SLO_CONFIG", "path", "knn_tpu/obs/slo.py", _OBS,
       "JSON objective list replacing the default SLOs."),
    _s("KNN_TPU_PROFILE_DIR", "path", "knn_tpu/obs/profiler.py", _OBS,
       "Ambient device-trace gate: bench/tune winners capture one "
       "jax.profiler.trace run here."),
    _s("KNN_TPU_POSTMORTEM_DIR", "path", "knn_tpu/obs/blackbox.py", _OBS,
       "Arms the flight recorder: one postmortem bundle per "
       "edge-triggered SLO breach."),
    _s("KNN_TPU_POSTMORTEM_KEEP", "int", "knn_tpu/obs/blackbox.py", _OBS,
       "Postmortem bundle retention cap (default 8)."),
    _s("KNN_TPU_OBS_EXEMPLAR_CAP", "int", "knn_tpu/obs/registry.py",
       _OBS, "Worst-recent exemplars retained per histogram series "
       "(default 8; 0 disables retention)."),
    _s("KNN_TPU_OBS_EXEMPLAR_AGE_S", "float", "knn_tpu/obs/registry.py",
       _OBS, "Exemplar age-out horizon in seconds (default 600)."),
    # --- fleet observability plane (knn_tpu.obs.fleet) -----------------
    _s("KNN_TPU_FLEET_MEMBERS", "spec", "knn_tpu/obs/fleet.py", _OBS,
       "Comma/space-separated host:port list of fleet member metric "
       "endpoints the aggregator collects /metrics.json + /statusz "
       "from (/fleetz, cli fleet); unset = fleet plane unconfigured."),
    _s("KNN_TPU_FLEET_STALE_S", "float", "knn_tpu/obs/fleet.py", _OBS,
       "Staleness refusal threshold (seconds, default 120): a member "
       "snapshot older than the newest by more than this is refused "
       "as a different collection round and listed loudly under "
       "unreachable instead of silently understating the merge."),
    # --- shadow audit sampler (knn_tpu.obs.audit) ----------------------
    _s("KNN_TPU_AUDIT_RATE", "float", "knn_tpu/obs/audit.py", _OBS,
       "Fraction of live requests the shadow audit sampler replays "
       "against the f64 exact oracle, selected deterministically by "
       "trace-id hash (unset/0 = off; KNN_TPU_OBS=0 pins it off)."),
    _s("KNN_TPU_AUDIT_BUDGET_ROWS_S", "float", "knn_tpu/obs/audit.py",
       _OBS, "Hard oracle row budget for audit replays (rows/second "
       "token bucket, default 5e6); over-budget records are dropped "
       "and counted."),
    # --- measured-term calibration (knn_tpu.obs.calibrate) -------------
    _s("KNN_TPU_CALIBRATION", "path", "knn_tpu/obs/calibrate.py", _OBS,
       "Calibration store JSON: per-term roofline scale factors "
       "reconciled from measured device time (atomic writes, "
       "model-version-token keys); unset = analytic model only."),
    # --- measured-ceiling campaign (knn_tpu.campaign) ------------------
    _s("KNN_TPU_CAMPAIGN_", "family", "knn_tpu/campaign.py", _PERF,
       "Measured-ceiling campaign knob family (cli campaign); "
       "namespace scrubbed by conftest.", family=True, reserved=True),
    _s("KNN_TPU_CAMPAIGN_DIR", "path", "knn_tpu/campaign.py", _PERF,
       "Campaign artifact directory (one validated JSONL per arm; "
       "default artifacts/campaign)."),
    _s("KNN_TPU_CAMPAIGN_ARMS", "spec", "knn_tpu/campaign.py", _PERF,
       "Comma list of campaign arms to run (bf16x3_tiled, "
       "bf16x3_streaming, int8_streaming, int8_fused)."),
    _s("KNN_TPU_CAMPAIGN_ROUND", "int", "knn_tpu/campaign.py", _PERF,
       "Measurement-round stamp carried into campaign artifact "
       "provenance."),
    # --- tuning (knn_tpu.tuning) ---------------------------------------
    _s("KNN_TPU_TUNE_CACHE", "path", "knn_tpu/tuning/cache.py", _PERF,
       "Autotuner winner-cache file (default "
       "~/.cache/knn_tpu/autotune.json)."),
    _s("KNN_TPU_TUNE_PRUNE", "float", "knn_tpu/tuning/autotune.py", _OBS,
       "Roofline-model candidate-pruning fraction in (0, 1]; unset = "
       "exhaustive search."),
    # --- multi-host merge tree (knn_tpu.parallel.crossover) ------------
    _s("KNN_TPU_MERGE", "str", "knn_tpu/parallel/crossover.py", _PERF,
       "Override the measured ring/allgather crossover for the "
       "flat / per-host ICI merge level (explicit caller arg still "
       "wins; malformed values raise)."),
    _s("KNN_TPU_DCN_MERGE", "str", "knn_tpu/parallel/crossover.py",
       _PERF, "Same override for the cross-host DCN merge level of "
       "hierarchical placements."),
    # --- host-RAM shard tier (knn_tpu.parallel.sharded) ----------------
    _s("KNN_TPU_HOSTTIER_BUDGET_BYTES", "int",
       "knn_tpu/parallel/sharded.py", _PERF,
       "Per-host HBM byte budget: a corpus placing past it serves "
       "from host RAM, streamed segment-by-segment (unset = "
       "unbounded, everything resident)."),
    _s("KNN_TPU_HOSTTIER_DEPTH", "int", "knn_tpu/parallel/sharded.py",
       _PERF, "Bounded in-flight sweep depth of the host-RAM tier's "
       "dispatch-ahead stream (default 2)."),
    # --- PQ compressed tier (knn_tpu.parallel.sharded) -----------------
    _s("KNN_TPU_PQ_DSUB", "int", "knn_tpu/parallel/sharded.py", _PERF,
       "Dims per PQ subspace for the precision=\"pq\" placement "
       "(default 4); row code bytes = ceil(dim / dsub)."),
    _s("KNN_TPU_PQ_NCODES", "int", "knn_tpu/parallel/sharded.py",
       _PERF, "Codebook size per PQ subspace (default 256, one uint8 "
       "code); larger books shrink the certified bound but widen the "
       "per-query LUT."),
    # --- mutable index (knn_tpu.index.mutable) -------------------------
    _s("KNN_TPU_DELTA_MIN_ROWS", "int", "knn_tpu/index/mutable.py",
       _INDEX, "Smallest delta-tail capacity ladder rung (rows, "
       "default 256); the tail re-places within a rung without "
       "recompiling."),
    _s("KNN_TPU_DELTA_MAX_ROWS", "int", "knn_tpu/index/mutable.py",
       _INDEX, "Top delta-tail ladder rung: insert refuses loudly past "
       "it until compaction folds the tail in (default 65536)."),
    _s("KNN_TPU_DELTA_RESERVE", "int", "knn_tpu/index/mutable.py",
       _INDEX, "Certify-widening reserve: searches select k + reserve "
       "so up to this many tombstones can be masked exactly "
       "(default 32); delete refuses past it."),
    _s("KNN_TPU_COMPACT_TAIL_ROWS", "int", "knn_tpu/index/mutable.py",
       _INDEX, "Auto-compaction threshold on delta-tail rows (unset = "
       "manual/interval compaction only)."),
    _s("KNN_TPU_COMPACT_TOMBSTONES", "int", "knn_tpu/index/mutable.py",
       _INDEX, "Auto-compaction threshold on pending tombstones "
       "(unset = manual/interval compaction only)."),
    _s("KNN_TPU_COMPACT_INTERVAL_S", "float",
       "knn_tpu/index/mutable.py", _INDEX,
       "Background compactor period: fold pending writes in every "
       "this-many seconds even below the thresholds (unset = "
       "threshold-triggered only)."),
    # --- IVF tier (knn_tpu.ivf.index) ----------------------------------
    _s("KNN_TPU_IVF_", "family", "knn_tpu/ivf/index.py", _PERF,
       "IVF-tier knob family (coarse quantizer + probe defaults); any "
       "ambient member is scrubbed by conftest.", family=True),
    _s("KNN_TPU_IVF_NCENTROIDS", "int", "knn_tpu/ivf/index.py", _PERF,
       "Default k-means list count of an IVFIndex (unset = "
       "round(sqrt(n)))."),
    _s("KNN_TPU_IVF_NPROBE", "int", "knn_tpu/ivf/index.py", _PERF,
       "Default probed-list count per query (unset = ncentroids/4); "
       "nprobe = ncentroids reproduces exact brute force bitwise."),
    _s("KNN_TPU_IVF_TRAIN_ITERS", "int", "knn_tpu/ivf/index.py", _PERF,
       "Lloyd iterations of the seeded coarse-quantizer training "
       "(default 5)."),
    _s("KNN_TPU_IVF_SEED", "int", "knn_tpu/ivf/index.py", _PERF,
       "Deterministic k-means init seed (default 0); same seed + data "
       "=> same placement."),
    # --- bulk kNN-join engine (knn_tpu.join) ---------------------------
    _s("KNN_TPU_JOIN_", "family", "knn_tpu/join/engine.py", _PERF,
       "Bulk kNN-join knob family (superblock sizing + dispatch "
       "depth); any ambient member is scrubbed by conftest.",
       family=True),
    _s("KNN_TPU_JOIN_SUPERBLOCK", "int", "knn_tpu/join/engine.py",
       _PERF, "Query superblock rows of knn_join (unset = the h2d "
       "staging-budget model, else 4096); explicit call args win."),
    _s("KNN_TPU_JOIN_DEPTH", "int", "knn_tpu/join/engine.py", _PERF,
       "Bounded dispatch-ahead depth of the double-buffered query "
       "stream (default 2; 1 disables the overlap)."),
    _s("KNN_TPU_JOIN_QUERY_BUDGET_BYTES", "int",
       "knn_tpu/join/engine.py", _PERF,
       "Host->device staging budget the superblock resolution sizes "
       "against (analysis.hbm.plan_superblocks)."),
    # --- admission control (knn_tpu.serving.admission) -----------------
    _s("KNN_TPU_ADMISSION_", "family", "knn_tpu/serving/admission.py",
       _SERVING, "Admission-control knob family (ANY set member is an "
       "opt-in; a typo'd member raises).", family=True),
    _s("KNN_TPU_ADMISSION_MAX_DEPTH", "int",
       "knn_tpu/serving/admission.py", _SERVING,
       "Bounded outstanding-work depth (explicit rejection past it)."),
    _s("KNN_TPU_ADMISSION_SHED", "flag", "knn_tpu/serving/admission.py",
       _SERVING, "Deadline-aware load shedding at submit and dispatch."),
    _s("KNN_TPU_ADMISSION_DEFAULT_DEADLINE_MS", "float",
       "knn_tpu/serving/admission.py", _SERVING,
       "Deadline applied to requests that don't carry one."),
    _s("KNN_TPU_ADMISSION_QUOTAS", "spec",
       "knn_tpu/serving/admission.py", _SERVING,
       "Per-tenant token-bucket quotas, tenant:rate[:burst],..."),
    _s("KNN_TPU_ADMISSION_PRIORITIES", "spec",
       "knn_tpu/serving/admission.py", _SERVING,
       "Per-tenant dispatch priorities, tenant:level,..."),
    _s("KNN_TPU_ADMISSION_AGING_MS", "float",
       "knn_tpu/serving/admission.py", _SERVING,
       "Priority aging constant (starvation safety)."),
    # --- loadgen (namespace reserved; all config is flags/args today) --
    _s("KNN_TPU_LOADGEN_", "family", "knn_tpu/loadgen/", _SERVING,
       "Reserved loadgen namespace — scrubbed by conftest so future "
       "knobs are isolated from day one.", family=True, reserved=True),
    # --- bench.py: problem shape & run shape ---------------------------
    _s("KNN_BENCH_CONFIG", "str", "bench.py", _PERF,
       "Named benchmark config: sift1m (default) | glove | gist1m."),
    _s("KNN_BENCH_MODES", "spec", "bench.py", _PERF,
       "Comma list of modes to run (exact, certified_approx, "
       "certified_pallas, serving, knee, multihost, mutation, ivf, "
       "join)."),
    _s("KNN_BENCH_MULTIHOST_HOSTS", "int", "bench.py", _PERF,
       "Host-axis size of the multihost mode's hierarchical mesh "
       "(default 2)."),
    _s("KNN_BENCH_MULTIHOST_SWEEPS", "int", "bench.py", _PERF,
       "Target host-RAM tier sweep count of the multihost mode's "
       "budget-forced stream (default 4)."),
    _s("KNN_BENCH_RUNS", "int", "bench.py", _PERF,
       "Timed repetitions per mode (default 5)."),
    _s("KNN_BENCH_N", "int", "bench.py", _PERF, "Database rows."),
    _s("KNN_BENCH_DIM", "int", "bench.py", _PERF, "Feature dim."),
    _s("KNN_BENCH_K", "int", "bench.py", _PERF, "Neighbor count."),
    _s("KNN_BENCH_METRIC", "str", "bench.py", _PERF,
       "Distance metric of the synthetic config."),
    _s("KNN_BENCH_NQ", "int", "bench.py", _PERF, "Query count."),
    _s("KNN_BENCH_BATCH", "int", "bench.py", _PERF,
       "Queries per device step."),
    _s("KNN_BENCH_TILE", "int", "bench.py", _PERF,
       "HBM train-tile rows for the streamed distance matrix."),
    _s("KNN_BENCH_CPU_QUERIES", "int", "bench.py", _PERF,
       "Query count of the CPU-oracle pass."),
    _s("KNN_BENCH_MARGIN", "int", "bench.py", _PERF,
       "Certified-mode candidate margin."),
    _s("KNN_BENCH_DTYPE", "str", "bench.py", _PERF,
       "Placement compute dtype (bfloat16 | float32)."),
    # --- bench.py: environment/bring-up --------------------------------
    _s("KNN_BENCH_PLATFORM", "str", "bench.py", _PERF,
       "The JAX platform to run on; unset means TPU, and a run that "
       "finds another backend fails (cpu is what the CPU tests ask "
       "for)."),
    _s("KNN_BENCH_PEAK_FLOPS", "float", "bench.py", _PERF,
       "Override the per-chip peak FLOP/s used for MFU."),
    _s("KNN_BENCH_CPU_CACHE", "flag", "bench.py", _PERF,
       "0 forces a fresh CPU-oracle measurement instead of the cached "
       "one."),
    _s("KNN_BENCH_GATE", "flag", "bench.py", _PERF,
       "0 skips the exactness gate on huge dims."),
    _s("KNN_BENCH_VERBOSE", "flag", "bench.py", _PERF,
       "1 prints stage progress on stderr."),
    _s("KNN_BENCH_TRACE", "path", "bench.py", _PERF,
       "Write a jax.profiler trace of one extra per-mode run here."),
    _s("KNN_BENCH_TUNE_CACHE", "path", "bench.py", _PERF,
       "Autotuner cache the bench resolves knobs through."),
    _s("KNN_BENCH_OBS_OVERHEAD", "flag", "bench.py", _PERF,
       "1 A/Bs the serving sweep with telemetry off/on and emits "
       "obs_overhead_pct."),
    # --- bench.py: XLA-selector knobs ----------------------------------
    _s("KNN_BENCH_APPROX_RT", "float", "bench.py", _PERF,
       "ApproxTopK recall target of the certified_approx mode."),
    _s("KNN_BENCH_APPROX_MARGIN", "int", "bench.py", _PERF,
       "Margin override of the certified_approx mode."),
    # --- bench.py: pallas knob overrides (unset = tuned/default) -------
    _s("KNN_BENCH_PALLAS_", "family", "bench.py", _PERF,
       "Pallas knob-override family; unset members resolve through the "
       "autotuner cache.", family=True),
    _s("KNN_BENCH_PALLAS_PRECISION", "str", "bench.py", _PERF,
       "Kernel matmul precision (bf16x3 | bf16x3f | int8 | highest)."),
    _s("KNN_BENCH_PALLAS_TILE", "int", "bench.py", _PERF,
       "Kernel db tile rows (tile_n)."),
    _s("KNN_BENCH_PALLAS_SURVIVORS", "int", "bench.py", _PERF,
       "Per-bin survivor count."),
    _s("KNN_BENCH_PALLAS_BLOCK_Q", "int", "bench.py", _PERF,
       "Query block rows (block_q)."),
    _s("KNN_BENCH_PALLAS_FINAL", "str", "bench.py", _PERF,
       "Final select: exact | approx."),
    _s("KNN_BENCH_PALLAS_FINAL_RT", "float", "bench.py", _PERF,
       "Approx final-select recall target."),
    _s("KNN_BENCH_PALLAS_GRID", "str", "bench.py", _PERF,
       "Grid order: query_major | db_major."),
    _s("KNN_BENCH_PALLAS_KERNEL", "str", "bench.py", _PERF,
       "Db-streaming strategy: tiled | streaming | fused."),
    _s("KNN_BENCH_PALLAS_BATCH", "int", "bench.py", _PERF,
       "Queries per kernel launch in the pallas mode."),
    # --- bench.py: serving sweep ---------------------------------------
    _s("KNN_BENCH_SERVING_REQUESTS", "int", "bench.py", _PERF,
       "Replayed request count of the serving mode."),
    _s("KNN_BENCH_SERVING_DEPTH", "int", "bench.py", _PERF,
       "Dispatch-ahead depth of the serving mode."),
    _s("KNN_BENCH_SERVING_MIN_BUCKET", "int", "bench.py", _PERF,
       "Smallest bucket rung of the serving mode's ladder."),
    # --- bench.py: mutation sweep (opt-in mutation mode) ---------------
    _s("KNN_BENCH_MUTATION_", "family", "bench.py", _INDEX,
       "Mutation-sweep knob family of the opt-in mutation mode.",
       family=True),
    _s("KNN_BENCH_MUTATION_RATE", "float", "bench.py", _INDEX,
       "Offered request rate (req/s) of the mixed read+write "
       "scenario."),
    _s("KNN_BENCH_MUTATION_SECONDS", "float", "bench.py", _INDEX,
       "Duration of the mixed-traffic run."),
    _s("KNN_BENCH_MUTATION_WRITE_FRACTION", "float", "bench.py",
       _INDEX, "Fraction of scheduled requests that are writes "
       "(split between inserts and deletes)."),
    # --- bench.py: knee sweep ------------------------------------------
    _s("KNN_BENCH_KNEE_", "family", "bench.py", _PERF,
       "Knee-sweep knob family of the opt-in knee mode.", family=True),
    _s("KNN_BENCH_KNEE_RATES", "spec", "bench.py", _PERF,
       "Offered-rate ladder, comma-separated q/s."),
    _s("KNN_BENCH_KNEE_STEP_S", "float", "bench.py", _PERF,
       "Seconds per rate step."),
    _s("KNN_BENCH_KNEE_SLO_MS", "float", "bench.py", _PERF,
       "Admitted-p99 bound defining the knee."),
    _s("KNN_BENCH_KNEE_TENANTS", "spec", "bench.py", _PERF,
       "Tenant mix spec, name[:weight[:priority]],..."),
    _s("KNN_BENCH_KNEE_SEED", "int", "bench.py", _PERF,
       "Workload-schedule seed."),
    # --- bench.py: bulk kNN-join sweep (opt-in join mode) --------------
    _s("KNN_BENCH_JOIN_", "family", "bench.py", _PERF,
       "Join-sweep knob family of the opt-in join mode.", family=True),
    _s("KNN_BENCH_JOIN_ROWS", "int", "bench.py", _PERF,
       "Query rows of the join line's host-resident set A (0 = sized "
       "from NQ/BATCH)."),
    _s("KNN_BENCH_JOIN_SUPERBLOCK", "int", "bench.py", _PERF,
       "Superblock rows of the join sweep (0 = the engine's "
       "resolution ladder)."),
    _s("KNN_BENCH_JOIN_DEPTH", "int", "bench.py", _PERF,
       "Dispatch-ahead depth of the join sweep (default 2)."),
    # --- bench.py: shadow-audit replay (opt-in quality mode) -----------
    _s("KNN_BENCH_QUALITY_REQUESTS", "int", "bench.py", _PERF,
       "Serving requests of the opt-in quality mode's shadow-audit "
       "replay (default 8; each pays one full f64 oracle scan)."),
)

#: name -> Switch for exact lookups
BY_NAME: Dict[str, Switch] = {s.name: s for s in SWITCHES}

#: declared family prefixes (names ending in ``_``)
FAMILY_PREFIXES: Tuple[str, ...] = tuple(
    s.name for s in SWITCHES if s.family)


def _validate() -> None:
    for s in SWITCHES:
        if not SWITCH_RE.match(s.name):
            raise ValueError(f"switch {s.name!r} does not match "
                             f"{SWITCH_RE.pattern}")
        if s.family != s.name.endswith("_"):
            raise ValueError(
                f"switch {s.name!r}: family declarations (and only "
                f"those) must end with '_'")
    if len(BY_NAME) != len(SWITCHES):
        raise ValueError("duplicate switch declarations")


_validate()


def lookup(token: str) -> Optional[Switch]:
    """The declaration covering ``token``: an exact catalog row, or the
    family row when ``token`` IS a declared prefix.  A concrete member
    of a family must still be declared individually — the family only
    legitimizes prefix literals (startswith scans) and conftest
    scrubbing, never an undeclared concrete switch."""
    hit = BY_NAME.get(token)
    if hit is not None:
        return hit
    if token.endswith("_") and token in FAMILY_PREFIXES:
        return BY_NAME[token]
    return None


def isolation_names(environ: Optional[Mapping[str, str]] = None
                    ) -> List[str]:
    """The environment-variable names ``tests/conftest.py`` must scrub
    before the suite runs: every concrete cataloged switch with
    ``isolate=True``, plus any AMBIENT variable (from ``environ``)
    under an isolated family prefix — so a developer shell's
    ``KNN_BENCH_PALLAS_WHATEVER=...`` is scrubbed even before it gets
    its own catalog row.  Generated, never hand-listed: a new catalog
    row is isolated on the next test run with zero conftest edits."""
    names = [s.name for s in SWITCHES if s.isolate and not s.family]
    if environ:
        prefixes = tuple(s.name for s in SWITCHES
                         if s.family and s.isolate)
        names.extend(k for k in environ
                     if k.startswith(prefixes) and k not in names)
    return sorted(set(names))


def tokens_in_source(text: str) -> Iterable[str]:
    """Every switch-shaped token in ``text`` (used by the checker over
    docs; source literals go through the AST instead)."""
    return re.findall(r"\bKNN_(?:TPU|BENCH)_[A-Z0-9_]*\b", text)
