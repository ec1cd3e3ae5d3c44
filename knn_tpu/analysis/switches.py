"""The env-switch catalog — ONE jax-free home for every ``KNN_TPU_*``
environment switch the repo reads.

The metric catalog (knn_tpu.obs.names) proved the pattern: declare every
name centrally, lint source/docs/tests against the declaration, and an
undeclared name can never ship half-wired.  Switches had no such home —
PR 9 left ~65 switch literals scattered over serving/obs/tuning
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
SWITCH_RE = re.compile(r"^KNN_TPU_[A-Z0-9_]*$")


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
    # --- telemetry / obs (knn_tpu.obs) ---------------------------------
    _s("KNN_TPU_OBS", "flag", "knn_tpu/obs/registry.py", _OBS,
       "0/false/off disables the telemetry subsystem (default on)."),
    _s("KNN_TPU_OBS_LOG", "path", "knn_tpu/obs/trace.py", _OBS,
       "JSONL sink for structured events (spans, alerts)."),
    _s("KNN_TPU_OBS_LOG_MAX_BYTES", "int", "knn_tpu/obs/trace.py", _OBS,
       "Rotation cap for the JSONL sink (default 64 MiB)."),
    _s("KNN_TPU_SLO_CONFIG", "path", "knn_tpu/obs/slo.py", _OBS,
       "JSON objective list replacing the default SLOs."),
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
    ``KNN_TPU_IVF_WHATEVER=...`` is scrubbed even before it gets
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
    return re.findall(r"\bKNN_TPU_[A-Z0-9_]*\b", text)
