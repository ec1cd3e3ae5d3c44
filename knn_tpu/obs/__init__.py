"""knn_tpu.obs — the unified telemetry subsystem.

One registry, one event log, two exporters; everything else in the
repo (serving, certified search, tuning, pipeline phases, JAX compiles)
writes through here instead of keeping private ad-hoc counters:

- **Metrics registry** (:mod:`knn_tpu.obs.registry`): process-wide,
  thread-safe counters / gauges / bounded histograms with p50/p95/p99,
  validated against the catalog (:mod:`knn_tpu.obs.names`).  Disabled
  mode (``KNN_TPU_OBS=0``) hands out one shared no-op instrument —
  near-zero cost, bitwise-identical results.
- **Spans + events** (:mod:`knn_tpu.obs.trace`): request-scoped trace
  ids minted at submit and propagated through micro-batching; a bounded
  in-memory event ring plus an optional JSONL sink
  (``KNN_TPU_OBS_LOG``).  A scoped span is also a ``knn.<span>``
  profiler annotation, on the clock of a device trace.  A bulk call
  keeps one account of the device programs it launched and records it
  once when it ends (``trace.CallAccount``).
- **Exporters** (:mod:`knn_tpu.obs.export`): Prometheus text served
  from a stdlib-HTTP endpoint (``--metrics-port``), an atomic JSON
  snapshot writer, and ``python -m knn_tpu.cli metrics`` to read
  either.
- **Compile hook** (:mod:`knn_tpu.obs.jax_hooks`): every trace,
  lowering, backend compile and persistent-cache lookup's count +
  seconds via ``jax.monitoring``, and the record of a device program's
  first call (which one traced, compiled or loaded, and for how long).
- **Tail forensics** (:mod:`knn_tpu.obs.waterfall`): per-request
  latency waterfalls reconstructed from the span stream, critical-path
  attribution at p50 vs p99 per tenant/bucket, histogram->trace
  exemplars, and the slowest-requests tables.
- **Flight recorder** (:mod:`knn_tpu.obs.blackbox`): one atomic,
  retention-capped postmortem bundle per edge-triggered SLO breach
  (``KNN_TPU_POSTMORTEM_DIR``), readable offline by ``cli waterfall``.
- **Shadow audit sampler** (:mod:`knn_tpu.obs.audit`): off-path exact
  replay of a deterministic sample of served requests against the f64
  oracle (``KNN_TPU_AUDIT_RATE``), emitting per-tenant recall@k,
  rank-displacement, and distance-error telemetry under a hard row
  budget.
- **Drift detection** (:mod:`knn_tpu.obs.drift`): streaming query
  distribution sketches (norms, centroid assignments) scored by PSI
  against train-time baselines, plus index-health gauges.
- **Fleet plane** (:mod:`knn_tpu.obs.fleet`): N processes' telemetry
  merged into one cross-host report — counters summed, gauges kept
  per-host with min/max/argmax, quantiles from element-wise-summed
  histogram buckets (never averaged percentiles), stitched multi-host
  waterfalls, fleet SLO edges with member-embedding postmortems
  (``KNN_TPU_FLEET_MEMBERS``, ``/fleetz``, ``cli fleet``); every
  payload stamped with the process identity (:mod:`knn_tpu.obs.ident`).

The package itself imports no JAX (jax_hooks defers it), so the CLI's
flag parsing and the lint script stay import-light.

Metric catalog, span lifecycle, and overhead numbers:
``docs/OBSERVABILITY.md``.
"""

from knn_tpu.obs import (  # noqa: F401
    audit,
    blackbox,
    drift,
    fleet,
    health,
    ident,
    names,
    slo,
    waterfall,
)
from knn_tpu.obs.export import (  # noqa: F401
    compact_snapshot,
    prometheus_text,
    start_metrics_server,
    write_json_snapshot,
)
from knn_tpu.obs.jax_hooks import install_compile_hook  # noqa: F401
from knn_tpu.obs.slo import (  # noqa: F401
    SLOEngine,
    Objective,
    get_slo_engine,
    load_objectives,
    reset_slo_engine,
    slo_report,
)
from knn_tpu.obs.registry import (  # noqa: F401
    NOOP,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    enabled,
    gauge,
    get_registry,
    histogram,
    reset,
    snapshot,
)
from knn_tpu.obs.trace import (  # noqa: F401
    EventLog,
    current_span,
    emit_event,
    get_event_log,
    new_trace_id,
    record_span,
    reset_event_log,
    span,
)

__all__ = [
    "NOOP", "Counter", "EventLog", "Gauge", "Histogram",
    "MetricsRegistry", "Objective", "SLOEngine", "audit", "blackbox",
    "compact_snapshot", "drift",
    "counter", "current_span", "emit_event", "enabled", "fleet", "gauge",
    "get_event_log",
    "get_registry", "get_slo_engine", "health", "histogram", "ident",
    "install_compile_hook", "load_objectives", "names", "new_trace_id",
    "prometheus_text", "record_span", "reset",
    "reset_event_log", "reset_slo_engine", "slo",
    "slo_report", "snapshot", "span", "start_metrics_server",
    "waterfall", "write_json_snapshot",
]
