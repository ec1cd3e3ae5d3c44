"""knn_tpu.loadgen — production-shaped load generation, replay, and
knee measurement for the serving stack.

The serving layer (knn_tpu.serving) is fast on closed-loop
microbatches; whether it survives TRAFFIC — open-loop arrivals that do
not wait for completions, bursts, mixed request shapes, multiple
tenants — was unobservable before this package.  Four pieces:

- :mod:`~knn_tpu.loadgen.workload` — deterministic seeded arrival
  processes (Poisson, bursty on/off, JSONL trace replay) over a
  multi-tenant mix spec: same spec, same schedule, every time;
- :mod:`~knn_tpu.loadgen.driver` — the open-loop driver: dedicated
  submitter threads (arrivals never gated by completions) driving a
  ``QueryQueue``-shaped target, every request recorded into a bounded
  result log with an explicit outcome (ok / rejected:* / shed:* /
  error);
- :mod:`~knn_tpu.loadgen.knee` — the stepped-rate sweep that locates
  the latency-vs-throughput knee and emits it as a validated
  artifact block;
- :mod:`~knn_tpu.loadgen.synthetic` — a jax-free single-server target
  with a configured capacity, so the harness itself (and the knee
  detector) is testable without hardware.

The controls the measured knee motivates live in
:mod:`knn_tpu.serving.admission`: bounded queues, deadline-aware
shedding, per-tenant quotas, starvation-safe priorities — shed, don't
collapse.  Entry point: ``python -m knn_tpu.cli loadgen``
(docs/serving.md).

Jax-free by construction (numpy only): generating and replaying load
must not require the accelerator the target owns.
"""

from knn_tpu.loadgen.driver import (  # noqa: F401
    DEFAULT_LOG_CAP,
    ResultLog,
    report,
    run_workload,
)
from knn_tpu.loadgen.knee import (  # noqa: F401
    closed_loop_anchor,
    knee_block,
    knee_sweep,
    rates_around,
    run_step,
    validate_knee_block,
)
from knn_tpu.loadgen.synthetic import SyntheticTarget  # noqa: F401
from knn_tpu.loadgen.workload import (  # noqa: F401
    ARRIVALS,
    Request,
    TenantSpec,
    WorkloadSpec,
    generate,
    load_trace,
    parse_tenants,
    save_trace,
)

__all__ = [
    "ARRIVALS",
    "DEFAULT_LOG_CAP",
    "Request",
    "ResultLog",
    "SyntheticTarget",
    "TenantSpec",
    "WorkloadSpec",
    "closed_loop_anchor",
    "generate",
    "knee_block",
    "knee_sweep",
    "load_trace",
    "parse_tenants",
    "rates_around",
    "report",
    "run_step",
    "run_workload",
    "save_trace",
    "validate_knee_block",
]
