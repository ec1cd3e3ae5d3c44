"""Shape-bucketed serving engine — the query-traffic subsystem.

The batch library (``ShardedKNN``) compiles one SPMD program per exact
query-batch shape and runs strictly synchronously; a realistic stream of
variable-size requests recompiles repeatedly and leaves the device idle
between dispatches.  This package turns it into a throughput engine:

- :mod:`~knn_tpu.serving.buckets` — the geometric bucket ladder that
  bounds the compile cache at O(log(max/min)) executables;
- :mod:`~knn_tpu.serving.engine` — :class:`ServingEngine`: precompiled
  (AOT) per-bucket executables with ``warmup()``, async dispatch-ahead
  handles, trace replay, and full
  compile/dispatch/latency accounting;
- :mod:`~knn_tpu.serving.queue` — :class:`QueryQueue`: dynamic
  micro-batching of concurrent small requests under a max-wait deadline.

Padding is arithmetic-transparent: pad rows are whole zero queries
whose outputs are sliced away, and every query row's result is
independent of its batchmates — bucketed results are bitwise identical
to a direct ``ShardedKNN.search`` of the same placed batch, and
neighbor identity + tie-break order match the unpadded direct call on
every backend (distances additionally match bitwise on TPU, whose MXU
reduction order is batch-shape invariant; see serving.engine).

Admission control (:mod:`~knn_tpu.serving.admission`) layers onto the
queue and is OFF by default: bounded depth with explicit rejection,
deadline-aware load shedding, per-tenant token-bucket quotas, and
starvation-safe aged-priority ordering — the controls the measured
latency-vs-throughput knee (knn_tpu.loadgen) motivates.

Entry points: ``ShardedKNN.search_bucketed()`` for the one-liner,
``ServingEngine`` + ``QueryQueue`` for a long-running service,
``--serve-buckets`` on the CLI.
"""

from knn_tpu.serving.admission import (
    AdmissionConfig,
    AdmissionController,
    AdmissionError,
    DeadlineError,
    QueueFullError,
    QuotaExceededError,
)
from knn_tpu.serving.buckets import (
    DEFAULT_MAX_BUCKET,
    DEFAULT_MIN_BUCKET,
    bucket_for,
    bucket_ladder,
    parse_buckets,
    split_sizes,
)
from knn_tpu.serving.engine import ServingEngine, latency_summary
from knn_tpu.serving.queue import QueryQueue

__all__ = [
    "AdmissionConfig",
    "AdmissionController",
    "AdmissionError",
    "DeadlineError",
    "QueueFullError",
    "QuotaExceededError",
    "DEFAULT_MAX_BUCKET",
    "DEFAULT_MIN_BUCKET",
    "bucket_for",
    "bucket_ladder",
    "parse_buckets",
    "split_sizes",
    "ServingEngine",
    "latency_summary",
    "QueryQueue",
]
