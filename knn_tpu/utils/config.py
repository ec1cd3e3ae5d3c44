"""Runtime job configuration — the reference's compile-time constant block
(knn_mpi.cpp:108-119; report PDF p.11 §3.2.2) promoted to a real config.

The reference's documented workflow for changing any of these is *edit the
source and recompile* (PDF p.11 §3.3.1); here they are dataclass fields fed
by the CLI (knn_tpu.cli) — SURVEY.md §5 calls this the single biggest
usability delta.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Optional

from knn_tpu.ops.metrics import METRICS

#: Execution backends: JAX/XLA (TPU-native path) and the C++ CPU parity
#: oracle (knn_tpu.native, SURVEY.md §7 step 3).
BACKENDS = ("jax", "native")

#: the kernel matmul precisions (ops.pallas_knn.PRECISIONS is this
#: tuple): each has a certified tolerance model.  ONE home (jax-free,
#: so the CLI can build its --help without importing JAX); cli.py's
#: choices, this module's validation, the kernel's and
#: parallel.sharded's _pallas_setup check all consume it.
CERTIFIED_PRECISIONS = ("bf16x3", "bf16x3f", "highest", "int8", "pq")


@dataclass
class JobConfig:
    """One KNN classification job.

    Field ↔ reference mapping:
      dim          <- ``dim``                 knn_mpi.cpp:108 (None = infer from file)
      k            <- ``K``                   :109
      num_classes  <- ``class_cnt``           :113 (None = infer from labels)
      metric       <- ``Euclidean_distance``  :114 ('l2' true / 'l1' false, plus cosine/dot)
      normalize    <- ``Normalize``           :115
      validation   <- ``Validation``          :116
      train_file / val_file / test_file      :117-119
      output_file  <- the hard-coded ``Test_label.csv``  :390

    Fields with no reference counterpart configure the TPU execution:
    mesh shape (query_shards × db_shards), merge strategy, HBM train tile,
    query batch size, and matmul dtype.
    """

    train_file: str = "mnist_train.csv"
    test_file: str = "mnist_test.csv"
    val_file: Optional[str] = "mnist_validation.csv"
    output_file: str = "Test_label.csv"
    dim: Optional[int] = None
    k: int = 50
    num_classes: Optional[int] = None
    metric: str = "l2"
    normalize: bool = True
    validation: bool = True
    backend: str = "jax"
    # --- TPU execution knobs (no reference counterpart) ---
    query_shards: Optional[int] = None
    db_shards: int = 1
    merge: str = "allgather"
    train_tile: Optional[int] = None
    batch_size: Optional[int] = None
    compute_dtype: Optional[str] = None
    #: "exact" ranks every candidate in float32; "certified" uses a fast
    #: approximate selector + float64 refinement + the count-below
    #: certificate (ops.certified) — exact results, higher throughput at
    #: scale.  Certified supports the l2 and cosine metrics (cosine runs
    #: the certificate on unit vectors; ShardedKNN.search_certified).
    mode: str = "exact"
    #: local-shard selector for certified mode: "approx" | "pallas" | "exact"
    selector: str = "approx"
    #: shape-bucketed serving (knn_tpu.serving): "auto" for the default
    #: geometric ladder, or an explicit comma list like "64,128,256".
    #: Queries route through precompiled per-bucket executables and the
    #: job metrics gain per-bucket compile counts + latency percentiles.
    #: None (default) = direct dispatch, one compile per batch shape.
    serve_buckets: Optional[str] = None
    #: micro-batching deadline (knn_tpu.serving.QueryQueue): how long a
    #: request may wait to be coalesced with others.  Echoed into the
    #: serving metrics; only a concurrent-request queue consults it.
    max_wait_ms: float = 2.0
    #: explicit kernel matmul precision for the certified pallas
    #: selector (CERTIFIED_PRECISIONS): "bf16x3" | "bf16x3f" |
    #: "highest" | "int8"
    #: (the quantized MXU arm — ops.quantize) | "pq" (product-quantized
    #: codes — ops.pq).  None = the library default
    #: (tuning.DEFAULT_KNOBS); the resolved knob set lands in
    #: metrics()["certified_stats"]["pallas_knobs"].
    pallas_precision: Optional[str] = None
    # --- native backend knobs ---
    num_threads: int = 0  # 0 = hardware concurrency

    def __post_init__(self):
        # normalize case ONCE at the boundary: downstream dispatch
        # (ShardedKNN's `metric == "cosine"` placement normalization,
        # selector tables) compares lowercase names
        self.metric = self.metric.lower()
        if self.metric not in METRICS:
            raise ValueError(f"metric {self.metric!r} not in {METRICS}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {self.k}")
        if self.validation and not self.val_file:
            raise ValueError("validation=True requires val_file")
        if self.mode not in ("exact", "certified"):
            raise ValueError(f"mode {self.mode!r} not in ('exact', 'certified')")
        if self.selector not in ("exact", "approx", "pallas"):
            raise ValueError(f"selector {self.selector!r} unknown")
        if self.pallas_precision is not None and \
                self.pallas_precision not in CERTIFIED_PRECISIONS:
            raise ValueError(
                f"pallas_precision {self.pallas_precision!r} not in "
                f"{CERTIFIED_PRECISIONS}")
        if self.mode == "certified" and self.metric not in (
            "l2", "sql2", "euclidean", "cosine"
        ):
            raise ValueError(
                "mode='certified' requires the l2 or cosine metric")
        if self.serve_buckets is not None:
            # dependency-free ladder validation (knn_tpu.serving.buckets
            # imports no jax/numpy), so bad flags fail at parse time
            from knn_tpu.serving.buckets import parse_buckets

            if parse_buckets(self.serve_buckets) is None:
                self.serve_buckets = None  # empty spec = serving off
            if self.serve_buckets is not None and self.mode == "certified":
                raise ValueError(
                    "serve_buckets routes through the exact bucketed "
                    "programs; mode='certified' has its own batching "
                    "(batch_size) and does not compose with it")
            if self.serve_buckets is not None and self.backend != "jax":
                raise ValueError("serve_buckets requires the jax backend")
        if self.max_wait_ms < 0:
            raise ValueError(
                f"max_wait_ms must be >= 0, got {self.max_wait_ms}")

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)

    @classmethod
    def from_json(cls, s: str) -> "JobConfig":
        return cls(**json.loads(s))
