"""L4 driver: the reference's entire ``main()`` (knn_mpi.cpp:86-399) as a
library function — read CSVs, distribute, transductively normalize, KNN both
query sets, score validation, write ``Test_label.csv``, report time.

Reference flow reproduced (SURVEY.md §1 data-flow):
  ingest        <- rank-specialized CSV readers        knn_mpi.cpp:154-222
  distribute    <- Bcast/Scatter placement             :224-227  (shardings)
  normalize     <- joint extrema + Allreduce + rescale :229-306  (pmin/pmax)
  knn val/test  <- distance/sort/vote per shard        :308-393  (SPMD program)
  score         <- acc_calc on gathered val labels     :342-349
  output        <- Test_label.csv writer               :385-393
  timing        <- barrier-fenced Wtime pair           :133-134,395-398
                   (upgraded to per-phase fences, utils.timing)

Backends: ``jax`` (the TPU-native path, any mesh shape) and ``native`` (the
C++ CPU parity oracle, knn_tpu.native).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Dict, Optional

import numpy as np

from knn_tpu.data.csv_io import read_labeled_csv, read_unlabeled_csv, write_labels
from knn_tpu.utils.config import JobConfig
from knn_tpu.utils.timing import PhaseTimer


@dataclass
class JobResult:
    """Everything the reference prints or writes, plus structured metrics."""

    test_labels: np.ndarray
    val_labels: Optional[np.ndarray]
    val_accuracy: Optional[float]
    phase_times: Dict[str, float]
    total_time: float
    n_train: int
    n_test: int
    n_val: int
    config: JobConfig
    #: ``--mode certified`` observability: how many queries certified exactly
    #: on the fast path vs fell back to the widened re-select (None outside
    #: certified mode).  Keys: "certified", "fallback_queries".
    certified_stats: Optional[Dict[str, int]] = None
    #: ``--serve-buckets`` observability (None outside serving mode): the
    #: bucket ladder, per-bucket compile/dispatch counts, and per-request
    #: latency percentiles (knn_tpu.serving.ServingEngine.stats).
    serving_stats: Optional[dict] = None

    @property
    def queries_per_sec(self) -> float:
        n = self.n_test + self.n_val
        return n / self.total_time if self.total_time > 0 else float("inf")

    def metrics(self) -> dict:
        """Structured per-run JSON — the metrics/observability subsystem the
        reference lacks (SURVEY.md §5: cout only, knn_mpi.cpp:348,398)."""
        out = {
            "val_accuracy": self.val_accuracy,
            "queries_per_sec": self.queries_per_sec,
            "total_time_s": self.total_time,
            "phase_times_s": self.phase_times,
            "n_train": self.n_train,
            "n_test": self.n_test,
            "n_val": self.n_val,
            "config": dataclasses.asdict(self.config),
        }
        if self.certified_stats is not None:
            out["certified_stats"] = self.certified_stats
        if self.serving_stats is not None:
            out["serving"] = self.serving_stats
        # the unified telemetry view (knn_tpu.obs): phase histograms,
        # compile events, certified quality counters, serving series —
        # everything above is a per-run slice; this is the process-wide
        # registry the exporters scrape.  Absent when KNN_TPU_OBS=0, so
        # pre-obs consumers see the exact shape they always did.
        from knn_tpu import obs

        if obs.enabled():
            out["obs"] = obs.compact_snapshot()
            # the judgment layer over the snapshot: one burn-rate
            # evaluation pass per metrics() render (knn_tpu.obs.slo)
            out["slo"] = obs.slo_report()
        return out

    def metrics_json(self) -> str:
        return json.dumps(self.metrics(), indent=2)


def _infer_num_classes(cfg: JobConfig, *label_arrays) -> int:
    if cfg.num_classes is not None:
        return cfg.num_classes
    hi = 0
    for a in label_arrays:
        if a is not None and a.size:
            hi = max(hi, int(a.max()))
    return hi + 1


def _accuracy(pred: np.ndarray, real: np.ndarray) -> float:
    """``acc_calc`` (knn_mpi.cpp:69-84)."""
    return float(np.mean(pred == real))


def _np_minmax_apply(x: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Host-side rescale with the constant-dim passthrough guard
    (knn_mpi.cpp:284) — applied on host so the full arrays never
    materialize on a single device."""
    rng = hi - lo
    safe = np.where(rng != 0, rng, 1.0)
    return np.where(rng != 0, (x - lo) / safe, x).astype(np.float32)


def _run_jax(cfg: JobConfig, timer: PhaseTimer, train, train_labels, test, val,
             val_labels_real, mesh):
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.parallel.sharded import ShardedKNN, sharded_minmax

    if mesh is None:
        mesh = make_mesh(cfg.query_shards, cfg.db_shards)

    if cfg.normalize:
        with timer.phase("normalize"):
            # extrema via the distributed pmin/pmax reduction (the
            # reference's Allreduce pair); the rescale applies on host so
            # no full array ever lands on one device
            present = [a for a in (train, test, val) if a is not None]
            lo, hi = sharded_minmax(present, mesh=mesh)
            lo, hi = np.asarray(lo), np.asarray(hi)
            train = _np_minmax_apply(train, lo, hi)
            test = _np_minmax_apply(test, lo, hi)
            if val is not None:
                val = _np_minmax_apply(val, lo, hi)

    num_classes = _infer_num_classes(cfg, train_labels, val_labels_real)

    with timer.phase("distribute"):
        # Database padded on host, then placed shard-by-shard — once;
        # every query batch reuses the placement and compiled program.
        program = ShardedKNN(
            train,
            mesh=mesh,
            k=cfg.k,
            metric=cfg.metric,
            merge=cfg.merge,
            train_tile=cfg.train_tile,
            compute_dtype=cfg.compute_dtype,
            labels=train_labels,
            num_classes=num_classes,
        )

    certified_stats = {"fallback_queries": 0, "certified": 0}

    engine = None
    if cfg.serve_buckets is not None:
        # shape-bucketed serving (knn_tpu.serving): variable-size chunks
        # route through precompiled per-bucket executables — warmup pays
        # every compile up front, the job loop never compiles again, and
        # per-bucket compile counts + latency percentiles land in
        # JobResult.metrics()["serving"]
        from knn_tpu.serving.buckets import parse_buckets
        from knn_tpu.serving.engine import ServingEngine

        with timer.phase("serving_warmup"):
            engine = ServingEngine(program, buckets=parse_buckets(cfg.serve_buckets))
            engine.warmup(ops=("predict",))

    def classify(queries):
        n = queries.shape[0]
        bs = cfg.batch_size or n
        out = []
        for start in range(0, n, bs):
            chunk = queries[start : start + bs]
            take = min(bs, n - start)
            if cfg.mode == "certified":
                # real rows only: zero-pad queries would pollute the
                # certificate stats (and can spuriously fall back)
                labels_out, stats = program.predict_certified(
                    chunk[:take], selector=cfg.selector,
                    precision=cfg.pallas_precision,
                )
                for key, v in stats.items():  # incl. host_exact_queries
                    if isinstance(v, (int, np.integer)):
                        certified_stats[key] = certified_stats.get(key, 0) + v
                    else:
                        # non-additive observability (the resolved
                        # pallas_knobs / tuning provenance): keep as-is
                        certified_stats[key] = v
                out.append(np.asarray(labels_out))
            elif engine is not None:
                # the engine pads to its bucket ladder itself; the raw
                # (possibly short tail) chunk hits a precompiled bucket
                out.append(engine.predict(chunk))
            else:
                if chunk.shape[0] < bs:  # pad the tail so XLA sees one shape
                    chunk = np.pad(chunk, ((0, bs - chunk.shape[0]), (0, 0)))
                out.append(np.asarray(program.predict(chunk))[:take])
        return np.concatenate(out)

    val_pred = None
    if val is not None:
        with timer.phase("knn_val"):
            val_pred = classify(val)
    with timer.phase("knn_test"):
        test_pred = classify(test)
    serving_stats = None
    if engine is not None:
        serving_stats = {"max_wait_ms": cfg.max_wait_ms, **engine.stats()}
    return test_pred, val_pred, (
        certified_stats if cfg.mode == "certified" else None
    ), serving_stats


def _run_native(cfg: JobConfig, timer: PhaseTimer, train, train_labels, test, val,
                val_labels_real):
    from knn_tpu import native

    native.require()  # a failed build raises here, with make's error
    num_classes = _infer_num_classes(cfg, train_labels, val_labels_real)
    arrays = [a for a in (train, test, val) if a is not None]
    if cfg.normalize:
        with timer.phase("normalize"):
            lo, hi = native.minmax_stats(arrays)
            train = native.minmax_apply(train, lo, hi)
            test = native.minmax_apply(test, lo, hi)
            if val is not None:
                val = native.minmax_apply(val, lo, hi)
    val_pred = None
    if val is not None:
        with timer.phase("knn_val"):
            val_pred = native.knn_predict(
                train, train_labels, val, k=cfg.k, num_classes=num_classes,
                metric=cfg.metric, num_threads=cfg.num_threads,
            )
    with timer.phase("knn_test"):
        test_pred = native.knn_predict(
            train, train_labels, test, k=cfg.k, num_classes=num_classes,
            metric=cfg.metric, num_threads=cfg.num_threads,
        )
    return test_pred, val_pred


def run_job(cfg: JobConfig, *, mesh=None) -> JobResult:
    """Run the full reference job under ``cfg``; returns what the reference
    prints/writes plus per-phase timings and throughput."""
    from knn_tpu import obs

    obs.install_compile_hook()  # count+seconds of every XLA compile
    timer = PhaseTimer()

    with timer.phase("ingest"):
        train, train_labels = read_labeled_csv(cfg.train_file, cfg.dim)
        test = read_unlabeled_csv(cfg.test_file, cfg.dim or train.shape[1])
        val, val_labels_real = (None, None)
        if cfg.validation:
            val, val_labels_real = read_labeled_csv(cfg.val_file, cfg.dim)
    if cfg.k > train.shape[0]:
        raise ValueError(f"k={cfg.k} > n_train={train.shape[0]}")
    # Label range check, applied identically for both backends (the jax vote
    # would silently drop out-of-range labels, the native one rejects them —
    # the reference OOB-writes its vote array instead, knn_mpi.cpp:330).
    if train_labels.size and train_labels.min() < 0:
        raise ValueError(f"negative train label {int(train_labels.min())}")
    if cfg.num_classes is not None and train_labels.size and (
        train_labels.max() >= cfg.num_classes
    ):
        raise ValueError(
            f"train label {int(train_labels.max())} outside [0, {cfg.num_classes})"
        )

    if cfg.backend == "native":
        test_pred, val_pred = _run_native(
            cfg, timer, train, train_labels, test, val, val_labels_real
        )
        certified_stats = None
        serving_stats = None
    else:
        test_pred, val_pred, certified_stats, serving_stats = _run_jax(
            cfg, timer, train, train_labels, test, val, val_labels_real, mesh
        )

    val_acc = None
    if val_pred is not None:
        val_acc = _accuracy(val_pred, val_labels_real)

    with timer.phase("output"):
        write_labels(cfg.output_file, test_pred)

    return JobResult(
        test_labels=np.asarray(test_pred),
        val_labels=None if val_pred is None else np.asarray(val_pred),
        val_accuracy=val_acc,
        phase_times=timer.phases,
        total_time=timer.total,
        n_train=train.shape[0],
        n_test=test.shape[0],
        n_val=0 if val is None else val.shape[0],
        config=cfg,
        certified_stats=certified_stats,
        serving_stats=serving_stats,
    )
