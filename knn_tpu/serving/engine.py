"""The serving engine: precompiled shape-bucketed executables over a placed
:class:`~knn_tpu.parallel.sharded.ShardedKNN`, with async dispatch-ahead.

Three mechanisms turn the batch library into a throughput engine:

- **Shape bucketing** (serving.buckets): each request pads up to the
  smallest ladder bucket, so any traffic pattern hits O(log) compiled
  programs.  Pad rows are whole zero queries whose outputs are sliced
  away on host — the distance matrix is row-separable and the top-k runs
  per row, so padding is ARITHMETIC-TRANSPARENT: bucketed results are
  bitwise identical to a direct ``search()`` call of the same placed
  batch (asserted in tests/test_serving.py).  Against the *unpadded*
  direct call the guarantee is backend-dependent, exactly as it already
  is between two direct calls of different batch sizes: the TPU MXU's
  K-dim reduction order is batch-shape invariant (bitwise there), while
  CPU XLA's gemm strategy varies with batch shape in the last float
  bits — neighbor IDENTITY and lexicographic tie-break order are
  preserved either way (same pad-and-slice contract
  ``ShardedKNN._place_queries`` already relies on for mesh
  divisibility).
- **Precompiled executables**: :meth:`ServingEngine.warmup` AOT-compiles
  every bucket up front via ``jit(...).lower(...).compile()`` — no
  request ever stalls on an inline XLA compile.  Compiles are counted
  per bucket; a replayed trace of any batch-size mix compiles at most
  ``len(buckets)`` programs (asserted in tests/test_serving.py).
- **Async dispatch-ahead**: :meth:`submit` returns immediately with a
  :class:`PendingSearch` handle — JAX dispatch is asynchronous, so the
  host can pad/place/dispatch request N+1 while the device executes
  request N (double-buffered via :meth:`replay`'s bounded in-flight
  window).
"""

from __future__ import annotations

import threading
import time
from collections import Counter, deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from knn_tpu import obs
from knn_tpu.analysis.annotations import hot_path
from knn_tpu.obs import names as mn
from knn_tpu.serving.buckets import (
    DEFAULT_MAX_BUCKET,
    DEFAULT_MIN_BUCKET,
    bucket_for,
    bucket_ladder,
    normalize_ladder,
    split_sizes,
)

#: operations the engine can serve; each maps to one cached program family
OPS = ("search", "predict")


def latency_summary(samples_s: Sequence) -> Optional[Dict[str, float]]:
    """p50/p95/p99/mean (milliseconds) of per-request wall latencies —
    the engine feeds its bounded recent-request window (``count`` is the
    window's fill, not the lifetime request total; see stats()).

    Samples may be plain durations or ``(monotonic_ts, duration)``
    pairs; with timestamps the summary also labels WHICH window the
    quantiles cover — ``window_samples`` (the fill, same number as
    ``count``) and ``window_span_s`` (wall span from oldest to newest
    windowed sample) — so a consumer doing burn-rate math can never
    mistake a window quantile for a lifetime one."""
    if not samples_s:
        return None
    first = samples_s[0]
    ts = None
    if isinstance(first, tuple):
        ts = [t for t, _ in samples_s]
        vals = [v for _, v in samples_s]
    else:
        vals = samples_s
    arr = np.asarray(vals, dtype=np.float64) * 1e3
    out = {
        "p50": round(float(np.percentile(arr, 50)), 3),
        "p95": round(float(np.percentile(arr, 95)), 3),
        "p99": round(float(np.percentile(arr, 99)), 3),
        "mean": round(float(arr.mean()), 3),
        "max": round(float(arr.max()), 3),
        "count": int(arr.size),
        "window_samples": int(arr.size),
    }
    if ts is not None:
        out["window_span_s"] = round(max(ts) - min(ts), 3)
    return out


class PendingSearch:
    """An in-flight bucketed request: device work was dispatched
    asynchronously; :meth:`result` blocks on the transfer, slices the pad
    rows away, and records the request's wall latency."""

    def __init__(self, engine: "ServingEngine", op: str, chunks, n: int,
                 t0: float, trace_id: Optional[str] = None,
                 tenant: Optional[str] = None,
                 audit_queries: Optional[np.ndarray] = None):
        self._engine = engine
        self._op = op
        self._chunks = chunks  # [(device outputs, redo, rows)]
        self._n = n
        self._t0 = t0
        self._done = False
        self._error_counted = False
        #: request-scoped trace id (minted in submit; None when obs off)
        self.trace_id = trace_id
        #: tenant tag for per-tenant latency/error attribution (None =
        #: untagged: produces no tenant series at all)
        self.tenant = tenant
        #: query copy pinned at submit when the shadow audit sampler
        #: selected this request (knn_tpu.obs.audit); None = unsampled
        self._audit_queries = audit_queries

    def result(self):
        from knn_tpu.parallel.sharded import _fetch_or_redispatch

        t_join = time.perf_counter()
        try:
            parts = []
            for out, redo, rows in self._chunks:
                if self._op == "search":
                    d = _fetch_or_redispatch(
                        out[0], lambda r=redo: r()[0], "serving fetch (d)")
                    i = _fetch_or_redispatch(
                        out[1], lambda r=redo: r()[1], "serving fetch (i)")
                    parts.append((d[:rows], i[:rows]))
                else:
                    lbl = _fetch_or_redispatch(out, redo, "serving fetch (labels)")
                    parts.append(lbl[:rows])
            if self._op == "search":
                d = np.concatenate([p[0] for p in parts])[: self._n]
                i = np.concatenate([p[1] for p in parts])[: self._n]
                # positions among the placed rows become the caller's
                # row ids here (an interleaved placement: ShardedKNN)
                by_id = getattr(self._engine.program, "_answers_by_id",
                                None)
                res = (d, i) if by_id is None else by_id(d, i)
            else:
                res = np.concatenate(parts)[: self._n]
        except Exception:
            # errors, like latency, count once per REQUEST: a caller
            # retrying result() after a failure must not inflate
            # errors_total on every attempt
            if not self._error_counted:
                self._error_counted = True
                self._engine._record_error(self._op, tenant=self.tenant)
            raise
        if not self._done:  # latency is per request, not per .result() call
            self._done = True
            done = time.perf_counter()
            # join = time blocked on the device/transfer inside result();
            # the request span is the full submit-to-result wall
            obs.record_span("serving.join", self.trace_id,
                            done - t_join, op=self._op,
                            **({} if self.tenant is None
                               else {"tenant": self.tenant}))
            self._engine._record_latency(done - self._t0, self._op,
                                         trace_id=self.trace_id,
                                         rows=self._n,
                                         tenant=self.tenant)
            if self._audit_queries is not None:
                self._engine._submit_audit(self, res)
        return res


class ServingEngine:
    """Shape-bucketed query-serving frontend over a placed ``ShardedKNN``.

    Construction is cheap (no compiles); call :meth:`warmup` at startup to
    AOT-compile every bucket, or let the first request of each bucket pay
    its compile once.  All compile/dispatch accounting is exposed via
    :meth:`stats`.

    Thread-safety: guarded by ``self._lock`` (machine-checked by the
    ``locked-mutation`` checker, knn_tpu.analysis); the lock is never
    held across an XLA compile or a device dispatch (see
    :meth:`_executable`).
    """

    def __init__(
        self,
        program,
        *,
        buckets: Optional[Sequence[int]] = None,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        max_bucket: int = DEFAULT_MAX_BUCKET,
        k: Optional[int] = None,
        aot: bool = True,
        latency_window: int = 4096,
    ):
        self.program = program
        self.k = program.k if k is None else int(k)
        self.buckets = (
            bucket_ladder(min_bucket, max_bucket) if buckets is None
            else normalize_ladder(buckets)
        )
        self._aot = bool(aot)
        if getattr(program, "_tp", None) is None:
            # a host-RAM-tier placement has no resident database to
            # AOT-compile against — refuse with the tier's own message
            # instead of a cryptic NoneType AttributeError below
            program._require_resident("ServingEngine")
        #: user-facing request dim (what submit validates/pads against);
        #: the PLACED width below is wider wherever the rows lie in whole
        #: lane tiles or carry a dot placement's norm column:
        #: _place_queries appends the zero columns
        self._dim = int(getattr(program, "dim_in", program._tp.shape[1]))
        self._placed_dim = int(program._tp.shape[1])
        self._lock = threading.Lock()
        self._execs: Dict[Tuple[str, int], object] = {}
        #: per-key in-flight compile events (see _executable)
        self._compiling: Dict[Tuple[str, int], threading.Event] = {}
        self._compiles: Counter = Counter()  # bucket -> compile count
        self._dispatches: Counter = Counter()  # bucket -> dispatch count
        #: LIFETIME totals — the bounded latency window below reports
        #: recent-window truth only, so a long-running engine needs these
        #: to report lifetime truth alongside (also mirrored to the obs
        #: registry: knn_tpu_serving_{requests,queries,errors}_total)
        self._requests = 0
        self._queries = 0
        self._errors = 0
        #: bounded sample window of (monotonic ts, seconds) pairs: a
        #: long-running service must not grow a per-request list
        #: forever, and stats() percentiles over the recent window are
        #: the operationally useful number anyway — lifetime counts
        #: live in requests_total/queries_total above; the timestamps
        #: let latency_summary label the window's wall span
        self._latencies_s: deque = deque(maxlen=int(latency_window))
        #: ops whose buckets have all been AOT-compiled (warmup());
        #: the readiness probe (/healthz) gates on this being non-empty
        self.warmed_ops: set = set()
        # every XLA compile this engine triggers lands in the registry
        # (count + seconds), not just the per-bucket tallies above
        obs.install_compile_hook()
        # readiness/self-diagnosis surface (/healthz, /statusz, doctor)
        obs.health.register_engine(self)

    # -- compile cache -----------------------------------------------------
    def _jit_fn(self, op: str):
        from knn_tpu.parallel.sharded import _knn_program, _predict_program

        p = self.program
        if op == "search":
            return _knn_program(
                p.mesh, self.k, p.metric, p.merge, p.n_train, p.train_tile,
                p._dtype_key, dcn_merge=p.dcn_merge,
            )
        if p._labels is None:
            raise RuntimeError(
                "ServingEngine op='predict' needs a ShardedKNN built with "
                "labels")
        return _predict_program(
            p.mesh, self.k, p.num_classes, p.metric, p.merge, p.n_train,
            p.train_tile, p._dtype_key, dcn_merge=p.dcn_merge,
        )

    def _placed_rows(self, bucket: int) -> int:
        from knn_tpu.parallel.mesh import QUERY_AXIS

        qs = self.program.mesh.shape[QUERY_AXIS]
        return -(-bucket // qs) * qs

    def _tail_args(self, op: str) -> tuple:
        p = self.program
        return (p._tp,) if op == "search" else (p._tp, p._labels)

    def _executable(self, op: str, bucket: int,
                    trace_id: Optional[str] = None):
        """The compiled executable for ``(op, bucket)``; compiles AOT on
        first use (``lower().compile()`` — no example batch is executed).
        Distinct buckets below the mesh's query-shard count share one
        placed shape and therefore one executable.

        The engine lock is NEVER held across the XLA compile (seconds on
        real hardware): a cold bucket's compile must not freeze
        concurrent dispatches to warm buckets, stats(), or latency
        recording.  Concurrent first requests to the same key wait on a
        per-key event instead."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from knn_tpu.parallel.mesh import QUERY_AXIS

        key = (op, self._placed_rows(bucket))
        while True:
            with self._lock:
                ex = self._execs.get(key)
                if ex is not None:
                    return ex
                ev = self._compiling.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._compiling[key] = ev
                    break  # this thread owns the compile
            ev.wait()  # another thread is compiling this key; re-check
        try:
            # the compile span carries the trace id of the request that
            # triggered it (None for warmup), so a live request's inline
            # compile stall is attributable to that request end-to-end
            with obs.span("serving.compile", trace_id=trace_id, op=op,
                          bucket=int(bucket), placed_rows=int(key[1])):
                fn = self._jit_fn(op)
                if self._aot:
                    q_spec = jax.ShapeDtypeStruct(
                        (key[1], self._placed_dim), np.float32,
                        sharding=NamedSharding(self.program.mesh, P(QUERY_AXIS)),
                    )
                    ex = fn.lower(q_spec, *self._tail_args(op)).compile()
                else:
                    ex = fn
            with self._lock:
                self._execs[key] = ex
                self._compiles[bucket] += 1
            obs.counter(mn.SERVING_COMPILES, op=op, bucket=bucket).inc()
            return ex
        finally:
            # waiters re-check _execs; on a raised _jit_fn error they
            # find the key absent and retry (re-raising for themselves)
            with self._lock:
                del self._compiling[key]
            ev.set()

    def warmup(self, ops: Sequence[str] = ("search",)) -> Dict[str, int]:
        """AOT-compile every bucket for each requested op so no live
        request ever pays an inline compile.  Returns per-op executable
        counts (ladder rungs sharing a placed shape share an executable)."""
        counts = {}
        for op in ops:
            if op not in OPS:
                raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
            for b in self.buckets:
                self._executable(op, b)
            with self._lock:  # concurrent cold compiles mutate _execs
                keys = list(self._execs)
            counts[op] = len({k for k in keys if k[0] == op})
            self.warmed_ops.add(op)  # /healthz readiness flips here
        return counts

    # -- dispatch ----------------------------------------------------------
    @hot_path
    def _dispatch_chunk(self, op: str, chunk: np.ndarray,
                        trace_id: Optional[str] = None):
        """Pad one <=max_bucket chunk to its bucket and dispatch (async).
        Returns (device outputs, redo closure, real row count)."""
        from knn_tpu.parallel.sharded import _retry_transient

        n = chunk.shape[0]
        bucket = bucket_for(self.buckets, n)
        assert bucket is not None  # callers split oversize requests first
        if n < bucket:
            padded = np.zeros((bucket, self._dim), dtype=np.float32)
            padded[:n] = chunk
        else:
            padded = chunk

        def go():
            qp, _ = self.program._place_queries(padded)
            return self._executable(op, bucket, trace_id)(
                qp, *self._tail_args(op))

        out = _retry_transient(go, "serving dispatch")
        with self._lock:
            self._dispatches[bucket] += 1
        obs.counter(mn.SERVING_DISPATCHES, op=op, bucket=bucket).inc()
        return out, go, n

    # np.asarray/ascontiguousarray coerce the caller's HOST request
    # array (never a device fetch); int() reads numpy shape tuples
    @hot_path(allow=("np.asarray", "np.ascontiguousarray", "int"))
    def submit(self, queries, *, op: str = "search",
               trace_id: Optional[str] = None,
               tenant: Optional[str] = None) -> PendingSearch:
        """Dispatch ``queries`` (async) and return a handle; oversize
        requests split into max-bucket chunks, each dispatched back to
        back so the device pipeline stays full.  ``trace_id`` scopes the
        request's spans (dispatch / compile / join); None mints a fresh
        one when telemetry is enabled (knn_tpu.obs).  ``tenant`` tags
        the request for per-tenant attribution (requests/errors/latency
        series + the per-tenant SLO objectives); None produces no
        tenant series — a tenant-free caller's telemetry is unchanged."""
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
        q = np.ascontiguousarray(np.asarray(queries, dtype=np.float32))
        if q.ndim != 2 or q.shape[1] != self._dim:
            raise ValueError(
                f"queries shape {q.shape} incompatible with database dim "
                f"{self._dim}")
        if trace_id is None:
            trace_id = obs.new_trace_id()
        # shadow audit sampling (knn_tpu.obs.audit): the only hot-path
        # costs are one trace-id hash plus, on the sampled fraction, one
        # query copy pinned here so a later in-place caller mutation
        # cannot corrupt the replay.  The oracle scan itself runs on the
        # audit worker thread, never here.
        audit_q = (q.copy()
                   if op == "search" and obs.audit.sampled(trace_id)
                   else None)
        t0 = time.perf_counter()
        try:
            with obs.span("serving.dispatch", trace_id=trace_id, op=op,
                          rows=int(q.shape[0]),
                          **({"tenant": tenant}
                             if tenant is not None else {})) as sp:
                chunks = []
                lo = 0
                rungs = []
                for size in split_sizes(q.shape[0], self.buckets[-1]):
                    rungs.append(int(bucket_for(self.buckets, size)))
                    chunks.append(
                        self._dispatch_chunk(op, q[lo : lo + size], trace_id))
                    lo += size
                # which ladder rungs this request rode: the waterfall
                # layer groups its per-bucket attribution off this
                sp.set("buckets", rungs)
        except Exception:
            self._record_error(op, tenant=tenant)
            raise
        with self._lock:
            self._requests += 1
            self._queries += int(q.shape[0])
        obs.counter(mn.SERVING_REQUESTS, op=op).inc()
        obs.counter(mn.SERVING_QUERIES, op=op).inc(int(q.shape[0]))
        if tenant is not None:
            obs.counter(mn.TENANT_REQUESTS, tenant=tenant).inc()
        return PendingSearch(self, op, chunks, q.shape[0], t0, trace_id,
                             tenant, audit_queries=audit_q)

    def search(self, queries, *, return_sqrt: bool = False):
        """Bucketed exact search: (distances [Q, k], indices [Q, k]) as
        numpy arrays, bitwise identical to ``ShardedKNN.search``."""
        d, i = self.submit(queries, op="search").result()
        if return_sqrt:
            from knn_tpu.ops.distance import metric_values

            d = np.asarray(metric_values(d, self.program.metric))
        return d, i

    def predict(self, queries) -> np.ndarray:
        """Bucketed classification: labels [Q] int32 (majority vote on
        device, same program family as ``ShardedKNN.predict``)."""
        return self.submit(queries, op="predict").result()

    # -- trace replay ------------------------------------------------------
    def replay(self, requests: Sequence[np.ndarray], *, depth: int = 2):
        """Replay a request trace with at most ``depth`` requests in
        flight: request N+1 is padded/placed/dispatched while request N
        executes (the double-buffer that overlaps host staging with
        device compute).  Returns ``(results, report)`` where ``report``
        carries sustained q/s and the latency percentiles."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        results: List[object] = [None] * len(requests)
        pending: List[Tuple[int, PendingSearch]] = []
        total_rows = 0
        t0 = time.perf_counter()
        for idx, q in enumerate(requests):
            # drain BEFORE submitting so at most ``depth`` requests are
            # ever in flight, the new one included — while the oldest's
            # result() blocks, the depth-1 behind it keep the device busy
            while len(pending) >= depth:
                j, h = pending.pop(0)
                results[j] = h.result()
            total_rows += int(np.shape(q)[0])
            pending.append((idx, self.submit(q)))
        for j, h in pending:
            results[j] = h.result()
        wall = time.perf_counter() - t0
        report = {
            "requests": len(requests),
            "total_queries": total_rows,
            "wall_s": round(wall, 4),
            "sustained_qps": round(total_rows / wall, 2) if wall > 0 else None,
            "depth": depth,
            **self.stats(),
        }
        return results, report

    # -- observability -----------------------------------------------------
    def _record_latency(self, seconds: float, op: str = "search", *,
                        trace_id: Optional[str] = None,
                        rows: Optional[int] = None,
                        tenant: Optional[str] = None) -> None:
        with self._lock:
            self._latencies_s.append((time.monotonic(), seconds))
        # the registry histogram is the machine-scrapable counterpart of
        # stats()["latency_ms"]: every sample feeds both, but each keeps
        # its own bounded percentile window (latency_window here, the
        # registry default there), so quantiles can differ when the
        # engine was built with a non-default window.  The exemplar
        # keeps the worst samples' trace ids joinable back to their
        # spans (the histogram->trace join the waterfall layer reads).
        obs.histogram(mn.SERVING_REQUEST_LATENCY, op=op).observe(
            seconds, exemplar=trace_id)
        if tenant is not None:
            obs.histogram(mn.TENANT_REQUEST_LATENCY,
                          tenant=tenant).observe(seconds,
                                                 exemplar=trace_id)
        obs.record_span("serving.request", trace_id, seconds, op=op,
                        **({} if rows is None else {"rows": int(rows)}),
                        **({} if tenant is None else {"tenant": tenant}))

    def _submit_audit(self, handle: PendingSearch, res) -> None:
        """Enqueue one sampled, already-served request for off-path
        exact replay (knn_tpu.obs.audit).  Cheap here — one bounded
        queue put under the sampler's row budget; the oracle closure
        below (full-database f64 scan via ops.refine) runs ONLY on the
        audit worker thread.  Failure-proof: the request was already
        served, so a broken audit layer degrades to a dropped record,
        never an exception into the caller."""
        try:
            d, i = res
            program = self.program
            k = self.k
            metric = program.metric

            def oracle(queries, served_ids):
                from knn_tpu.ops.refine import (
                    _pairwise_f64,
                    refine_shared_exact,
                )

                db = program._host_train()  # may raise -> loud drop
                # dot placements are norm-augmented one column wider
                # than the request dim; original rows are the first
                # D columns (queries ride with a zero column appended)
                if db.shape[1] != queries.shape[1]:
                    db = db[:, : queries.shape[1]]
                n = db.shape[0]
                od, oi = refine_shared_exact(
                    db, queries, np.arange(n), k, metric=metric)
                ids = np.asarray(served_ids, np.int64)[:, :k]
                valid = (ids >= 0) & (ids < n)
                safe = np.where(valid, ids, 0)
                se = _pairwise_f64(queries, db[safe], metric)
                return od, oi, np.where(valid, se, np.inf)

            q_audit = handle._audit_queries
            obs.audit.submit(obs.audit.AuditRecord(
                trace_id=handle.trace_id,
                tenant=handle.tenant,
                k=k,
                queries=q_audit,
                served_d=np.asarray(d),
                served_ids=np.asarray(i),
                epoch=None,
                cost_rows=int(q_audit.shape[0]) * int(program.n_train),
                oracle=oracle,
            ))
        except Exception:  # noqa: BLE001 - audit must not fail serving
            obs.emit_event("audit.submit_error", op=handle._op,
                           trace_id=handle.trace_id)

    def _record_error(self, op: str, *,
                      tenant: Optional[str] = None) -> None:
        with self._lock:
            self._errors += 1
        obs.counter(mn.SERVING_ERRORS, op=op).inc()
        if tenant is not None:
            obs.counter(mn.TENANT_ERRORS, tenant=tenant).inc()

    def stats(self, *, include_slo: bool = True) -> dict:
        """Compile/dispatch accounting + request latency percentiles —
        the serving metrics JobResult/bench surface.  When telemetry is
        enabled, also carries the ``slo`` section: one burn-rate
        evaluation pass over the process-wide objectives
        (knn_tpu.obs.slo) — so every stats() consumer sees breach state
        next to the raw numbers it would otherwise misjudge.
        ``include_slo=False`` skips that pass for callers that already
        ran their own (the health report evaluates once and reads every
        engine's raw stats alongside)."""
        slo_section = (obs.slo_report()
                       if include_slo and obs.enabled() else None)
        # the slowest-requests exemplar table (trace ids of the worst
        # recent samples, no inline waterfalls at this altitude —
        # /statusz carries those).  Present only while telemetry is on:
        # the disabled stats() shape is part of the obs-off contract.
        slowest = None
        if obs.enabled():
            try:
                from knn_tpu.obs import waterfall

                slowest = waterfall.slowest_table(with_waterfalls=False)
            except Exception:  # pragma: no cover - stats must not die
                slowest = []
        # the shadow audit sampler's quality section: present only when
        # the sampler is armed (rate > 0 AND telemetry on), so both the
        # obs-off and the audit-off stats() shapes are unchanged
        quality = None
        if obs.enabled():
            try:
                if obs.audit.audit_rate() > 0:
                    quality = obs.audit.status()
            except Exception:  # pragma: no cover - stats must not die
                quality = None
        with self._lock:
            return {
                **({"slo": slo_section} if slo_section else {}),
                **({"quality": quality} if quality else {}),
                **({"slowest_requests": slowest}
                   if slowest is not None else {}),
                "buckets": list(self.buckets),
                "compile_count": int(sum(self._compiles.values())),
                "executables": len(self._execs),
                "per_bucket_compiles": {
                    int(b): int(c) for b, c in sorted(self._compiles.items())
                },
                "per_bucket_dispatches": {
                    int(b): int(c) for b, c in sorted(self._dispatches.items())
                },
                "requests": self._requests,
                # lifetime truth, alongside the window percentiles: the
                # latency deque is bounded, so on a long-running engine
                # latency_ms["count"] is the window fill, NOT the total
                "requests_total": self._requests,
                "queries_total": self._queries,
                "errors_total": self._errors,
                "latency_ms": latency_summary(self._latencies_s),
            }
