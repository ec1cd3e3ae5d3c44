"""Per-phase timing — the observability the reference lacks.

The reference has exactly one timer: a barrier-fenced ``MPI_Wtime`` pair
around the entire job, printed by rank 0 (knn_mpi.cpp:133-134, 395-398), so
its published numbers cannot attribute time to ingest vs communication vs
compute (SURVEY.md §5).  ``PhaseTimer`` gives each phase its own fence:
call :meth:`PhaseTimer.block` on the phase's device outputs before the
phase block closes (JAX dispatch is async — without the fence the timer
measures dispatch latency, not compute).

Since the telemetry subsystem landed (knn_tpu.obs), ``PhaseTimer`` is a
thin view over it: every phase close also records into the process-wide
``knn_tpu_phase_seconds{phase=...}`` histogram, so pipeline phases show
up in the same Prometheus scrape as serving latencies — the per-run
``summary()`` shape is unchanged.

Concurrency contract: a PhaseTimer may be SHARED across threads (the
serving worker threads and the pipeline do — all mutation is locked),
but phases must not NEST within one thread: the phase sum and the
first-start/last-stop total silently double-count under re-entrant
``phase()`` scopes, so nesting raises instead of corrupting the
numbers.  Distinct threads timing concurrent phases are fine (their
wall intervals legitimately overlap).

For deep dives, capture a device trace with ``jax.profiler.trace``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

import jax

from knn_tpu import obs
from knn_tpu.obs import names as _mn


class PhaseTimer:
    """Accumulates named phase durations; total covers first start→last stop
    (the reference's single Wtime pair, knn_mpi.cpp:134,396, recovered as
    the sum).  Thread-safety: guarded by ``self._lock`` (machine-checked
    by the ``locked-mutation`` checker, knn_tpu.analysis); re-entrant
    nesting within a thread raises (see module docstring)."""

    def __init__(self):
        self.phases: Dict[str, float] = {}
        self._t0: Optional[float] = None
        self._t_end: Optional[float] = None
        self._lock = threading.Lock()
        #: per-thread open-phase name — nesting detection must not trip
        #: on OTHER threads' concurrently open phases
        self._open = threading.local()

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a named phase.  Call :meth:`block` inside the body on any
        device arrays the phase produced — JAX dispatch is async, so the
        fence must come from within, after the work exists."""
        already = getattr(self._open, "name", None)
        if already is not None:
            raise RuntimeError(
                f"PhaseTimer.phase({name!r}) opened inside still-open "
                f"phase {already!r}: nested phases double-count the "
                f"phase sum and the total — close the outer phase first "
                f"(or use a second PhaseTimer)")
        self._open.name = name
        start = time.perf_counter()
        with self._lock:
            if self._t0 is None:
                self._t0 = start
        try:
            yield
        finally:
            end = time.perf_counter()
            self._open.name = None
            with self._lock:
                self.phases[name] = self.phases.get(name, 0.0) + (end - start)
                if self._t_end is None or end > self._t_end:
                    self._t_end = end
            obs.histogram(_mn.PHASE_SECONDS, phase=name).observe(end - start)
            obs.emit_event("phase", phase=name,
                           dur_s=round(end - start, 6))

    def block(self, *arrays) -> None:
        """Fence device work into the *current* phase timing."""
        for a in jax.tree_util.tree_leaves(arrays):
            if isinstance(a, jax.Array):
                a.block_until_ready()

    @property
    def total(self) -> float:
        with self._lock:
            if self._t0 is None or self._t_end is None:
                return 0.0
            return self._t_end - self._t0

    def summary(self) -> Dict[str, float]:
        with self._lock:
            out = dict(self.phases)
        out["total"] = self.total
        return out
