"""The latency-vs-throughput knee: a stepped-rate sweep that locates
the maximum sustained rate where admitted-request tail latency still
meets the SLO, emitted as a validated artifact block.

TPU-KNN (arXiv:2206.14286) frames peak-FLOP serving as a
throughput-recall-latency tradeoff; the knee is where that tradeoff
lives for a serving deployment — below it, added load is free; above
it, every extra offered request is paid in tail latency (or, with
admission control on, in explicit sheds).  :func:`knee_block` is the
artifact shape and :func:`validate_knee_block` its validator.

The sweep is target-agnostic: a factory returning a fresh
``QueryQueue``-shaped target per step (fresh so one step's saturated
backlog can never pollute the next step's latency — the real engine's
queue is cheap to rebuild over a warmed engine; the synthetic target's
knee is known by construction, which is what makes the detector
testable without a device).
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence

from knn_tpu.loadgen import driver
from knn_tpu.loadgen.workload import WorkloadSpec, generate

#: artifact schema version (bump on shape changes so a reader can
#: tell a malformed block from an old one) — the version token the
#: artifact-schema catalog's ``loadgen_knee`` entry consumes
BLOCK_VERSION = 1


def _step_fields():
    from knn_tpu.analysis.artifacts import element_required

    return element_required("loadgen_knee", "rate_steps")


#: fields every rate step must carry for the artifact to validate —
#: DERIVED from the artifact-schema catalog (knn_tpu.analysis.
#: artifacts), the one declaration the validator and the
#: artifact-lockstep checker both read
STEP_FIELDS = _step_fields()


def run_step(target, spec: WorkloadSpec, *, queries,
             submitters: int = 2, waiters: int = 2) -> dict:
    """One rate step: drive the spec open-loop, return the driver
    report plus the step's offered-rate label."""
    reqs = generate(spec)
    rep = driver.run_workload(target, reqs, queries=queries,
                              submitters=submitters, waiters=waiters)
    rep["rate_qps"] = spec.rate_qps
    return rep


def knee_sweep(target_factory: Callable[[], object],
               base: WorkloadSpec, rates: Sequence[float], *,
               queries, slo_p99_ms: float,
               submitters: int = 2, waiters: int = 2) -> dict:
    """Stepped-rate sweep -> knee artifact block.  ``target_factory``
    builds a FRESH target per step (closed afterwards when it has a
    ``close``); ``rates`` are the offered request rates (q/s) to step
    through, ascending; the knee is the highest ACHIEVED rate among
    steps whose admitted p99 meets ``slo_p99_ms``."""
    if not rates:
        raise ValueError("need at least one rate step")
    if slo_p99_ms <= 0:
        raise ValueError(f"slo_p99_ms must be > 0, got {slo_p99_ms}")
    steps: List[dict] = []
    for rate in rates:
        spec = base.at_rate(rate)
        if not generate(spec):
            # a low step's Poisson draw can produce zero arrivals
            # (P = e^{-rate*duration}); record the empty step instead
            # of letting it abort the sweep and lose the higher steps
            steps.append({
                "rate_qps": float(rate), "offered": 0, "ok": 0,
                "rejected": 0, "shed": 0, "errors": 0,
                "offered_qps": None, "achieved_qps": None,
                "shed_fraction": None, "admitted_p50_ms": None,
                "admitted_p95_ms": None, "admitted_p99_ms": None,
                "within_slo": False, "empty_schedule": True,
                "per_tenant": {}})
            continue
        target = target_factory()
        try:
            rep = run_step(target, spec, queries=queries,
                           submitters=submitters, waiters=waiters)
        finally:
            close = getattr(target, "close", None)
            if callable(close):
                close()
        lat = rep.get("latency_ms") or {}
        p99 = lat.get("p99")
        within = p99 is not None and p99 <= slo_p99_ms
        steps.append({
            "rate_qps": float(rate),
            "offered": rep["offered"],
            "ok": rep["ok"],
            "rejected": rep["rejected"],
            "shed": rep["shed"],
            "errors": rep["errors"],
            "offered_qps": rep["offered_qps"],
            "achieved_qps": rep["achieved_qps"],
            "shed_fraction": rep["shed_fraction"],
            "admitted_p50_ms": lat.get("p50"),
            "admitted_p95_ms": lat.get("p95"),
            "admitted_p99_ms": lat.get("p99"),
            "within_slo": bool(within),
            "per_tenant": rep.get("per_tenant"),
            # worst admitted requests' trace ids: the step's tail is
            # joinable against spans/waterfalls (cli waterfall)
            "slowest": rep.get("slowest"),
        })
    return knee_block(steps, slo_p99_ms=slo_p99_ms)


def knee_block(steps: Sequence[dict], *, slo_p99_ms: float) -> dict:
    """The artifact: the step table plus the detected knee —
    the highest achieved q/s among SLO-meeting steps (None when no
    step met the SLO: an honest 'knee below the lowest step' beats a
    fabricated number)."""
    best = None
    best_rate = None
    for s in steps:
        if s.get("within_slo") and s.get("achieved_qps") is not None:
            if best is None or s["achieved_qps"] > best:
                best = s["achieved_qps"]
                best_rate = s["rate_qps"]
    return {
        "version": BLOCK_VERSION,
        "slo_p99_ms": float(slo_p99_ms),
        "rate_steps": list(steps),
        "knee_qps": best,
        "knee_rate_qps": best_rate,
    }


def validate_knee_block(block) -> List[str]:
    """Structural validation of a ``loadgen_knee`` block: returns the
    list of violations (empty = valid).  Blocks that recorded their
    own failure (an ``error`` key) are exempt — an honest error field
    beats a refused block.  A shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``loadgen_knee`` entry)
    with the legacy error strings byte-identical."""
    from knn_tpu.analysis.artifacts import validate

    return validate("loadgen_knee", block, style="legacy")


def closed_loop_anchor(queue, pool, *, requests: int = 32,
                       rows: int = 4) -> float:
    """A quick CLOSED-LOOP capacity probe: burst ``requests`` small
    submissions through ``queue`` and measure completions/s.  Bursts
    coalesce maximally, so this OVER-estimates open-loop capacity —
    pair it with :func:`rates_around`, whose default ladder reaches a
    decade below.  Drive an admission-FREE queue: the probe measures
    capacity, not policy (a tight depth bound would reject the burst
    before the sweep even starts)."""
    rows = min(rows, pool.shape[0])
    t0 = time.monotonic()
    futs = [queue.submit(pool[:rows]) for _ in range(requests)]
    for f in futs:
        f.result()
    return requests / max(time.monotonic() - t0, 1e-9)


def rates_around(anchor_qps: float,
                 fractions: Sequence[float] = (0.05, 0.1, 0.2, 0.4,
                                               0.7, 1.0, 1.5),
                 ) -> List[float]:
    """Default step ladder around an anchor rate.  The anchor is
    usually a CLOSED-LOOP burst probe, which over-estimates open-loop
    capacity (a burst coalesces maximally; spread arrivals pay a
    dispatch each), so the ladder reaches more than a decade below the
    anchor and modestly above it — wide enough to bracket the knee
    wherever the coalescing ratio lands it."""
    if anchor_qps <= 0:
        raise ValueError(f"anchor_qps must be > 0, got {anchor_qps}")
    return [round(anchor_qps * f, 3) for f in fractions]
