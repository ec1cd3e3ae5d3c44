"""Live health introspection: liveness/readiness probes and the
self-diagnosis report behind ``/healthz``, ``/statusz``, and the
jax-free ``doctor`` CLI subcommand.

Serving components REGISTER here (weakly — a collected engine drops out
of the report instead of pinning itself alive): ``ServingEngine``
registers at construction and marks ops warmed in :meth:`warmup`;
``QueryQueue`` registers its worker threads.  The probes then answer
the two questions a load balancer asks:

- **live** (``/healthz`` exists at all): the process is up and the obs
  subsystem can answer — always true once this module is importable.
- **ready** (``/healthz`` returns 200): at least one registered engine
  has COMPLETED ``warmup()`` (no live request will pay an inline XLA
  compile) and every open queue's batcher/completer threads are alive
  (a dead worker thread hangs every later request — the one failure
  readiness exists to catch before traffic does).

``/statusz`` (and ``doctor``) render :func:`report` — readiness plus
self-diagnosis: device inventory (only when JAX is ALREADY initialized
in the process; a status probe must never trigger a backend init),
per-engine warmup/bucket/compile state, queue depth vs capacity and
worker liveness, active SLO breaches, and the last
N alert events from the trace ring.  :func:`write
<knn_tpu.obs.export.write_json_snapshot>` embeds the same report in the
atomic snapshot, so ``doctor --snapshot`` renders the identical
structure offline.

Disabled mode (``KNN_TPU_OBS=0``): registration is skipped (no obs
objects ride the serving hot path) and the report says so — the health
surface is part of the telemetry opt-in, exactly like the exporters.
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from typing import List, Optional

from knn_tpu.obs import ident, names, registry, slo, trace

#: alert events included in the report (newest last)
REPORT_ALERTS = 20

_lock = threading.Lock()
_engines: List[weakref.ref] = []
_queues: List[weakref.ref] = []
_indexes: List[weakref.ref] = []


def register_engine(engine) -> None:
    """Called by ServingEngine.__init__ (no-op when obs is disabled)."""
    if not registry.enabled():
        return
    with _lock:
        _engines[:] = [r for r in _engines if r() is not None]
        if not any(r() is engine for r in _engines):
            _engines.append(weakref.ref(engine))


def register_queue(queue) -> None:
    """Called by QueryQueue.__init__ (no-op when obs is disabled)."""
    if not registry.enabled():
        return
    with _lock:
        _queues[:] = [r for r in _queues if r() is not None]
        if not any(r() is queue for r in _queues):
            _queues.append(weakref.ref(queue))


def register_index(index) -> None:
    """Called by MutableIndex.__init__ (no-op when obs is disabled)."""
    if not registry.enabled():
        return
    with _lock:
        _indexes[:] = [r for r in _indexes if r() is not None]
        if not any(r() is index for r in _indexes):
            _indexes.append(weakref.ref(index))


def reset() -> None:
    """Drop every registration (test isolation)."""
    with _lock:
        _engines.clear()
        _queues.clear()
        _indexes.clear()


def _live_components():
    with _lock:
        engines = [e for e in (r() for r in _engines) if e is not None]
        queues = [q for q in (r() for r in _queues) if q is not None]
    return engines, queues


def probe() -> dict:
    """The /healthz payload: ``ready`` is the 200-vs-503 verdict, the
    reasons say why not."""
    engines, queues = _live_components()
    reasons = []
    if not registry.enabled():
        reasons.append("telemetry disabled (KNN_TPU_OBS=0): health "
                       "introspection is part of the obs opt-in")
    if not engines:
        reasons.append("no ServingEngine registered")
    warmed = [e for e in engines if getattr(e, "warmed_ops", ())]
    if engines and not warmed:
        reasons.append("no registered engine has completed warmup()")
    for q in queues:
        if getattr(q, "_closed", False):
            continue  # a deliberately closed queue is not a failure
        for tname in ("_batcher_t", "_completer_t"):
            t = getattr(q, tname, None)
            if t is not None and not t.is_alive():
                reasons.append(
                    f"queue worker thread {tname.strip('_')} is dead")
    ready = not reasons
    if registry.enabled():
        registry.gauge(names.HEALTH_READY).set(1.0 if ready else 0.0)
    return {"live": True, "ready": ready, "reasons": reasons}


def _device_inventory() -> dict:
    """Device list WITHOUT triggering a backend init: only consult JAX
    when something else in the process already imported it."""
    if "jax" not in sys.modules:
        return {"available": False,
                "reason": "jax not imported in this process"}
    try:
        import jax
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            return {"available": False,
                    "reason": "jax imported but no backend initialized"}
        devs = jax.devices()
        return {
            "available": True,
            "backend": jax.default_backend(),
            "count": len(devs),
            "kinds": sorted({getattr(d, "device_kind", str(d))
                             for d in devs}),
        }
    except Exception as e:  # noqa: BLE001 - introspection must not raise
        return {"available": False,
                "reason": f"{type(e).__name__}: {e}"}


def _engine_status(e) -> dict:
    try:
        # the report's top level already ran ONE SLO evaluation; each
        # engine contributes raw stats only (no per-engine re-pass —
        # it would inflate knn_tpu_slo_evaluations_total per scrape)
        st = e.stats(include_slo=False)
    except TypeError:  # engine-like object without the kwarg
        st = e.stats()
    except Exception as ex:  # noqa: BLE001
        return {"error": f"{type(ex).__name__}: {ex}"}
    return {
        "warmed_ops": sorted(getattr(e, "warmed_ops", ())),
        "buckets": st.get("buckets"),
        "executables": st.get("executables"),
        "compile_count": st.get("compile_count"),
        "requests_total": st.get("requests_total"),
        "queries_total": st.get("queries_total"),
        "errors_total": st.get("errors_total"),
        "latency_ms": st.get("latency_ms"),
    }


def _queue_status(q) -> dict:
    # racy-but-safe reads of the queue's own backlog (list len / int):
    # a status probe must never contend for the dispatch condvar
    depth_req = len(getattr(q, "_pending", ()))
    depth_rows = int(getattr(q, "_pending_rows", 0))
    ctrl = getattr(q, "_ctrl", None)
    out = {
        "op": getattr(q, "op", None),
        "closed": bool(getattr(q, "_closed", False)),
        "max_wait_ms": round(getattr(q, "max_wait_s", 0.0) * 1e3, 3),
        "capacity_rows": getattr(q, "max_rows", None),
        "depth_requests": depth_req,
        "depth_rows": depth_rows,
        "rows_utilization": (round(depth_rows / q.max_rows, 4)
                             if getattr(q, "max_rows", 0) else None),
        # outstanding = queued + in flight: what admission's depth
        # bound and wait estimate actually judge
        "outstanding_requests": int(getattr(q, "_out_req", 0)),
        "batcher_alive": q._batcher_t.is_alive(),
        "completer_alive": q._completer_t.is_alive(),
    }
    if ctrl is not None:
        try:
            out["admission"] = ctrl.stats()
        except Exception as ex:  # noqa: BLE001 — probe must not die on it
            out["admission"] = {"error": f"{type(ex).__name__}: {ex}"}
    return out


def _slowest_requests() -> list:
    """The slowest-requests exemplar table with inline waterfalls
    (knn_tpu.obs.waterfall) — never fatal: a status probe must render
    even when the forensics layer cannot."""
    try:
        from knn_tpu.obs import waterfall

        return waterfall.slowest_table()
    except Exception as e:  # noqa: BLE001 - introspection must not raise
        return [{"error": f"{type(e).__name__}: {e}"}]


def _postmortems() -> dict:
    try:
        from knn_tpu.obs import blackbox

        return blackbox.status()
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}


def _quality_status() -> dict:
    """The shadow audit sampler's quality section (knn_tpu.obs.audit)
    plus drift sketches from every registered IVF index — never fatal,
    and never ARMS anything: a disabled sampler reports itself
    disabled without starting a worker."""
    try:
        from knn_tpu.obs import audit

        out = audit.status()
        with _lock:
            indexes = [i for i in (r() for r in _indexes)
                       if i is not None]
        drifts = []
        for idx in indexes:
            mon = getattr(idx, "_drift", None)
            if mon is not None:
                try:
                    drifts.append(mon.status())
                except Exception as e:  # noqa: BLE001
                    drifts.append({"error": f"{type(e).__name__}: {e}"})
        if drifts:
            out["drift"] = drifts
        return out
    except Exception as e:  # noqa: BLE001 - introspection must not raise
        return {"error": f"{type(e).__name__}: {e}"}


def report(slo_section: Optional[dict] = None,
           slowest: Optional[list] = None) -> dict:
    """The full /statusz payload (see module docstring).  Everything in
    it is JSON-serializable; ``doctor`` renders the same structure.

    ``slo_section`` injects an ALREADY-COMPUTED SLO report instead of
    evaluating a fresh pass — the flight recorder passes the evaluation
    that fired it, so building a postmortem bundle can never observe
    (and re-fire on) a second transition mid-dump.  ``slowest``
    likewise injects a prebuilt slowest-requests table so the bundle
    path reconstructs the event ring once, not per consumer."""
    pr = probe()
    if slo_section is None:
        slo_section = slo.slo_report()
    alerts = [e for e in trace.get_event_log().recent()
              if e.get("name") == "slo.alert"][-REPORT_ALERTS:]
    engines, queues = _live_components()
    return {
        "generated_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "pid": os.getpid(),
        # who this process is (host, process_index/count, device kind,
        # coordinator, commit, catalog version) — the fleet aggregator
        # keys members and detects catalog skew off this stamp
        "identity": ident.identity(),
        "obs_enabled": registry.enabled(),
        "liveness": {"live": pr["live"]},
        "readiness": {"ready": pr["ready"], "reasons": pr["reasons"]},
        "devices": _device_inventory(),
        "engines": [_engine_status(e) for e in engines],
        "queues": [_queue_status(q) for q in queues],
        "slo": slo_section,
        "active_breaches": (slo_section.get("breached", [])
                            if slo_section else []),
        "alerts": alerts,
        # tail forensics: the worst recent requests (histogram
        # exemplars) with inline waterfalls, and the flight recorder's
        # bundle inventory (knn_tpu.obs.{waterfall,blackbox})
        "slowest_requests": (_slowest_requests() if slowest is None
                             else slowest),
        "postmortems": _postmortems(),
        # multi-host serving: the last cross-host merge's straggler
        # attribution (per-host walls, gap, DCN volume/strategy) —
        # None until a MultiHostKNN merge ran in this process
        "multihost": _multihost_status(),
        # mutable indexes registered in this process (knn_tpu.index):
        # epoch / delta-tail / tombstone / compaction state — the
        # write-path health beside the read-path numbers above
        "index": _index_status(),
        # quality observability: the shadow audit sampler's state
        # (sampled/replayed/deficient/dropped) and any registered
        # index's drift sketches (knn_tpu.obs.{audit,drift})
        "quality": _quality_status(),
    }


def _index_status() -> list:
    with _lock:
        indexes = [i for i in (r() for r in _indexes) if i is not None]
    out = []
    for idx in indexes:
        try:
            out.append(idx.stats())
        except Exception as e:  # noqa: BLE001 - probe must not die on it
            out.append({"error": f"{type(e).__name__}: {e}"})
    return out


def _multihost_status() -> Optional[dict]:
    """The parallel.multihost last-merge report, import-guarded so a
    jax-free doctor render of a snapshot never pays (or breaks on) the
    jax import."""
    try:
        from knn_tpu.parallel import multihost

        return multihost.last_report()
    except Exception:  # noqa: BLE001 — introspection must not kill /statusz
        return None


def report_from_snapshot(payload: dict) -> dict:
    """Recover a report from an atomic JSON snapshot (export.
    write_json_snapshot embeds ``health``; pre-health snapshots degrade
    to what the metrics alone can say)."""
    if "health" in payload:
        return payload["health"]
    metrics = payload.get("metrics", {})
    ready_series = metrics.get(names.HEALTH_READY, {}).get("series", [])
    ready = bool(ready_series and ready_series[0]["value"] == 1.0)
    return {
        "generated_at": payload.get("written_at"),
        "pid": payload.get("pid"),
        "obs_enabled": payload.get("enabled"),
        "liveness": {"live": None},
        "readiness": {
            "ready": ready if ready_series else None,
            "reasons": ["snapshot predates the health section — "
                        "readiness derived from the "
                        + names.HEALTH_READY + " gauge only"],
        },
        "devices": {"available": False,
                    "reason": "not recorded in this snapshot"},
        "engines": [], "queues": [],
        "slo": {},
        "multihost": None, "index": [], "quality": {},
        "active_breaches": [], "alerts": [],
        "slowest_requests": [], "postmortems": {},
    }


def render_text(rep: dict) -> str:
    """Human-readable rendering of a report dict — shared by ``doctor``
    against both a live /statusz fetch and an offline snapshot, so the
    two sources print identically for identical state."""
    lines = []
    ready = rep.get("readiness", {}).get("ready")
    verdict = {True: "READY", False: "NOT READY", None: "UNKNOWN"}[ready]
    lines.append(f"health: {verdict}   (pid {rep.get('pid')}, "
                 f"generated {rep.get('generated_at')}, "
                 f"obs_enabled={rep.get('obs_enabled')})")
    for r in rep.get("readiness", {}).get("reasons", []):
        lines.append(f"  reason: {r}")
    dev = rep.get("devices", {})
    if dev.get("available"):
        lines.append(f"devices: {dev['count']}x {','.join(dev['kinds'])} "
                     f"({dev['backend']})")
    else:
        lines.append(f"devices: unavailable ({dev.get('reason')})")
    for i, e in enumerate(rep.get("engines", [])):
        lat = e.get("latency_ms") or {}
        lines.append(
            f"engine[{i}]: warmed={e.get('warmed_ops')} "
            f"buckets={e.get('buckets')} "
            f"executables={e.get('executables')} "
            f"compiles={e.get('compile_count')} "
            f"requests={e.get('requests_total')} "
            f"errors={e.get('errors_total')} "
            f"p99_ms={lat.get('p99')} "
            f"(window {lat.get('window_samples')} samples / "
            f"{lat.get('window_span_s')}s)")
    for i, q in enumerate(rep.get("queues", [])):
        lines.append(
            f"queue[{i}]: op={q.get('op')} closed={q.get('closed')} "
            f"depth={q.get('depth_requests')}req/"
            f"{q.get('depth_rows')}rows of {q.get('capacity_rows')} "
            f"(util {q.get('rows_utilization')}) "
            f"batcher={'up' if q.get('batcher_alive') else 'DOWN'} "
            f"completer={'up' if q.get('completer_alive') else 'DOWN'}")
    for i, ix in enumerate(rep.get("index") or []):
        if "error" in ix:
            lines.append(f"index[{i}]: status unavailable "
                         f"({ix['error']})")
            continue
        lc = ix.get("last_compaction") or {}
        lines.append(
            f"index[{i}]: epoch={ix.get('epoch')} "
            f"rows={ix.get('rows')} tail={ix.get('tail_rows')}"
            f"/{ix.get('tail_capacity')} "
            f"tombstones={ix.get('tombstones')}/{ix.get('budget')} "
            f"live={ix.get('live_rows')} "
            f"compactions={ix.get('compactions')}"
            + (f" (last swap {lc.get('swap_s')}s)" if lc else "")
            + (" compactor=up" if ix.get("compactor_alive") else ""))
    qual = rep.get("quality") or {}
    if qual.get("enabled"):
        dropped = qual.get("dropped") or {}
        drop_s = (f" dropped={dropped}" if dropped else "")
        lines.append(
            f"quality: audit rate={qual.get('rate')} "
            f"sampled={qual.get('sampled_requests')} "
            f"replayed={qual.get('replayed_queries')}q "
            f"deficient={qual.get('deficient_queries')} "
            f"last_recall@k={qual.get('last_recall_at_k')}{drop_s}")
    elif qual and "error" not in qual:
        lines.append("quality: audit sampler off "
                     "(KNN_TPU_AUDIT_RATE unset)")
    for i, dr in enumerate(qual.get("drift") or []):
        lines.append(
            f"drift[{i}]: queries={dr.get('queries_observed')} "
            f"norm_psi={dr.get('norm_psi')} "
            f"assign_psi={dr.get('centroid_assign_psi')}")
    mh = rep.get("multihost")
    if mh:
        walls = mh.get("host_walls_s") or []
        sh = mh.get("straggler_host")
        # the named slow host: per-host walls (not just max-min) are in
        # the report, so the argmax renders here and the fleet view can
        # attribute the gap to a member
        sh_s = f" straggler=host{sh}" if sh is not None else ""
        lines.append(
            f"multihost: {mh.get('hosts')} host(s) "
            f"[{mh.get('transport')}] dcn_merge={mh.get('dcn_merge')} "
            f"bytes={mh.get('dcn_merge_bytes')} "
            f"straggler_gap={mh.get('straggler_gap_s')}s{sh_s} "
            f"(walls {', '.join(str(w) for w in walls)})")
    breaches = rep.get("active_breaches", [])
    lines.append(f"slo breaches: {', '.join(breaches) if breaches else 'none'}")
    def _slo_line(name, o, indent="  "):
        state = "BREACHED" if o.get("breached") else "ok"
        if o.get("kind") == "quantile":
            return (f"{indent}slo {name}: {state} {o.get('quantile')}="
                    f"{o.get('value_s')}s (threshold "
                    f"{o.get('threshold_s')}s, window "
                    f"{o.get('window_samples')} samples / "
                    f"{o.get('window_span_s')}s)")
        burns = {w: d.get("burn_rate")
                 for w, d in (o.get("windows") or {}).items()}
        return (f"{indent}slo {name}: {state} burn={burns} "
                f"(target {o.get('target')})")

    for o_name, o in (rep.get("slo", {}).get("objectives", {}) or {}).items():
        if o.get("group_by") is not None:
            # grouped objective: one line per label value (the
            # per-tenant drill-down), a summary line when idle
            groups = o.get("groups") or {}
            if not groups:
                lines.append(f"  slo {o_name}: no {o.get('group_by')} "
                             f"traffic")
                continue
            breached = o.get("breached") or []
            lines.append(f"  slo {o_name} (per {o.get('group_by')}): "
                         f"{len(breached)}/{len(groups)} breached")
            for gval, gentry in sorted(groups.items()):
                lines.append(_slo_line(f"{o_name}:{gval}", gentry,
                                       indent="    "))
            continue
        lines.append(_slo_line(o_name, o))
    alerts = rep.get("alerts", [])
    if alerts:
        lines.append(f"last {len(alerts)} alert event(s):")
        for a in alerts:
            lines.append(f"  [{a.get('ts')}] {a.get('objective')} "
                         f"{a.get('state')}")
    slowest = [r for r in rep.get("slowest_requests") or []
               if "trace_id" in r]
    if slowest:
        lines.append(f"slowest recent request(s) ({len(slowest)}):")
        from knn_tpu.obs import waterfall as _wf

        for r in slowest:
            tag = f"  {r.get('latency_ms')} ms  {r.get('trace_id')}"
            if r.get("tenant") is not None:
                tag += f"  tenant={r['tenant']}"
            lines.append(tag)
            if r.get("waterfall"):
                for ln in _wf.render_waterfall(r["waterfall"]).splitlines():
                    lines.append("    " + ln)
    pm = rep.get("postmortems") or {}
    if pm.get("dir"):
        lines.append(f"postmortems: {pm['dir']} "
                     f"({len(pm.get('bundles') or [])} bundle(s), "
                     f"keep {pm.get('keep')})")
        for b in pm.get("bundles") or []:
            lines.append(f"  {b.get('file')} ({b.get('bytes')} B, "
                         f"{b.get('modified_at')})")
    return "\n".join(lines) + "\n"
