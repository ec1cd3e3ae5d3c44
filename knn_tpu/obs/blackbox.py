"""Flight recorder: on every edge-triggered SLO breach, capture ONE
postmortem bundle — the forensic state an operator would have wanted
logging on for, written at the moment the breach fires instead.

The SLO engine (knn_tpu.obs.slo) is edge-triggered: each
healthy->breached transition emits exactly one firing alert.  This
module rides that edge — :func:`on_breach` is invoked once per firing
transition (AFTER the engine's evaluation lock is released) and writes
one bounded bundle to ``KNN_TPU_POSTMORTEM_DIR``:

- the structured event ring (every span/event still held in memory —
  the raw material the waterfalls reconstruct from),
- the full metrics snapshot and the /statusz self-diagnosis report
  (built from the SAME evaluation pass that fired — no re-evaluation,
  no second transition),
- the slowest-requests exemplar table with their inline waterfalls,
  plus the critical-path attribution over every reconstructable
  request,
- the SLO report and the breach detail that fired,
- the telemetry-relevant environment (``KNN_TPU_*`` knobs), pid, and a schema version.

Disciplines:

- **at most one bundle per breach transition** — the caller is the
  edge, and a re-evaluated still-breached objective never calls here;
- **atomic** — tmp + ``os.replace``, the tune-cache/snapshot rule, so
  a reader never sees a torn bundle;
- **retention-capped** — ``KNN_TPU_POSTMORTEM_KEEP`` (default 8)
  newest bundles survive; older ones are pruned after each write, so a
  flapping objective cannot fill a disk;
- **failure-proof** — everything is wrapped: a full disk or unwritable
  directory degrades to a ``postmortem.error`` event, never an
  exception into the stats()/scrape path that ran the evaluation;
- **off by default** — no ``KNN_TPU_POSTMORTEM_DIR`` (or
  ``KNN_TPU_OBS=0``) means no work at all: one env lookup per
  transition, nothing else.

Bundles are plain JSON, readable offline by the jax-free
``python -m knn_tpu.cli waterfall --bundle <path>`` and listed in
``/statusz`` (``postmortems`` section).  Schema: docs/OBSERVABILITY.md
"Flight recorder / postmortems".
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
from typing import List, Optional

from knn_tpu.obs import names, registry, trace

#: directory bundles land in; unset = flight recorder disabled
DIR_ENV = "KNN_TPU_POSTMORTEM_DIR"

#: how many bundles survive pruning (newest kept)
KEEP_ENV = "KNN_TPU_POSTMORTEM_KEEP"
DEFAULT_KEEP = 8

#: bundle schema version (bump on shape changes so offline readers can
#: tell a malformed bundle from an old one)
BUNDLE_VERSION = 1

_FNAME_RE = re.compile(r"^postmortem-\d{8}T\d{6}-\d{4}-.*\.json$")

_seq_lock = threading.Lock()
_seq = 0
#: reentrancy guard: building a bundle reads health/waterfall state
#: that may itself evaluate metrics — a nested transition during the
#: dump must not recurse into a second dump on the same thread
_busy = threading.local()


def postmortem_dir() -> Optional[str]:
    return os.environ.get(DIR_ENV) or None


def keep_count() -> int:
    try:
        return max(1, int(os.environ.get(KEEP_ENV, DEFAULT_KEEP)))
    except ValueError:
        return DEFAULT_KEEP


def enabled() -> bool:
    """Recorder armed: a destination is configured AND telemetry is on
    (the bundle is nothing but telemetry; KNN_TPU_OBS=0 disarms it like
    every other obs surface)."""
    return postmortem_dir() is not None and registry.enabled()


def on_breach(objective: str, detail: dict,
              slo_report: Optional[dict] = None) -> Optional[str]:
    """The SLO engine's edge hook: write one bundle for this firing
    transition.  Returns the bundle path (None when disabled, busy, or
    the write failed — failures degrade to a ``postmortem.error``
    event, never an exception into the evaluating caller)."""
    if not enabled():
        return None
    if getattr(_busy, "v", False):
        return None
    _busy.v = True
    try:
        path = _write_bundle(objective, detail, slo_report)
        registry.counter(names.POSTMORTEMS_WRITTEN,
                         objective=objective).inc()
        trace.emit_event("postmortem.write", objective=objective,
                         path=path)
        return path
    except Exception as e:  # noqa: BLE001 — recorder must never raise
        try:
            trace.emit_event("postmortem.error", objective=objective,
                             error=f"{type(e).__name__}: {e}")
        except Exception:  # pragma: no cover - double fault
            pass
        return None
    finally:
        _busy.v = False


def _audit_evidence() -> Optional[dict]:
    """The audit sampler's evidence section, failure-proof: a broken
    audit layer must not take the flight recorder down with it."""
    try:
        from knn_tpu.obs import audit

        return audit.get_auditor().evidence()
    except Exception as e:  # noqa: BLE001 — recorder must never raise
        return {"error": f"{type(e).__name__}: {e}"}


def _write_bundle(objective: str, detail: dict,
                  slo_report: Optional[dict]) -> str:
    global _seq
    from knn_tpu.obs import health, waterfall

    d = postmortem_dir()
    os.makedirs(d, exist_ok=True)
    events = trace.get_event_log().recent()
    wfs = waterfall.reconstruct(events)
    slowest = waterfall.slowest_table(events=events, waterfalls=wfs)
    payload = {
        "version": BUNDLE_VERSION,
        "written_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "pid": os.getpid(),
        "objective": objective,
        "state": "firing",
        "breach_detail": detail,
        "slo": slo_report,
        # the statusz report REUSES the evaluation pass that fired
        # (slo_section=...) — a re-evaluation here could observe and
        # fire a second transition mid-dump — and the slowest table
        # built above, so the ring is reconstructed once, not twice
        "statusz": health.report(slo_section=slo_report,
                                 slowest=slowest),
        "metrics": registry.snapshot(),
        "events": events,
        "slowest": slowest,
        "attribution": waterfall.attribute(wfs),
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(("KNN_TPU_", "JAX_PLATFORMS"))},
        # the shadow audit sampler's evidence: summary + the bounded
        # ring of failing audit records — for a quality-SLO breach
        # this IS the postmortem (which requests served wrong answers,
        # vs what the oracle says)
        "audit": _audit_evidence(),
    }
    with _seq_lock:
        _seq += 1
        seq = _seq
    safe_obj = re.sub(r"[^A-Za-z0-9_.-]", "_", objective)[:64]
    fname = (f"postmortem-{time.strftime('%Y%m%dT%H%M%S', time.gmtime())}"
             f"-{seq:04d}-{safe_obj}.json")
    path = os.path.join(d, fname)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, sort_keys=True, default=str)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    _prune(d)
    return path


def _bundles_in(d: str) -> List[str]:
    try:
        entries = os.listdir(d)
    except OSError:
        return []
    # timestamp-then-sequence filenames sort chronologically
    return sorted(f for f in entries if _FNAME_RE.match(f))


def _prune(d: str) -> None:
    keep = keep_count()
    bundles = _bundles_in(d)
    for f in bundles[:-keep] if len(bundles) > keep else []:
        try:
            os.unlink(os.path.join(d, f))
        except OSError:  # pragma: no cover - racing reader/cleaner
            pass


def status() -> dict:
    """The ``/statusz`` ``postmortems`` section: where bundles go, how
    many survive pruning, and what is on disk right now."""
    d = postmortem_dir()
    out: dict = {"dir": d, "keep": keep_count(), "bundles": []}
    if d is None:
        return out
    for f in _bundles_in(d):
        p = os.path.join(d, f)
        try:
            st = os.stat(p)
        except OSError:
            continue
        out["bundles"].append({
            "file": f,
            "bytes": int(st.st_size),
            "modified_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime(st.st_mtime)),
        })
    return out


def read_bundle(path: str) -> dict:
    """Load + structurally sanity-check a bundle (offline readers)."""
    with open(path) as f:
        payload = json.load(f)
    if not isinstance(payload, dict) or "version" not in payload:
        raise ValueError(f"{path}: not a postmortem bundle (no version)")
    return payload
