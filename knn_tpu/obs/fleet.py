"""Fleet observability plane: N processes' telemetry merged into ONE
cross-host report — jax-free, like every offline obs surface.

Every obs surface so far is per-process: ``/metrics.json`` snapshots one
registry, ``/statusz`` diagnoses one process, a waterfall reconstructs
one host's spans.  A multi-host replica (knn_tpu.parallel.multihost) is
N of those — and "what is the fleet's p99" is NOT answerable from N
per-process p99s (percentiles do not average; the mean of two p99s is a
number with no operational meaning).  This module is the sound merge:

- **counters sum.**  Lifetime monotone counts add across processes —
  the fleet served ``sum(requests)`` requests, full stop.  Members are
  summed in sorted key order, so the same member set always produces
  the bitwise-identical total.
- **gauges keep their host.**  A queue depth averaged across hosts is
  fiction; the fleet report keeps every gauge PER HOST plus min / max /
  argmax rollups, so "which host" survives the merge.
- **quantiles merge through buckets, never through percentiles.**
  Every histogram exports cumulative counts over the ONE fixed
  ``registry.BUCKET_BOUNDS`` grid; identical bounds in every process
  means the cumulative vectors add element-wise, and the fleet
  quantile is taken from the SUM (``registry.quantile_from_buckets`` —
  a sound upper estimate).  The per-host window quantiles are carried
  too, labeled per host; they are never combined.

Collection reads live ``/metrics.json`` + ``/statusz`` (+
``/waterfallz`` for stitched cross-host waterfalls) from the
``KNN_TPU_FLEET_MEMBERS`` host:port list, or offline snapshot files
written by ``export.write_json_snapshot`` (``cli fleet
--snapshot-dir``).  Every payload is keyed by its identity stamp
(knn_tpu.obs.ident).

Degraded modes are LOUD, never silently narrower numbers:

- an unreachable endpoint / unreadable or torn snapshot lists the
  member under ``unreachable`` with the reason;
- a snapshot older than the newest by more than ``KNN_TPU_FLEET_STALE_S``
  seconds is refused as stale (an older collection round summed in
  would silently understate every counter) and listed under
  ``unreachable`` with a ``stale`` reason;
- a member whose ``catalog_version`` differs from ours is refused
  under ``skewed`` — summing a counter whose meaning changed between
  catalog versions would silently produce nonsense;
- any of these flips ``partial`` true; ``cli fleet`` exits 2 on a
  partial fleet.

Fleet SLO: the merged counters/buckets feed
``slo.FleetSLOEngine`` (lifetime ratios; quantiles ONLY from merged
buckets).  Edge-triggered fleet alerts write a postmortem bundle
embedding EVERY member's snapshot plus the stitched cross-host
waterfalls, next to the per-process bundles (knn_tpu.obs.blackbox).

Served by ``/fleetz`` (knn_tpu.obs.export) and ``python -m knn_tpu.cli
fleet``.  Schema: docs/OBSERVABILITY.md "Fleet observability".
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Sequence, Tuple

from knn_tpu.obs import names, registry, trace

#: comma/space-separated ``host:port`` list of member metric endpoints
MEMBERS_ENV = "KNN_TPU_FLEET_MEMBERS"

#: refuse members whose snapshot is older than the newest by more than
#: this many seconds (an older collection round merged in would
#: silently understate the fleet)
STALE_ENV = "KNN_TPU_FLEET_STALE_S"
DEFAULT_STALE_S = 120.0

#: per-member HTTP timeout for live collection
DEFAULT_TIMEOUT_S = 3.0

#: fleet report schema version (the ``fleet`` artifact block pins it)
FLEET_VERSION = 1

_QS = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))


def fleet_members() -> List[str]:
    """The configured member endpoints (``KNN_TPU_FLEET_MEMBERS``)."""
    raw = os.environ.get(MEMBERS_ENV, "")
    return [m for m in re.split(r"[,\s]+", raw) if m]


def stale_threshold_s() -> float:
    try:
        return float(os.environ.get(STALE_ENV, DEFAULT_STALE_S))
    except ValueError:
        return DEFAULT_STALE_S


# -- collection ------------------------------------------------------------
def _http_json(url: str, timeout_s: float) -> dict:
    with urllib.request.urlopen(url, timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def _member_record(member: str, *, identity=None, metrics=None,
                   health=None, written_at_unix=None, stitched=None,
                   error: Optional[str] = None) -> dict:
    return {"member": member, "identity": identity or {},
            "metrics": metrics or {}, "health": health,
            "written_at_unix": written_at_unix, "stitched": stitched,
            "error": error}


def collect_live(members: Sequence[str],
                 timeout_s: float = DEFAULT_TIMEOUT_S) -> List[dict]:
    """One record per configured endpoint: ``/metrics.json`` (identity +
    metrics), ``/statusz`` (health, incl. the multihost section), and
    best-effort ``/waterfallz`` (stitched cross-host waterfalls).  A
    failing member degrades to an ``error`` record — collection never
    raises on an unreachable fleet."""
    out = []
    for m in members:
        base = m if "://" in m else f"http://{m}"
        try:
            snap = _http_json(base + "/metrics.json", timeout_s)
            if not isinstance(snap, dict) or "metrics" not in snap:
                raise ValueError("no metrics section in /metrics.json")
        except Exception as e:  # noqa: BLE001 — degrade, never raise
            out.append(_member_record(
                m, error=f"{type(e).__name__}: {e}"))
            continue
        health = stitched = None
        try:
            health = _http_json(base + "/statusz", timeout_s)
        except Exception:  # noqa: BLE001 — statusz is best-effort
            pass
        try:
            wf = _http_json(base + "/waterfallz", timeout_s)
            stitched = (wf.get("multihost") or {}).get("waterfalls")
        except Exception:  # noqa: BLE001 — waterfalls are best-effort
            pass
        out.append(_member_record(
            m, identity=snap.get("identity"), metrics=snap["metrics"],
            health=health, written_at_unix=snap.get("written_at_unix"),
            stitched=stitched))
    return out


def collect_snapshot_files(paths: Sequence[str]) -> List[dict]:
    """One record per snapshot file (``export.write_json_snapshot``
    payloads).  Unreadable / torn / shapeless files degrade to
    ``error`` records — the merge lists them loudly instead of summing
    a partial fleet silently."""
    out = []
    for p in paths:
        try:
            with open(p) as f:
                payload = json.load(f)
            if not isinstance(payload, dict) or "metrics" not in payload:
                raise ValueError("not a metrics snapshot (no metrics)")
        except Exception as e:  # noqa: BLE001 — degrade, never raise
            out.append(_member_record(
                os.path.basename(p), error=f"{type(e).__name__}: {e}"))
            continue
        out.append(_member_record(
            os.path.basename(p), identity=payload.get("identity"),
            metrics=payload["metrics"], health=payload.get("health"),
            written_at_unix=payload.get("written_at_unix")))
    return out


def collect_snapshot_dir(d: str) -> Tuple[List[dict], Dict[str, dict]]:
    """Offline collection from a directory: every ``*.json`` is a member
    snapshot; every ``*.jsonl`` (+ rotated ``.jsonl.1``) is an event log
    whose ``multihost.merge`` spans are stitched into cross-host
    waterfalls (knn_tpu.obs.waterfall.stitch_multihost)."""
    from knn_tpu.obs import waterfall

    snaps = sorted(f for f in os.listdir(d) if f.endswith(".json"))
    members = collect_snapshot_files(
        [os.path.join(d, f) for f in snaps])
    events: List[dict] = []
    for f in sorted(os.listdir(d)):
        if f.endswith(".jsonl"):
            try:
                events.extend(
                    waterfall.read_jsonl_events(os.path.join(d, f)))
            except Exception:  # noqa: BLE001 — logs are best-effort
                pass
    return members, waterfall.stitch_multihost(events)


# -- the merge -------------------------------------------------------------
def _member_key(rec: dict) -> str:
    ident = rec.get("identity") or {}
    host = ident.get("host")
    if host is not None:
        return f"{host}/{ident.get('process_index', 0)}"
    return str(rec["member"])


def merge(collected: Sequence[dict], *,
          stale_s: Optional[float] = None,
          stitched: Optional[Dict[str, dict]] = None) -> dict:
    """The fleet report over collected member records (module
    docstring).  Publishes the ``knn_tpu_fleet_*`` gauges when
    telemetry is on."""
    stale_s = stale_threshold_s() if stale_s is None else float(stale_s)
    ours = names.catalog_version()
    unreachable: List[dict] = []
    skewed: List[dict] = []
    ok: List[Tuple[str, dict]] = []
    for rec in collected:
        if rec.get("error"):
            unreachable.append(
                {"member": rec["member"], "reason": rec["error"]})
            continue
        cv = (rec.get("identity") or {}).get("catalog_version")
        if cv is not None and cv != ours:
            skewed.append({"member": rec["member"],
                           "catalog_version": cv, "expected": ours})
            continue
        ok.append((_member_key(rec), rec))
    # duplicate keys (two snapshots of one process) keep the newest
    by_key: Dict[str, dict] = {}
    for key, rec in ok:
        prev = by_key.get(key)
        if prev is None or ((rec.get("written_at_unix") or 0)
                            >= (prev.get("written_at_unix") or 0)):
            by_key[key] = rec
    # stale refusal: a member more than stale_s older than the newest
    # is a different collection round — summing it in would silently
    # understate every counter
    stamps = {k: r["written_at_unix"] for k, r in by_key.items()
              if r.get("written_at_unix") is not None}
    staleness = (round(max(stamps.values()) - min(stamps.values()), 3)
                 if stamps else 0.0)
    if stamps:
        newest = max(stamps.values())
        for k in sorted(by_key):
            ts = stamps.get(k)
            if ts is not None and newest - ts > stale_s:
                unreachable.append({
                    "member": by_key[k]["member"],
                    "reason": (f"stale snapshot: {round(newest - ts, 3)}s "
                               f"older than the newest member "
                               f"(threshold {stale_s}s)")})
                del by_key[k]
        stamps = {k: v for k, v in stamps.items() if k in by_key}
        staleness = (round(max(stamps.values()) - min(stamps.values()), 3)
                     if stamps else 0.0)
    keys = sorted(by_key)  # deterministic merge order
    counters, gauges, hists = _merge_metrics(keys, by_key)
    wfs = dict(stitched or {})
    for k in keys:
        for tid, w in (by_key[k].get("stitched") or {}).items():
            prev = wfs.get(tid)
            if prev is None or ((w.get("total_s") or 0)
                                > (prev.get("total_s") or 0)):
                wfs[tid] = w
    mh = _merge_multihost(keys, by_key)
    partial = bool(unreachable or skewed)
    # the FULL report is a superset of the validated `fleet` artifact
    # block — artifact_block() is the schema's emitter, so the version
    # stamp rides outside this literal
    report = {
        "generated_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "enabled": True,
        "catalog_version": ours,
        "partial": partial,
        "member_count": len(keys),
        "expected": len(collected),
        "members": [{
            "key": k,
            "member": by_key[k]["member"],
            "identity": by_key[k].get("identity") or {},
            "written_at_unix": by_key[k].get("written_at_unix"),
        } for k in keys],
        "unreachable": unreachable,
        "skewed": skewed,
        "staleness_s": staleness,
        "counters": counters,
        "gauges": gauges,
        "histograms": hists,
        "multihost": mh,
        # cross-host waterfalls stitched from multihost.merge spans
        "waterfalls": wfs or None,
    }
    report["fleet_version"] = FLEET_VERSION
    from knn_tpu.obs import slo

    report["slo"] = slo.evaluate_fleet(counters, hists)
    _publish_gauges(report)
    return report


def _merge_metrics(keys, by_key):
    """counters sum / gauges keep-per-host / histograms bucket-merge —
    the one place the three instrument kinds' merge semantics live."""
    counters: Dict[str, list] = {}
    gauges: Dict[str, list] = {}
    hists: Dict[str, list] = {}
    # (name, sorted-labels) -> {member key: series value}
    series: Dict[Tuple[str, tuple], Dict[str, dict]] = {}
    kinds: Dict[str, str] = {}
    for k in keys:
        for name, m in (by_key[k].get("metrics") or {}).items():
            kinds[name] = m.get("type", "gauge")
            for s in m.get("series", ()):
                lk = (name, tuple(sorted(s["labels"].items())))
                series.setdefault(lk, {})[k] = s
    for (name, litems) in sorted(series):
        labels = dict(litems)
        per = series[(name, litems)]
        kind = kinds[name]
        if kind == "counter":
            per_host = {k: float(per[k]["value"]) for k in sorted(per)}
            counters.setdefault(name, []).append({
                "labels": labels,
                # sorted-key order: the same member set always sums to
                # the bitwise-identical total
                "value": sum(per_host[k] for k in sorted(per_host)),
                "per_host": per_host,
            })
        elif kind == "gauge":
            per_host = {k: float(per[k]["value"]) for k in sorted(per)}
            argmax = max(sorted(per_host), key=lambda k: per_host[k])
            gauges.setdefault(name, []).append({
                "labels": labels,
                "per_host": per_host,
                "min": min(per_host.values()),
                "max": per_host[argmax],
                "argmax": argmax,
            })
        else:  # histogram
            hists.setdefault(name, []).append(
                _merge_hist_series(labels, per))
    return counters, gauges, hists


def _merge_hist_series(labels: dict, per: Dict[str, dict]) -> dict:
    """One histogram label-series across members: lifetime count/sum
    add; cumulative bucket vectors add element-wise (identical
    ``registry.BUCKET_BOUNDS`` in every process — catalog-version
    skew is refused before we get here); the FLEET quantiles come from
    the merged vector ONLY.  The per-host window quantiles ride along
    labeled by host — they are never combined (max-of-quantiles is the
    single-process conservative read in slo._hist_summary; across a
    fleet it would overstate every host but the worst)."""
    merged_cum: Optional[List[float]] = None
    window: Dict[str, dict] = {}
    count = 0.0
    total = 0.0
    for k in sorted(per):
        v = per[k]["value"]
        count += float(v.get("count", 0))
        total += float(v.get("sum", 0.0))
        cum = v.get("buckets")
        if cum:
            merged_cum = (list(cum) if merged_cum is None
                          else [a + b for a, b in zip(merged_cum, cum)])
        window[k] = {q: v[q] for q, _ in _QS if q in v}
        if "count" in v:
            window[k]["count"] = v["count"]
    fleet_q = None
    if merged_cum is not None:
        fleet_q = {q: registry.quantile_from_buckets(merged_cum, frac)
                   for q, frac in _QS}
        fleet_q["source"] = "merged_buckets"
    return {"labels": labels, "count": count, "sum": round(total, 9),
            "buckets": merged_cum, "fleet_quantiles": fleet_q,
            "window_quantiles_per_host": window}


def _merge_multihost(keys, by_key) -> Optional[dict]:
    """The fleet's straggler verdict from the members' /statusz
    multihost sections: name the argmax host (by its last DCN-merge
    local wall) instead of reporting one max-minus-min scalar."""
    sections = {}
    for k in keys:
        mh = (by_key[k].get("health") or {}).get("multihost")
        if mh:
            sections[k] = mh
    if not sections:
        return None
    # the authoritative section: every process records the same walls,
    # so any one suffices — take the newest-stamped member's
    auth_key = max(sorted(sections),
                   key=lambda k: by_key[k].get("written_at_unix") or 0)
    auth = dict(sections[auth_key])
    walls = auth.get("host_walls_s") or []
    straggler = auth.get("straggler_host")
    if straggler is None and walls:
        straggler = max(range(len(walls)), key=lambda i: walls[i])
    # map the straggler process index back to a member key when one of
    # the merged members IS that process
    straggler_key = None
    for k in keys:
        ident = by_key[k].get("identity") or {}
        if ident.get("process_index") == straggler:
            straggler_key = k
            break
    return {
        "reported_by": auth_key,
        "host_walls_s": walls,
        "straggler_host": straggler,
        "straggler_member": straggler_key,
        "straggler_gap_s": auth.get("straggler_gap_s"),
        "per_member": sections,
    }


def _publish_gauges(report: dict) -> None:
    if not registry.enabled():
        return
    registry.gauge(names.FLEET_MEMBERS).set(float(report["member_count"]))
    registry.gauge(names.FLEET_UNREACHABLE).set(
        float(len(report["unreachable"]) + len(report["skewed"])))
    registry.gauge(names.FLEET_MERGE_STALENESS).set(
        float(report["staleness_s"]))
    mh = report.get("multihost") or {}
    straggler_key = mh.get("straggler_member")
    if straggler_key is not None:
        for m in report["members"]:
            registry.gauge(names.FLEET_STRAGGLER_HOST,
                           host=m["key"]).set(
                1.0 if m["key"] == straggler_key else 0.0)


# -- fleet SLO edge + postmortems ------------------------------------------
_engine_lock = threading.Lock()
_engine = None


def _get_fleet_engine():
    global _engine
    with _engine_lock:
        if _engine is None:
            from knn_tpu.obs import slo

            _engine = slo.FleetSLOEngine()
        return _engine


def reset_fleet_engine() -> None:
    """Drop the edge state (tests)."""
    global _engine
    with _engine_lock:
        _engine = None


def observe(report: dict, collected: Sequence[dict]) -> None:
    """Feed one merged report through the edge-triggered fleet SLO
    engine; each healthy->breached transition emits one ``fleet.alert``
    event and writes one fleet postmortem bundle embedding EVERY
    member's snapshot plus the stitched cross-host waterfalls."""
    fired = _get_fleet_engine().observe(report.get("slo") or {})
    for key, detail in fired:
        trace.emit_event("fleet.alert", objective=key, state="firing",
                         **{k: v for k, v in detail.items()
                            if k != "state"
                            and isinstance(v, (int, float, str, bool))})
        _write_fleet_bundle(key, detail, report, collected)


def _write_fleet_bundle(objective: str, detail: dict, report: dict,
                        collected: Sequence[dict]) -> Optional[str]:
    """One fleet postmortem bundle per firing transition, next to the
    per-process bundles (same dir, same retention, ``fleet_`` objective
    prefix in the filename) — atomic, failure-proof."""
    from knn_tpu.obs import blackbox

    d = blackbox.postmortem_dir()
    if d is None or not registry.enabled():
        return None
    try:
        payload = {
            "version": blackbox.BUNDLE_VERSION,
            "kind": "fleet",
            "written_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "pid": os.getpid(),
            "objective": objective,
            "state": "firing",
            "breach_detail": detail,
            "fleet": report,
            # every member's raw collection record: the per-host truth
            # behind the merged numbers
            "members": {str(rec["member"]): {
                "identity": rec.get("identity"),
                "metrics": rec.get("metrics"),
                "health": rec.get("health"),
                "written_at_unix": rec.get("written_at_unix"),
                "error": rec.get("error"),
            } for rec in collected},
            "waterfalls": report.get("waterfalls"),
        }
        os.makedirs(d, exist_ok=True)
        safe = re.sub(r"[^A-Za-z0-9_.-]", "_", objective)[:56]
        fname = (f"postmortem-"
                 f"{time.strftime('%Y%m%dT%H%M%S', time.gmtime())}"
                 f"-0000-fleet_{safe}.json")
        path = os.path.join(d, fname)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, sort_keys=True, default=str)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return path
    except Exception as e:  # noqa: BLE001 — recorder must never raise
        try:
            trace.emit_event("postmortem.error", objective=objective,
                             error=f"{type(e).__name__}: {e}")
        except Exception:  # pragma: no cover - double fault
            pass
        return None


# -- entry points ----------------------------------------------------------
def fleet_report(members: Optional[Sequence[str]] = None, *,
                 snapshot_dir: Optional[str] = None,
                 snapshot_files: Optional[Sequence[str]] = None,
                 timeout_s: float = DEFAULT_TIMEOUT_S,
                 stale_s: Optional[float] = None) -> dict:
    """Collect + merge + edge-evaluate, one call: live endpoints
    (``members``, default ``KNN_TPU_FLEET_MEMBERS``) or offline
    snapshots (``snapshot_dir`` / ``snapshot_files``)."""
    stitched: Dict[str, dict] = {}
    if snapshot_dir is not None:
        collected, stitched = collect_snapshot_dir(snapshot_dir)
    elif snapshot_files is not None:
        collected = collect_snapshot_files(snapshot_files)
    else:
        members = fleet_members() if members is None else list(members)
        if not members:
            return {"enabled": False, "fleet_version": FLEET_VERSION,
                    "reason": f"{MEMBERS_ENV} not set and no snapshot "
                              f"source given"}
        collected = collect_live(members, timeout_s)
    report = merge(collected, stale_s=stale_s, stitched=stitched)
    observe(report, collected)
    return report


def live_fleet_report() -> dict:
    """What ``/fleetz`` serves: the merged report over
    ``KNN_TPU_FLEET_MEMBERS``, or a loud disabled/unconfigured stub.
    ``KNN_TPU_OBS=0`` turns the whole plane off — no collection, no
    merge, no gauges."""
    if not registry.enabled():
        return {"enabled": False, "fleet_version": FLEET_VERSION,
                "reason": "telemetry disabled (KNN_TPU_OBS=0)"}
    if not fleet_members():
        return {"enabled": False, "fleet_version": FLEET_VERSION,
                "reason": f"{MEMBERS_ENV} not set"}
    return fleet_report()


def artifact_block(report: dict) -> dict:
    """The validated ``fleet`` artifact block (one BlockSchema entry in
    knn_tpu/analysis/artifacts.py drives validator and docs
    lockstep): the merged report's flat, bounded headline shape."""
    if not report.get("enabled", True):
        return {"fleet_version": FLEET_VERSION,
                "member_count": 0,
                "error": report.get("reason")}
    mh = report.get("multihost") or {}
    return {
        "fleet_version": FLEET_VERSION,
        "catalog_version": report["catalog_version"],
        "member_count": report["member_count"],
        "expected_members": report["expected"],
        "unreachable_count": len(report["unreachable"]),
        "skewed_count": len(report["skewed"]),
        "partial": report["partial"],
        "staleness_s": report["staleness_s"],
        "straggler_host": mh.get("straggler_host"),
        "straggler_gap_s": mh.get("straggler_gap_s"),
        "stitched_requests": len(report.get("waterfalls") or {}),
        "slo_breached": len((report.get("slo") or {}).get("breached")
                            or ()),
    }


def render_text(report: dict) -> str:
    """The ``cli fleet`` text rendering (jax-free, offline-capable)."""
    if not report.get("enabled", True):
        return f"fleet: disabled ({report.get('reason')})"
    lines = [
        f"fleet report v{report['fleet_version']} "
        f"@ {report['generated_at']}  catalog {report['catalog_version']}",
        f"  members merged: {report['member_count']}/{report['expected']}"
        + ("  PARTIAL" if report["partial"] else "")
        + f"  staleness {report['staleness_s']}s",
    ]
    for m in report["members"]:
        ident = m["identity"]
        lines.append(
            f"    {m['key']}  ({m['member']}, "
            f"process {ident.get('process_index')}/"
            f"{ident.get('process_count')}, "
            f"device {ident.get('device_kind')})")
    for u in report["unreachable"]:
        lines.append(f"  UNREACHABLE {u['member']}: {u['reason']}")
    for s in report["skewed"]:
        lines.append(
            f"  SKEWED {s['member']}: catalog {s['catalog_version']} "
            f"!= expected {s['expected']}")
    mh = report.get("multihost")
    if mh:
        lines.append(
            f"  multihost: straggler host{mh.get('straggler_host')}"
            f" ({mh.get('straggler_member')})"
            f" gap {mh.get('straggler_gap_s')}s"
            f" walls {mh.get('host_walls_s')}")
    slo_rep = report.get("slo") or {}
    for key in sorted(slo_rep.get("objectives", {})):
        o = slo_rep["objectives"][key]
        lines.append(
            f"  slo {key}: {o.get('state', '?')}"
            f"  value={o.get('value')}"
            + (f"  fleet_{o.get('quantile')}={o.get('value')}"
               f" (merged buckets)" if o.get("kind") == "quantile"
               else ""))
    counters = report.get("counters", {})
    for name in sorted(counters):
        for s in counters[name]:
            lbl = ",".join(f"{k}={v}"
                           for k, v in sorted(s["labels"].items()))
            lines.append(
                f"  {name}{{{lbl}}} = {s['value']}  "
                f"(sum of {len(s['per_host'])} member(s))")
    hists = report.get("histograms", {})
    for name in sorted(hists):
        for s in hists[name]:
            fq = s.get("fleet_quantiles")
            if not fq:
                continue
            lbl = ",".join(f"{k}={v}"
                           for k, v in sorted(s["labels"].items()))
            lines.append(
                f"  {name}{{{lbl}}} fleet p50/p95/p99 = "
                f"{fq['p50']}/{fq['p95']}/{fq['p99']} "
                f"(merged buckets, n={int(s['count'])})")
    wfs = report.get("waterfalls")
    if wfs:
        from knn_tpu.obs import waterfall

        lines.append(f"  stitched cross-host waterfalls: {len(wfs)}")
        worst = max(wfs.values(),
                    key=lambda w: w.get("total_s") or 0.0)
        lines.extend("  " + ln for ln in
                     waterfall.render_waterfall(worst).splitlines())
    return "\n".join(lines)
