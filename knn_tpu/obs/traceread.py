"""Jax-free reader for captured profiler artifacts — the parsing half
of the measured-ceiling loop (ROADMAP open item 1).

PR 6 built trace *capture* (:mod:`knn_tpu.obs.profiler` wraps
``jax.profiler.trace`` and writes a TensorBoard-loadable artifact under
``<dir>/<section>``); nothing in the repo could *read* one back.  This
module parses the two measured-time sources the calibration layer
(:mod:`knn_tpu.obs.calibrate`) reconciles against the roofline model:

- **device traces** — the trace-viewer ``*.trace.json.gz`` event
  stream the profiler leaves under
  ``<section>/plugins/profile/<run>/*.trace.json.gz``: gzipped Chrome
  trace JSON whose ``ph == "M"`` metadata events name each pid's track
  (``/device:TPU:0 ...``) and whose ``ph == "X"`` complete events carry
  per-kernel ``ts``/``dur`` in microseconds.  Device busy time is the
  INTERVAL UNION of the device tracks' complete events (two kernels
  overlapping on one track must not double-bill), so the sample is the
  chip's measured wall occupancy, directly comparable to the model's
  per-sweep term times.
- **host-side phase records** — a ``phase_breakdown`` block
  (``device_s`` measured by fenced ``perf_counter`` around
  the already-compiled program) and the waterfall's device segments.
  CPU-testable: tier-1 exercises the identical reconcile loop against
  these without a TPU.

Event→config matching rides the capture convention: the profiler
writes each capture under its SANITIZED section name (the tuning
cache key), so :func:`read_section` resolves a section back to
its artifact — a trace can never be reconciled against a config that
did not produce it.  Malformed artifacts raise :class:`TraceReadError`
LOUDLY (a silently-empty trace would calibrate the model against
nothing and call it measured).

Everything here is stdlib-only: gzip + json + glob.  No JAX import,
ever — the offline doctor parses
on machines with no accelerator runtime.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Tuple

#: trace-viewer artifact glob under a capture directory (the layout
#: ``jax.profiler.trace`` writes: plugins/profile/<run>/<host>.trace.json.gz)
TRACE_GLOB = os.path.join("**", "*.trace.json.gz")

#: substrings that mark a metadata-named pid track as a DEVICE track
#: (XLA names them "/device:TPU:0", "/device:GPU:0", "TPU:0 (chip …)")
DEVICE_TRACK_MARKERS = ("/device:", "TPU", "GPU")

#: the two measured-time sources the reconciler accepts
SOURCES = ("device_trace", "host_phase")


class TraceReadError(ValueError):
    """A profiler artifact that cannot be parsed into a measured
    sample — raised LOUDLY: a malformed trace must never calibrate."""


def find_trace_files(root: str) -> List[str]:
    """Every ``*.trace.json.gz`` under ``root`` (sorted), or ``root``
    itself when it already names one.  Empty list when the directory
    exists but holds no artifact (the caller decides whether that is an
    error); :class:`TraceReadError` when ``root`` does not exist."""
    if os.path.isfile(root):
        return [root]
    if not os.path.isdir(root):
        raise TraceReadError(f"trace location {root!r} does not exist")
    return sorted(glob.glob(os.path.join(root, TRACE_GLOB),
                            recursive=True))


def read_trace_events(path: str) -> List[dict]:
    """The ``traceEvents`` list of one trace-viewer artifact.  Accepts
    gzipped or plain JSON; everything malformed raises
    :class:`TraceReadError` with the reason."""
    try:
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            doc = json.load(f)
    except OSError as e:
        raise TraceReadError(f"{path}: unreadable: {e}") from e
    except (json.JSONDecodeError, UnicodeDecodeError, EOFError) as e:
        raise TraceReadError(
            f"{path}: not trace-viewer JSON: {e}") from e
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        raise TraceReadError(
            f"{path}: no traceEvents list — not a trace-viewer "
            f"artifact")
    return doc["traceEvents"]


def process_names(events: List[dict]) -> Dict[int, str]:
    """pid -> track name from the ``ph == "M"`` ``process_name``
    metadata events."""
    out: Dict[int, str] = {}
    for e in events:
        if not isinstance(e, dict):
            continue
        if e.get("ph") == "M" and e.get("name") == "process_name":
            args = e.get("args") or {}
            name = args.get("name")
            pid = e.get("pid")
            if isinstance(pid, int) and isinstance(name, str):
                out[pid] = name
    return out


def device_pids(events: List[dict]) -> Dict[int, str]:
    """The pids whose metadata track name looks like a DEVICE track
    (:data:`DEVICE_TRACK_MARKERS`).  Empty on host-only traces (CPU
    captures have no device lanes — the caller falls back to all
    tracks, flagged)."""
    return {pid: name for pid, name in process_names(events).items()
            if any(m in name for m in DEVICE_TRACK_MARKERS)}


def complete_events(events: List[dict],
                    pids: Optional[set] = None) -> List[dict]:
    """The ``ph == "X"`` complete events (the per-kernel ts/dur
    samples), optionally restricted to ``pids``."""
    out = []
    for e in events:
        if not isinstance(e, dict) or e.get("ph") != "X":
            continue
        if not isinstance(e.get("ts"), (int, float)) or \
                not isinstance(e.get("dur"), (int, float)):
            continue
        if pids is not None and e.get("pid") not in pids:
            continue
        out.append(e)
    return out


def _interval_union_s(evts: List[dict]) -> float:
    """Seconds covered by the union of the events' [ts, ts+dur)
    microsecond intervals — overlapping kernels on one track bill
    once."""
    iv: List[Tuple[float, float]] = sorted(
        (float(e["ts"]), float(e["ts"]) + float(e["dur"]))
        for e in evts)
    total = 0.0
    cur_a = cur_b = None
    for a, b in iv:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total / 1e6


def summarize_events(events: List[dict]) -> dict:
    """One artifact's measured-time summary: device busy seconds (the
    busiest device track's interval union — the term the roofline's
    per-sweep times model), kernel-event count, and whether real device
    tracks were matched (host-only CPU traces fall back to every track,
    flagged ``device_tracks_matched: false`` so a calibration can say
    which fidelity it was fit from)."""
    dev = device_pids(events)
    matched = bool(dev)
    tracks = dev or {
        pid: name for pid, name in process_names(events).items()}
    per_track = {}
    for pid in tracks or {e.get("pid") for e in events
                          if isinstance(e, dict)}:
        evts = complete_events(events, pids={pid})
        if evts:
            per_track[pid] = {
                "name": tracks.get(pid, str(pid)),
                "events": len(evts),
                "busy_s": round(_interval_union_s(evts), 6),
            }
    if not per_track:
        raise TraceReadError(
            "trace holds no complete (ph=X) events on any track — "
            "nothing measured to reconcile against")
    busiest = max(per_track.values(), key=lambda t: t["busy_s"])
    return {
        "device_tracks_matched": matched,
        "tracks": per_track,
        "kernel_events": sum(t["events"] for t in per_track.values()),
        "device_busy_s": busiest["busy_s"],
        "busiest_track": busiest["name"],
    }


def read_section(base_dir: str, section: str) -> dict:
    """Parse the capture the profiler wrote for ``section`` under
    ``base_dir`` — the event→config match: the profiler's capture
    convention (``<dir>/<sanitized section>``) ties each artifact to
    the config label that produced it, so a section that never captured
    raises instead of silently matching another config's kernels.
    Returns the :func:`summarize_events` summary plus the artifact
    paths."""
    from knn_tpu.obs.profiler import sanitize_section

    sect = sanitize_section(section)
    root = os.path.join(base_dir, sect)
    files = find_trace_files(root)
    if not files:
        raise TraceReadError(
            f"capture dir {root!r} holds no *.trace.json.gz artifact "
            f"(profiler ran but the runtime wrote no trace?)")
    # one capture = one timestamped run dir (plugins/profile/<run>/,
    # one artifact per host inside it).  Re-running into the same base
    # dir leaves the older runs on disk — merging them would union
    # stale kernel intervals into the sample (disjoint ts epochs, so
    # busy times ADD) and calibrate against a measurement the machine
    # never produced.  Only the NEWEST run's files enter.
    by_run: Dict[str, List[str]] = {}
    for p in files:
        by_run.setdefault(os.path.dirname(p), []).append(p)
    runs_found = len(by_run)
    if runs_found > 1:
        newest = max(by_run, key=lambda r: (os.path.getmtime(r), r))
        files = sorted(by_run[newest])
    merged: List[dict] = []
    for path in files:
        merged.extend(read_trace_events(path))
    summary = summarize_events(merged)
    summary["section"] = sect
    summary["trace_files"] = files
    summary["runs_found"] = runs_found
    return summary


def sample_from_trace(base_dir: str, section: str, *, nq: int) -> dict:
    """A measured sample (the reconciler's input) from a captured
    device trace: ``device_s`` is the busiest device track's interval
    union over the traced sweep of ``nq`` queries."""
    summary = read_section(base_dir, section)
    dev_s = summary["device_busy_s"]
    if dev_s <= 0:
        raise TraceReadError(
            f"section {section!r}: zero device busy time in the trace")
    return {
        "source": "device_trace",
        "device_s": dev_s,
        "nq": int(nq),
        "qps": round(nq / dev_s, 2),
        "section": summary["section"],
        "trace_files": summary["trace_files"],
        "kernel_events": summary["kernel_events"],
        "device_tracks_matched": summary["device_tracks_matched"],
    }


def sample_from_phases(phase_breakdown: dict, *, nq: int) -> dict:
    """A measured sample from a host-side
    ``phase_breakdown`` — the CPU-testable fallback source.  Only the
    fenced ``device_s`` phase enters: the h2d/d2h phases are host-link
    time and never land in a device-term residual."""
    if not isinstance(phase_breakdown, dict):
        raise TraceReadError(
            f"phase_breakdown is {type(phase_breakdown).__name__}, "
            f"not dict")
    dev_s = phase_breakdown.get("device_s")
    if not isinstance(dev_s, (int, float)) or dev_s <= 0:
        raise TraceReadError(
            f"phase_breakdown carries no positive device_s "
            f"({dev_s!r}) — nothing measured to reconcile against")
    return {
        "source": "host_phase",
        "device_s": float(dev_s),
        "nq": int(nq),
        "qps": round(nq / float(dev_s), 2),
    }
