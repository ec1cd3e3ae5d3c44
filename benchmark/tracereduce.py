"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, time in the operations a pattern names,
the operations that took most time, and the device's idle time by what
the host was doing at each moment of it: the benchmark's own ``bench.``
spans and the program's ``knn.`` spans (``knn_tpu/obs/trace.py``: every
scoped span of the program is also a ``knn.<span>`` annotation).

Two steps, so that the arithmetic can be pinned on a small recorded
trace kept as JSON (``tests/data/``): :func:`read_xplane` pulls the
events out of the protobuf with nothing but ``jax.profiler.ProfileData``;
:func:`reduce` is plain Python over what it returns.

What is counted as the device: ONE line of each device plane (the
XLA-ops line; plane and line are named by regular expressions from
``peaks.json``, per device kind).  ``busy_s`` is the union of that
line's event intervals clipped to the traced window, so it cannot
exceed ``window_s``, which is the length of that window.  The window is
the host span ``bench.trace_window`` that the driver opens around the
traced work; host spans (``jax.profiler.TraceAnnotation``) and device
events share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from bisect import bisect_right
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
BENCH_PREFIX = "bench."
#: the host spans kept from a trace: the benchmark's own and the program's
HOST_PREFIXES = (BENCH_PREFIX, "knn.")
OUTSIDE = "outside-spans"
NAME_CHARS = 96

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


class TraceError(RuntimeError):
    """The trace cannot give the numbers asked of it; the message says
    what it holds instead."""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path: str, plane_re: str) -> dict:
    """Events of every line of the planes ``plane_re`` names, and the
    host spans (names starting with one of ``HOST_PREFIXES``) from every
    plane.  ``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, dur_ns], ...]}]}], "host_spans": [[name, start_ns,
    dur_ns], ...], "seen": {plane: [line, ...]}}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    want = re.compile(plane_re)
    planes, host, seen = [], [], {}
    for plane in data.planes:
        lines = list(plane.lines)
        seen[plane.name] = [ln.name for ln in lines]
        if want.search(plane.name):
            planes.append({"name": plane.name, "lines": [
                {"name": ln.name,
                 "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in ln.events]}
                for ln in lines]})
        for ln in lines:
            host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in ln.events
                        if e.name.startswith(HOST_PREFIXES))
    return {"planes": planes, "host_spans": host, "seen": seen}


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _self_times(events: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Seconds per name, each event counted for its own time only: the
    time of the events nested inside it (a loop's body inside the loop)
    goes to them."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, lo, hi in sorted(events, key=lambda e: (e[1], -(e[2]))):
        close(lo)
        if stack:
            stack[-1][2] -= min(hi, stack[-1][1]) - lo
        stack.append([name, hi, hi - lo])
    close(float("inf"))
    return out


def attribute(gaps: Sequence[Tuple[float, float]],
              spans: Sequence[Event]) -> Dict[str, float]:
    """The length of ``gaps`` (``(lo, hi)`` pairs that do not overlap)
    per span name: each gap is cut at every span edge inside it and
    every piece goes to the innermost of ``spans`` (``(name, start,
    length)``) that covers it, which is the shortest one, the later
    start where two are equally long; a piece under no span goes to
    ``OUTSIDE``.  Same unit as given; the values sum to the gaps'."""
    ends = [(name, lo, lo + dur) for name, lo, dur in spans if dur > 0]
    cuts = sorted({t for _, lo, hi in ends for t in (lo, hi)})
    # no span starts or ends between two neighbouring edges, so one span
    # is the innermost all through
    owner: List[Optional[str]] = []
    for lo, hi in zip(cuts, cuts[1:]):
        cover = [s for s in ends if s[1] <= lo and hi <= s[2]]
        owner.append(min(cover, key=lambda s: (s[2] - s[1], -s[1]))[0]
                     if cover else None)
    out: Dict[str, float] = {}
    for at, g_hi in gaps:
        i = bisect_right(cuts, at)  # cuts[i - 1] <= at < cuts[i]
        while at < g_hi:
            upto = min(cuts[i], g_hi) if i < len(cuts) else g_hi
            key = (owner[i - 1] if 0 < i < len(cuts) else None) or OUTSIDE
            out[key] = out.get(key, 0.0) + (upto - at)
            at, i = upto, i + 1
    return out


class Reduced:
    """What one traced window holds.  Times in seconds."""

    def __init__(self, window_s: float, busy_s: float,
                 per_chip: List[dict], host_spans: List[Event],
                 window: Tuple[float, float]):
        self.window_s = window_s
        self.busy_s = busy_s  # mean over the chips used
        self._chips = per_chip
        self._host = host_spans
        self._window = window

    def op_seconds(self, pattern: str) -> Optional[float]:
        """Seconds in which an operation whose name matches ``pattern``
        ran (union of their intervals, mean over chips); None where no
        operation matches."""
        rx = re.compile(pattern)
        totals, found = [], False
        for chip in self._chips:
            hit = [(lo, hi) for name, lo, hi in chip["events"]
                   if rx.search(name)]
            found = found or bool(hit)
            totals.append(sum(hi - lo for lo, hi in _union(hit)) / 1e9)
        return sum(totals) / len(totals) if found else None

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most device time (own time, summed
        over the chips), under the names the trace gives them."""
        total: Dict[str, float] = {}
        for chip in self._chips:
            for name, s in _self_times(chip["events"]).items():
                total[name] = total.get(name, 0.0) + s
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        # a TPU trace names an operation by its whole HLO line: keep the
        # head of it (name, result shape), which tells operations apart
        return [[name[:NAME_CHARS], s] for name, s in rows]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time of the first chip by what the host was doing at
        each moment of it (:func:`attribute` over the kept host spans
        but the window's own), longest first.  A program span goes
        under its full name (``knn.certified.rank_correct``), a
        ``bench.`` span with the prefix stripped (``call``,
        ``host-after-batch``)."""
        lo_w, hi_w = self._window
        gaps, at = [], lo_w
        for lo, hi in self._chips[0]["union"]:
            if lo > at:
                gaps.append((at, lo))
            at = max(at, hi)
        if hi_w > at:
            gaps.append((at, hi_w))
        by: Dict[str, float] = {}
        for name, ns in attribute(
                gaps, [s for s in self._host if s[0] != WINDOW_SPAN]).items():
            if name.startswith(BENCH_PREFIX):
                name = name[len(BENCH_PREFIX):]
            by[name] = by.get(name, 0.0) + ns / 1e9
        rows = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s] for name, s in rows]


def reduce(extracted: dict, line_re: str) -> Reduced:
    """Clip the chosen line of every device plane to the traced window
    and reduce it.  Raises :class:`TraceError` where there is no window
    span, no device plane, no such line, or no device event in the
    window."""
    spans = [tuple(s) for s in extracted["host_spans"]]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise TraceError(
            f"no {WINDOW_SPAN} span in the trace (host spans seen: "
            f"{sorted({s[0] for s in spans})}; planes: "
            f"{extracted.get('seen')})")
    _, lo_w, dur_w = max(windows, key=lambda s: s[2])
    hi_w = lo_w + dur_w
    if dur_w <= 0:
        raise TraceError(f"the {WINDOW_SPAN} span has no length")
    if not extracted["planes"]:
        raise TraceError(
            f"no device plane in the trace (planes: "
            f"{extracted.get('seen')})")
    rx = re.compile(line_re)
    chips = []
    for plane in extracted["planes"]:
        lines = [ln for ln in plane["lines"]
                 if rx.search(ln["name"]) and ln["events"]]
        if not lines:
            raise TraceError(
                f"plane {plane['name']} has no line matching {line_re!r} "
                f"with events (lines: "
                f"{[(ln['name'], len(ln['events'])) for ln in plane['lines']]})")
        # one line: where several match (the CPU's client threads in the
        # tests; a TPU plane has one XLA-ops line), the busiest
        lines.sort(key=lambda ln: -sum(e[2] for e in ln["events"]))
        events = []
        for name, start, dur in lines[0]["events"]:
            lo, hi = max(start, lo_w), min(start + dur, hi_w)
            if hi > lo:
                events.append((name, lo, hi))
        if not events:
            all_ev = lines[0]["events"]
            raise TraceError(
                f"no event of line {lines[0]['name']!r} of "
                f"{plane['name']} falls inside the traced window "
                f"[{lo_w}, {hi_w}] ns; the line spans "
                f"[{min(e[1] for e in all_ev)}, "
                f"{max(e[1] + e[2] for e in all_ev)}] ns")
        union = _union([(lo, hi) for _, lo, hi in events])
        chips.append({"plane": plane["name"], "line": lines[0]["name"],
                      "events": events, "union": union,
                      "busy_s": sum(hi - lo for lo, hi in union) / 1e9})
    busy = sum(c["busy_s"] for c in chips) / len(chips)
    return Reduced(dur_w / 1e9, busy, chips, spans, (lo_w, hi_w))


def describe(extracted: dict, top: int = 40) -> dict:
    """What a trace holds, for a first look by hand: per plane and line
    the event count, the span it covers, and its longest-running names."""
    out = {"seen": extracted["seen"],
           "host_spans": sorted({s[0] for s in extracted["host_spans"]}),
           "planes": {}}
    for plane in extracted["planes"]:
        lines = {}
        for ln in plane["lines"]:
            ev = ln["events"]
            if not ev:
                continue
            tot: Dict[str, float] = {}
            for name, _, dur in ev:
                tot[name] = tot.get(name, 0.0) + dur / 1e9
            lines[ln["name"]] = {
                "events": len(ev),
                "from_ns": min(e[1] for e in ev),
                "to_ns": max(e[1] + e[2] for e in ev),
                "top": sorted(tot.items(), key=lambda kv: -kv[1])[:top]}
        out["planes"][plane["name"]] = lines
    return out
