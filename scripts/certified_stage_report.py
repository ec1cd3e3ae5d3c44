#!/usr/bin/env python
"""Where a certified batch's time goes, read from the program's own
tracing (docs/OBSERVABILITY.md "Span lifecycle"), in three sub-commands:

    python scripts/certified_stage_report.py spans <events.jsonl> [--skip-calls N]
    python scripts/certified_stage_report.py startup <events.jsonl> [--run-log <the run's output>]
    python scripts/certified_stage_report.py idle <trace dir or .xplane.pb> [--device-plane /device:TPU:N]

``spans`` reads a ``KNN_TPU_OBS_LOG`` file: per ``certified.*`` stage,
and per phase of one right after it with the ``parent`` it names,
the mean ms a call and a batch (a stage that closes once a sub-batch is
ONE record a call, the sum of its scopes: ``spans`` counts calls, and
``per_batch`` is that sum over the call's launches), the self time of
``certified.call`` (its
length less its children's) and the share of it the children cover;
beside them the call's once-a-call account (``account_ms``: the exposed
seconds, the seconds in flight by device program, the insides of
``rank_correct`` and ``unpack``, each a mean per CALL whatever the
sub-batches).  ``--skip-calls`` leaves out the first N calls (a
benchmark's warm-up).

``startup`` reads the same file for what happened before the first
measured call: every device program the process built, once, with its
key, whether the persistent cache answered, and its trace / lower /
compile / load seconds (``program.first_call.*``), and the placement's
host passes (``placement.*``).  With ``--run-log`` (a benchmark run's
output) it lays them against that run's own ``set-up:`` lines and its
``setup_s``, the remainder as a row of its own.

``idle`` reads a profiler capture (``obs.profiler.device_trace``, or a
benchmark run with ``--trace 1``): every moment the device's ``XLA Ops``
line is idle inside the traced window is laid against the innermost
``knn.certified.*`` annotation the host was in (the same spans, on the
profiler's clock); it also says how much of each ``bench.call``
annotation ``knn.certified.call`` covers, and splits the device's busy
time by the program's ``knn.*`` device scopes.  A scope is the ``tf_op``
stat of an op's event METADATA in the device plane, which
``jax.profiler.ProfileData`` does not hand out (its ``event.stats`` are
the per-event ones: offsets and durations), so :func:`op_scopes` walks
the protobuf's wire format for just that.

Prints one JSON object.  The arithmetic (:func:`stage_table`,
:func:`startup_table`) is plain Python over lists, tested on the CPU;
laying gaps against spans and an event's own time are the benchmark's
(``benchmark/tracereduce.py``), so the two readers of a trace cannot
disagree.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import tracereduce  # noqa: E402 - benchmark/tracereduce.py, path set above

CALL = "certified.call"
RANGE_CALL = "certified.range_call"
FIRST_CALL = "program.first_call."
PREFIX = "knn."
DEVICE_PLANE = "/device:TPU:0"
SCOPE_STAT = "tf_op"
Interval = Tuple[float, float]


# --- spans: the JSONL event log ---------------------------------------------
def stage_table(events: Iterable[dict], skip_calls: int = 0) -> dict:
    """Per-stage means over the ``certified.*`` span events of whole
    calls (grouped by trace id, in the order their ``certified.call``
    closed), leaving out the first ``skip_calls``."""
    by_tid: Dict[str, List[dict]] = defaultdict(list)
    order: List[str] = []
    for e in events:
        if e.get("type") != "span" or "trace_id" not in e:
            continue
        if not e["span"].startswith("certified."):
            continue
        by_tid[e["trace_id"]].append(e)
        if e["span"] == CALL:
            order.append(e["trace_id"])
    calls = order[skip_calls:]
    if not calls:
        raise SystemExit(f"no whole {CALL} left after skipping "
                         f"{skip_calls} of {len(order)}")
    total: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    parent: Dict[str, str] = {}
    attrs: Dict[str, float] = defaultdict(float)
    account: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    merge: Dict[str, object] = {}
    select: Dict[str, int] = {}
    batches = children = 0.0
    for tid in calls:
        for e in by_tid[tid]:
            if "account_of" in e:
                # a sum over the call, recorded once when it ended: not
                # a stage, and no child of the call's
                row = account[e["span"]]
                row["ms"] += 1e3 * e["dur_s"]
                for key, v in e.items():
                    if key.endswith("_s") and key != "dur_s":
                        row[key[:-2] + "_ms"] += 1e3 * v
                    elif key == "launches":
                        row[key] += v
                continue
            total[e["span"]] += e["dur_s"]
            count[e["span"]] += 1
            if "parent" in e:
                parent[e["span"]] = e["parent"]
            if e["span"] == CALL:
                batches += e.get("batches", 1)
                # which cross-shard merge answered and what it moved
                attrs["merge_bytes"] += e.get("merge_bytes", 0)
                merge = {k: e[k] for k in ("db_shards", "merge",
                                           "merge_source") if k in e}
                # the final select's width a shard, as the kernel gave
                # it and as its top-k scanned it (less where the
                # bin-merge engaged)
                select = {k: e[k] for k in (
                    "select_width", "select_merged_width",
                    "select_merge_short") if k in e}
            elif e.get("parent") == CALL:
                children += e["dur_s"]
            for key in ("h2d_bytes", "d2h_bytes", "queries_corrected",
                        "members", "parts", "threads", "fallback_queries",
                        "host_exact_queries"):
                if key in e and e["span"] != CALL:
                    attrs[key] += e[key]
    n = len(calls)
    ms = lambda s: round(1e3 * s, 4)  # noqa: E731
    return {
        "calls": n, "batches": batches,
        "stages_ms": {
            # sorted by name: a phase's row follows its parent's
            name: {"per_call": ms(total[name] / n),
                   "per_batch": ms(total[name] / batches),
                   "spans": count[name],
                   **({"parent": parent[name]} if name in parent else {})}
            for name in sorted(total)},
        "account_ms": {
            name: {k: round(v / n, 4) for k, v in row.items()}
            for name, row in sorted(account.items())},
        "call_self_ms_per_call": ms((total[CALL] - children) / n),
        "children_share_of_call": round(children / total[CALL], 5),
        "per_batch": {k: round(v / batches, 3) for k, v in attrs.items()},
        "merge": merge,
        "select": select,
    }


# --- startup: what happened before the first measured call ------------------
_SETUP_LINE = re.compile(
    r"set-up: (drew|placed|first batch|warmed)\b.*?: ([0-9.]+) s")


def run_log_setup(text: str) -> dict:
    """A benchmark run's own account of its set-up, from its output: the
    ``set-up:`` lines (``drew``, ``placed``, ``first batch``, ``warmed``:
    seconds; the last counts from before the first batch) and
    ``setup_s`` from its result line (an untraced run's)."""
    out = {key.replace(" ", "_") + "_s": float(sec)
           for key, sec in _SETUP_LINE.findall(text)}
    for line in reversed(text.splitlines()):
        if line.startswith("{") and '"setup_s"' in line:
            out["setup_s"] = json.loads(line)["metrics"]["setup_s"]["value"]
            break
    return out


def startup_table(events: Iterable[dict], run: dict = None) -> dict:
    """Every ``program.first_call.*`` span (one a program object the
    process built and called) and every ``placement.*`` event of a log,
    in order; those up to the end of the first outermost call are that
    call's.  With ``run`` (:func:`run_log_setup`) also ``setup``: the
    run's set-up seconds by row, the rows of this log beside the run's
    own lines, what no row covers as ``unaccounted_s``."""
    programs, placement = [], []
    first_call_over = False
    for e in events:
        if e.get("type") == "event" and str(e.get("name", "")).startswith(
                "placement."):
            placement.append({
                "event": e["name"], "seconds": round(e["seconds"], 4),
                "in_first_call": not first_call_over,
                **{k: e[k] for k in ("rows", "dim", "bytes", "tile", "parts")
                   if k in e}})
        elif e.get("type") != "span":
            continue
        elif e["span"].startswith(FIRST_CALL):
            hit = e["cache_hits"] > 0 and e["cache_misses"] == 0
            programs.append({
                "program": e["program"], "key": e["key"],
                "seconds": e["dur_s"],
                "cache": ("hit" if hit else "miss" if e["cache_misses"]
                          else "off"),
                "traces": e["traces"], "trace_s": e["trace_s"],
                "lower_s": e["lower_s"], "compile_s": e["compile_s"],
                "load_s": e["cache_load_s"],
                "in_first_call": not first_call_over})
        elif e["span"] == RANGE_CALL or (
                e["span"] == CALL and "parent" not in e):
            first_call_over = True
    out = {"programs": programs, "placement": placement}
    if run:
        def total(rows, first):
            return sum(r["seconds"] for r in rows
                       if r["in_first_call"] is first)

        placed = sum(r["seconds"] for r in placement
                     if r["event"] in ("placement.dot_augment",
                                       "placement.cosine_normalize",
                                       "placement.device_put"))
        walk = sum(r["seconds"] for r in placement
                   if r["event"] in ("placement.norm_walk",
                                     "placement.host_copy"))
        built = sum(r["seconds"] for r in placement
                    if r["event"] == "placement.operands")
        rows = {
            "drawn_s": run.get("drew_s", 0.0),
            "placement_host_passes_s": round(placed, 4),
            "placed_rest_s (the wait for the transfer)": round(
                run.get("placed_s", 0.0) - placed, 4),
            "first_call_host_passes_s (norm walk, host copy)": round(
                walk, 4),
            "first_call_operands_s (the device's pass that builds the "
            "resident row operands)": round(built, 4),
            "first_call_programs_s": round(total(programs, True), 4),
            "first_call_rest_s (its own batch)": round(
                run.get("first_batch_s", 0.0) - walk - built
                - total(programs, True), 4),
            "later_warmup_programs_s": round(total(programs, False), 4),
            "later_warmup_rest_s (their batches)": round(
                run.get("warmed_s", 0.0) - run.get("first_batch_s", 0.0)
                - total(programs, False), 4),
        }
        out["setup"] = {"setup_s": run.get("setup_s"), "rows": rows}
        if run.get("setup_s"):
            left = run["setup_s"] - sum(rows.values())
            out["setup"]["unaccounted_s"] = round(left, 4)
            out["setup"]["accounted_share"] = round(
                1 - left / run["setup_s"], 4)
    return out


def read_jsonl(path: str) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


# --- idle: the profiler capture ---------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(hi, out[-1][1]))
        else:
            out.append((lo, hi))
    return out


def clipped(intervals: Sequence[Interval], window: Interval
            ) -> List[Interval]:
    """The union of ``intervals``, cut to ``window``."""
    lo_w, hi_w = window
    return [(max(lo, lo_w), min(hi, hi_w)) for lo, hi in union(intervals)
            if min(hi, hi_w) > max(lo, lo_w)]


def gaps_of(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    out, at = [], window[0]
    for lo, hi in clipped(busy, window):
        if lo > at:
            out.append((at, lo))
        at = hi
    if window[1] > at:
        out.append((at, window[1]))
    return out


def attribute(gaps: Sequence[Interval],
              spans: Sequence[Tuple[str, float, float]]) -> Dict[str, float]:
    """Idle time per span name: every moment of every gap goes to the
    innermost span that covers it (``tracereduce.attribute``: the
    shortest, the later start of two equally long), else to
    ``outside``.  ``spans`` are ``(name, start, end)``."""
    by = tracereduce.attribute(
        gaps, [(name, lo, hi - lo) for name, lo, hi in spans])
    if tracereduce.OUTSIDE in by:
        by["outside"] = by.pop(tracereduce.OUTSIDE)
    return by


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, at


def _fields(buf: bytes) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    at = 0
    while at < len(buf):
        key, at = _varint(buf, at)
        wire = key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, at = buf[at:at + size], at + size
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield key >> 3, value


def op_scopes(xspace: bytes, plane_name: str = DEVICE_PLANE,
              stat: str = SCOPE_STAT) -> Dict[str, str]:
    """``{op's event name: its tf_op}`` from the event metadata of one
    plane of a serialized ``XSpace`` (tsl/profiler/protobuf/xplane.proto:
    XSpace.planes=1; XPlane.name=2, .event_metadata=4, .stat_metadata=5
    (maps: key=1, value=2); XEventMetadata.name=2, .stats=5;
    XStatMetadata.name=2; XStat.metadata_id=1, .str_value=5)."""
    out: Dict[str, str] = {}
    for field, plane in _fields(xspace):
        if field != 1:
            continue
        parts = list(_fields(plane))
        if not any(f == 2 and v == plane_name.encode() for f, v in parts):
            continue
        stat_ids = set()
        for f, v in parts:
            if f == 5:
                entry = dict(_fields(v))
                if dict(_fields(entry[2])).get(2) == stat.encode():
                    stat_ids.add(entry[1])
        for f, v in parts:
            if f != 4:
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            name = next((x for g, x in meta if g == 2), b"").decode()
            for g, x in meta:
                if g == 5:
                    st = dict(_fields(x))
                    if st.get(1) in stat_ids and 5 in st:
                        out[name] = st[5].decode()
    return out


def innermost_scope(op_name: str) -> str:
    """The last ``knn.*`` component of an HLO ``op_name``, else
    ``unscoped``."""
    hits = re.findall(r"(?:^|/)(knn\.[a-z_]+)(?=/|:|$)", op_name)
    return hits[-1] if hits else "unscoped"


def idle_report(path: str, window_span: str, outer_span: str,
                device_plane: str = DEVICE_PLANE) -> dict:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    with open(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw, device_plane)
    data = ProfileData.from_serialized_xspace(raw)
    busy: List[Interval] = []
    host: List[Tuple[str, float, float]] = []
    scoped: List[Tuple[str, float, float]] = []  # (scope, start, end)
    example = None
    for plane in data.planes:
        device = plane.name == device_plane
        for line in plane.lines:
            for e in line.events:
                lo, hi = float(e.start_ns), float(e.start_ns + e.duration_ns)
                if device and line.name == "XLA Ops":
                    busy.append((lo, hi))
                    scope = innermost_scope(scopes.get(e.name, ""))
                    scoped.append((scope, lo, hi))
                    if example is None and scope == "knn.final_select":
                        example = {
                            "event": e.name[:120],
                            "event_stats": {k: str(v) for k, v in e.stats},
                            f"metadata_{SCOPE_STAT}": scopes[e.name]}
                elif e.name.startswith((PREFIX, "bench.")):
                    host.append((e.name, lo, hi))
    windows = [s for s in host if s[0] == window_span]
    if not windows or not busy:
        raise SystemExit(
            f"no {window_span} span or no XLA Ops event on {device_plane} "
            f"(planes: {[p.name for p in data.planes]})")
    _, lo_w, hi_w = max(windows, key=lambda s: s[2] - s[1])
    inside = [s for s in host if lo_w <= s[1] and s[2] <= hi_w]
    gaps = gaps_of(busy, (lo_w, hi_w))
    idle = sum(hi - lo for lo, hi in gaps)
    stages = [s for s in inside if s[0].startswith(PREFIX + "certified.")]
    by = attribute(gaps, stages)
    calls = [s for s in inside if s[0] == PREFIX + CALL]
    outers = [s for s in inside if s[0] == outer_span]
    sec = lambda ns: round(ns / 1e9, 6)  # noqa: E731
    return {
        "xplane": path, "device_plane": device_plane,
        "window_s": sec(hi_w - lo_w), "idle_s": sec(idle),
        "idle_pct": round(100 * idle / (hi_w - lo_w), 3),
        "calls_in_window": len(calls),
        "idle_by_stage_s": {k: sec(v) for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])},
        "idle_outside_stages_pct_of_idle": round(
            100 * by.get("outside", 0.0) / idle, 3) if idle else 0.0,
        "stage_ms_per_call": {
            name[len(PREFIX):]: round(
                sum(hi - lo for n, lo, hi in stages if n == name)
                / 1e6 / max(len(calls), 1), 4)
            for name in sorted({s[0] for s in stages})},
        f"{CALL}_share_of_{outer_span}": round(
            sum(hi - lo for _, lo, hi in calls)
            / sum(hi - lo for _, lo, hi in outers), 5) if outers else None,
        # each op's OWN time (a loop's body ops lie inside its %while:
        # their time is theirs, not the loop's too), so the rows sum to
        # the device's busy time
        "device_ms_per_call_by_scope": {
            k: round(1e3 * v / max(len(calls), 1), 4)
            for k, v in sorted(tracereduce._self_times(
                [(scope, max(lo, lo_w), min(hi, hi_w))
                 for scope, lo, hi in scoped
                 if min(hi, hi_w) > max(lo, lo_w)]).items())},
        "scoped_event_example": example,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    sp = sub.add_parser("spans")
    sp.add_argument("jsonl")
    sp.add_argument("--skip-calls", type=int, default=0)
    up = sub.add_parser("startup")
    up.add_argument("jsonl")
    up.add_argument("--run-log", help="the benchmark run's output")
    ip = sub.add_parser("idle")
    ip.add_argument("trace")
    ip.add_argument("--window-span", default="bench.trace_window")
    ip.add_argument("--outer-span", default="bench.call")
    ip.add_argument("--device-plane", default=DEVICE_PLANE,
                    help="one chip's plane (a sharded run has one a chip)")
    args = ap.parse_args(argv)
    if args.cmd == "spans":
        out = stage_table(read_jsonl(args.jsonl), args.skip_calls)
    elif args.cmd == "startup":
        run = None
        if args.run_log:
            with open(args.run_log) as f:
                run = run_log_setup(f.read())
        out = startup_table(read_jsonl(args.jsonl), run)
    else:
        out = idle_report(args.trace, args.window_span, args.outer_span,
                          args.device_plane)
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
