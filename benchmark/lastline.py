"""The last line of a benchmark run: built here, validated here, and
printed by ``run.py`` only after :func:`validate` passed it.

The rules are the benchmark contract's: one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` only in a traced run, and last of all ``compared``: each
number ``correct`` was decided by, beside its limit); ``metrics`` gives every metric
``BENCHMARK.json`` lists for this cell in this mode (``--trace 0``: its
end-to-end metrics; ``--trace 1``: its per-layer metrics) as a finite
``value`` with the declared ``unit``; ``device`` gives ``platform``,
``kind``, ``count``, ``memory_peak_bytes`` and, traced, ``window_s`` and
``busy_s`` with 0 < busy_s <= window_s.  A line that breaks a rule is an
error with a message, never a line with a NaN or a hole in it.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("window_s", "busy_s")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
#: a roofline or mfu share above this is a fault in the yardstick (the
#: contract's own threshold), so it is refused here rather than printed
SHARE_CEILING_PCT = 105.0


class LastLineError(ValueError):
    """The line may not be printed; the message says which rule broke."""


def cell_of(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise LastLineError(
        f"workload {workload!r} is not in BENCHMARK.json (has "
        f"{[c['name'] for c in bench['workloads']]})")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end_of(bench: dict, workload: str) -> List[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"] if _applies(m, workload)]


def per_layer_of(bench: dict, workload: str) -> List[dict]:
    """The per-layer metrics this cell reports: those whose
    ``workloads`` list names it."""
    return [m for m in bench["per_layer"] if workload in m["workloads"]]


def required_metrics(bench: dict, workload: str, traced: bool) -> List[dict]:
    cell_of(bench, workload)
    return (per_layer_of if traced else end_to_end_of)(bench, workload)


def _number(what: str, v) -> float:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        raise LastLineError(f"{what} is {v!r}, not a number")
    if not math.isfinite(v):
        raise LastLineError(f"{what} is {v!r}, not a finite number")
    return v


def _count(what: str, v) -> int:
    if isinstance(v, bool) or not isinstance(v, int) or v < 0:
        raise LastLineError(f"{what} is {v!r}, not a whole number >= 0")
    return v


def build(*, correct: bool, attempted: int, failed: int,
          values: Dict[str, float], units: Dict[str, str], device: dict,
          breakdown: Optional[dict] = None,
          compared: Optional[List[dict]] = None) -> str:
    """The line as a string.  ``values`` maps metric name to the number
    as measured; ``units`` to the declared unit; ``compared`` is
    ``reference.Checks.rows``.  Raises where a metric's value cannot be
    written as JSON (NaN, infinity, None); a compared number that is not
    finite is written as text, since it is what made the run incorrect."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": _number(f"metric {name}", v),
                           "unit": units[name]}
                    for name, v in values.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = {
            r["check"]: {"value": (r["value"] if math.isfinite(r["value"])
                                   else repr(r["value"])),
                         "limit": r["limit"], "rule": r["rule"]}
            for r in compared}
    try:
        return json.dumps(line, allow_nan=False)
    except (TypeError, ValueError) as e:
        raise LastLineError(f"the line cannot be written as JSON: {e}")


def validate(line: str, bench: dict, workload: str, traced: bool) -> dict:
    """Raise :class:`LastLineError` unless ``line`` is a last line the
    contract accepts for ``workload`` in this mode; return it parsed."""
    if not isinstance(line, str) or "\n" in line or "\r" in line:
        raise LastLineError("the last line is not one line of text")

    def refuse_constant(name):
        raise LastLineError(f"the line holds {name}, which is not JSON")

    try:
        obj = json.loads(line, parse_constant=refuse_constant)
    except json.JSONDecodeError as e:
        raise LastLineError(f"the last line is not JSON: {e}")
    if not isinstance(obj, dict):
        raise LastLineError("the last line is not a JSON object")
    for key in TOP_KEYS:
        if key not in obj:
            raise LastLineError(f"key {key!r} is missing")
    if not isinstance(obj["correct"], bool):
        raise LastLineError(f"correct is {obj['correct']!r}, not a boolean")
    attempted = _count("attempted", obj["attempted"])
    failed = _count("failed", obj["failed"])
    if failed > attempted:
        raise LastLineError(f"failed {failed} > attempted {attempted}")
    if attempted == 0:
        raise LastLineError("attempted is 0: the window did no work")

    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        raise LastLineError("metrics is not an object")
    required = required_metrics(bench, workload, traced)
    if not required:
        raise LastLineError(
            f"BENCHMARK.json lists no "
            f"{'per-layer' if traced else 'end-to-end'} metric for "
            f"{workload}")
    for m in required:
        name = m["name"]
        got = metrics.get(name)
        if not isinstance(got, dict) or "value" not in got \
                or "unit" not in got:
            raise LastLineError(
                f"metric {name!r}, which BENCHMARK.json lists for "
                f"{workload} with --trace {int(traced)}, is "
                f"{'absent' if got is None else 'not {value, unit}'}")
        v = _number(f"metric {name}", got["value"])
        if got["unit"] != m["unit"]:
            raise LastLineError(
                f"metric {name!r} has unit {got['unit']!r}; "
                f"BENCHMARK.json says {m['unit']!r}")
        if not traced and v <= 0:
            raise LastLineError(
                f"end-to-end metric {name!r} is {v!r}; it is never 0")
        if ("roofline" in name or "mfu" in name) and v > SHARE_CEILING_PCT:
            raise LastLineError(
                f"share {name!r} reads {v!r}%: over {SHARE_CEILING_PCT}, "
                f"so the operations or bytes are counted too high or the "
                f"time leaves out part of the work")
    for name, got in metrics.items():
        if not isinstance(got, dict):
            raise LastLineError(f"metric {name!r} is not {{value, unit}}")
        _number(f"metric {name}", got.get("value"))

    dev = obj["device"]
    if not isinstance(dev, dict):
        raise LastLineError("device is not an object")
    for key in DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ()):
        if key not in dev:
            raise LastLineError(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if not isinstance(dev[key], str) or not dev[key]:
            raise LastLineError(f"device.{key} is {dev[key]!r}")
    chips = cell_of(bench, workload)["chips"]
    if _count("device.count", dev["count"]) < chips:
        raise LastLineError(
            f"device.count {dev['count']} < the {chips} chip(s) the cell "
            f"asks for")
    if _count("device.memory_peak_bytes", dev["memory_peak_bytes"]) == 0:
        raise LastLineError("device.memory_peak_bytes is 0")
    if traced:
        window = _number("device.window_s", dev["window_s"])
        busy = _number("device.busy_s", dev["busy_s"])
        if not 0 < busy <= window:
            raise LastLineError(
                f"device.busy_s {busy!r} is not above 0 and at most "
                f"device.window_s {window!r}")

    if "breakdown" in obj:
        if not traced:
            raise LastLineError("breakdown belongs to a traced run only")
        bd = obj["breakdown"]
        if not isinstance(bd, dict) or set(bd) - set(BREAKDOWN_KEYS):
            raise LastLineError(
                f"breakdown has keys other than {BREAKDOWN_KEYS}")
        for key, rows in bd.items():
            if not isinstance(rows, list) or len(rows) > 10:
                raise LastLineError(
                    f"breakdown.{key} is not a list of at most 10 rows")
            for row in rows:
                if (not isinstance(row, list) or len(row) != 2
                        or not isinstance(row[0], str)):
                    raise LastLineError(
                        f"breakdown.{key} row {row!r} is not "
                        f"[name, seconds]")
                _number(f"breakdown.{key} {row[0]!r}", row[1])
    if "compared" in obj:
        if list(obj)[-1] != "compared":
            raise LastLineError("compared is not the line's last key")
        cmp = obj["compared"]
        if not isinstance(cmp, dict) or not cmp:
            raise LastLineError("compared is not an object with entries")
        for name, row in cmp.items():
            if not isinstance(row, dict) or set(row) != {
                    "value", "limit", "rule"}:
                raise LastLineError(
                    f"compared.{name} is not {{value, limit, rule}}")
            _number(f"compared.{name} limit", row["limit"])
            if not isinstance(row["value"], str):
                _number(f"compared.{name} value", row["value"])
    return obj
