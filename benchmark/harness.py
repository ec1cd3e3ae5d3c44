"""One run of one cell, driven by data: ``BENCHMARK.json`` names the
cell's configuration and traffic; this finds ``configs/<config>.json``
(by the path ``BENCHMARK.json`` gives), ``traffic/<traffic>.json``,
whose ``kind`` names ``drivers/<kind>.py``, and ``layers/<metric>.json``
for every per-layer metric the cell reports.  Adding a configuration, a
traffic mix, a driver kind or a per-layer metric is adding files and
entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import lastline
import tracereduce

CODE_DIR = os.path.dirname(os.path.abspath(__file__))
#: where a run writes (trace, notes); listed in .gitignore
OUT_DIR = ".bench_out"
READERS = ("trace_ops", "trace_busy", "counter", "span", "bench")


class BenchError(RuntimeError):
    """The run cannot give a result; ``run.py`` prints the message and
    exits non-zero."""


def say(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"{path} is missing")


def _module(kind: str, sub: str):
    """``<sub>/<kind>.py`` beside this file, imported by path."""
    if not re.fullmatch(r"[A-Za-z0-9_]+", kind):
        raise BenchError(f"{sub} name {kind!r} is not a module name")
    path = os.path.join(CODE_DIR, sub, f"{kind}.py")
    if not os.path.exists(path):
        raise BenchError(f"{sub} {kind!r}: {path} is missing")
    name = f"bench_{sub}_{kind}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """Everything the data files say of one cell."""

    root: str
    bench: dict
    name: str
    chips: int
    config: dict
    traffic: dict
    peaks_table: dict
    layers: Dict[str, dict] = field(default_factory=dict)

    @property
    def data_dir(self) -> str:
        return os.path.join(self.root, self.bench["paths"][0])


def load_cell(root: str, workload: str) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    try:
        cell = lastline.cell_of(bench, workload)
    except lastline.LastLineError as e:
        raise BenchError(str(e))
    data_dir = os.path.join(root, bench["paths"][0])
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == cell["config"]), None)
    if cfg_entry is None:
        raise BenchError(f"configuration {cell['config']!r} is not in "
                         f"BENCHMARK.json")
    out = Cell(
        root=root, bench=bench, name=workload, chips=int(cell["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(os.path.join(
            data_dir, "traffic", f"{cell['traffic']}.json")),
        peaks_table=_load_json(os.path.join(data_dir, "peaks.json")))
    for m in lastline.per_layer_of(bench, workload):
        layer = _load_json(os.path.join(
            data_dir, "layers", f"{m['name']}.json"))
        for key in ("layer", "unit", "moves", "source", "better"):
            if layer.get(key) != m[key]:
                raise BenchError(
                    f"layers/{m['name']}.json says {key}="
                    f"{layer.get(key)!r}; BENCHMARK.json says {m[key]!r}")
        if layer["reader"]["type"] not in READERS:
            raise BenchError(
                f"layers/{m['name']}.json: reader type "
                f"{layer['reader']['type']!r} not in {READERS}")
        out.layers[m["name"]] = layer
    return out


@dataclass
class Ctx:
    """What a driver is given."""

    cell: Cell
    seed: int
    seconds: float
    traced: bool
    t_found: float  # perf_counter() when JAX had found the chip: setup_s counts from it
    trace_dir: str

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


@dataclass
class Outcome:
    """What a driver hands back."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]  # setup_s and the cell's other metrics
    checks: object  # reference.Checks
    bench: Dict[str, float]  # the driver's own readings, by name
    registry: dict  # system.registry_delta over the window
    resident_bytes: int  # harness.resident_bytes() when the window closed


# --- readers: from readings to one per-layer number -------------------------
class Readings:
    def __init__(self, cell: Cell, outcome: Outcome, peaks: dict,
                 trace: Optional[tracereduce.Reduced]):
        self.cell, self.outcome, self.peaks, self.trace = (
            cell, outcome, peaks, trace)

    def counter_sum(self, selectors) -> Optional[float]:
        """Sum over the series each selector names (``name``, optional
        ``labels`` to match, optional ``times_label``: weigh a series by
        the whole number in that label); None where none exists."""
        total, found = 0.0, False
        for sel in selectors:
            want = {k: str(v) for k, v in sel.get("labels", {}).items()}
            for (name, items), v in self.outcome.registry.items():
                labels = dict(items)
                if name != sel["name"] or any(
                        labels.get(k) != x for k, x in want.items()):
                    continue
                weight = (float(labels[sel["times_label"]])
                          if "times_label" in sel else 1.0)
                total += weight * v[0]
                found = True
        return total if found else None

    def quantity(self, spec) -> Optional[float]:
        """A reading named in a layer file: a string is one of the
        driver's own readings, a list is a counter sum."""
        if isinstance(spec, str):
            return self.outcome.bench.get(spec)
        return self.counter_sum(spec)


def _ratio(num: Optional[float], den: Optional[float], scale: float
           ) -> Optional[float]:
    if num is None or den is None or den == 0:
        return None
    return scale * num / den


def read_metric(layer: dict, r: Readings) -> Optional[float]:
    """The number a layer file's reader gives, or None where it finds
    nothing to read (the metric is then left out of the line)."""
    rd = layer["reader"]
    kind, scale = rd["type"], float(rd.get("scale", 1.0))
    if kind in ("trace_ops", "trace_busy"):
        if r.trace is None:
            return None
        if kind == "trace_ops":
            secs = r.trace.op_seconds(rd["pattern"])
        else:
            secs = r.trace.busy_s
            if "minus_pattern" in rd:
                sub = r.trace.op_seconds(rd["minus_pattern"])
                secs = None if sub is None else secs - sub
        if secs is None:
            return None
        if rd.get("as") == "idle_pct":
            return 100.0 * (1.0 - secs / r.trace.window_s)
        per = r.quantity(rd["per"])
        if rd.get("as") == "roofline_pct":
            work = _module(rd["work"], "work")
            least = work.least_seconds(r.cell.config, r.cell.traffic, r.peaks)
            return _ratio(least * per if per else None, secs, 100.0)
        return _ratio(secs, per, scale)
    if kind == "counter":
        return _ratio(r.counter_sum(rd["num"]), r.counter_sum(rd["den"]),
                      scale)
    if kind == "span":
        # mean length of the spans one histogram series counted
        cs = r.outcome.registry.get(
            (rd["series"], tuple(sorted(
                (k, str(v)) for k, v in rd["labels"].items()))))
        return None if cs is None else _ratio(cs[1], cs[0], scale)
    if kind == "bench":
        if "value" in rd:
            v = r.outcome.bench.get(rd["value"])
            return None if v is None else scale * v
        return _ratio(r.outcome.bench.get(rd["num"]),
                      r.outcome.bench.get(rd["den"]), scale)
    raise BenchError(f"reader type {kind!r} not in {READERS}")


# --- one run ----------------------------------------------------------------
def resident_bytes(chips: int) -> int:
    """What the fullest of the first ``chips`` devices holds at this
    moment.  The runtime keeps two tallies that do not overlap: buffers
    the client holds (``bytes_in_use``: the placed rows, answers) and
    the space set aside for the loaded programs' temporaries
    (``bytes_reserved``).  Both are read in one call, so their sum is
    what is resident together (``bytes_limit`` less both is
    ``largest_free_block_bytes``; PERF.md has a dump).  A driver calls
    this when its window has closed and its rows are still placed; the
    whole dump goes on an earlier line, ``peak_bytes_in_use`` in it."""
    import jax

    most = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        say(f"memory_stats of {d} after the window: {stats}")
        most = max(most, int(stats.get("bytes_in_use", 0))
                   + int(stats.get("bytes_reserved", 0)))
    return most


def device_info(resident: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(resident)}


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, t_found: float,
             emit: Callable[[str], None] = print) -> dict:
    """Run the cell and print (through ``emit``) its validated last
    line; return the line parsed.  Raises where no valid line can be
    made."""
    import jax

    import system

    cell = load_cell(root, workload)
    kind = jax.devices()[0].device_kind
    if kind not in cell.peaks_table["kinds"]:
        raise BenchError(
            f"device kind {kind!r} is not in peaks.json (has "
            f"{sorted(cell.peaks_table['kinds'])}); a device that is not "
            f"in the table is an error, not a default")
    peaks = cell.peaks_table["kinds"][kind]
    if len(jax.devices()) < cell.chips:
        raise BenchError(f"{workload} asks for {cell.chips} chip(s); JAX "
                         f"found {len(jax.devices())}")
    system.listen_to_compiles()
    trace_dir = os.path.join(root, OUT_DIR, f"trace.{workload}")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, traced=traced,
              t_found=t_found, trace_dir=trace_dir)
    driver = _module(cell.traffic["kind"], "drivers")
    outcome: Outcome = driver.run(ctx)

    say("compared (value, limit): " + "; ".join(
        f"{r['check']}={r['value']:.6g} {r['rule']} {r['limit']:.6g}"
        f"{'' if r['ok'] else '  <-- OUTSIDE'}"
        for r in outcome.checks.rows))
    say(f"compiles: {system.COMPILES}")
    device = device_info(outcome.resident_bytes)
    declared = {m["name"]: m["unit"] for m in
                cell.bench["end_to_end"] + cell.bench["per_layer"]}
    breakdown = None
    if traced:
        extracted = tracereduce.read_xplane(
            tracereduce.find_xplane(trace_dir), peaks["trace_plane"])
        # what the trace holds, kept beside it for a look by hand
        with open(os.path.join(trace_dir, "describe.json"), "w") as f:
            json.dump(tracereduce.describe(extracted), f)
        try:
            red = tracereduce.reduce(extracted, peaks["trace_line"])
        except tracereduce.TraceError as e:
            raise BenchError(f"trace reduction: {e}")
        device["window_s"], device["busy_s"] = red.window_s, red.busy_s
        breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
        readings = Readings(cell, outcome, peaks, red)
        values = {}
        for name, layer in cell.layers.items():
            v = read_metric(layer, readings)
            if v is None:
                say(f"per-layer metric {name}: its reader found nothing")
            else:
                values[name] = v
    else:
        wanted = {m["name"] for m in
                  lastline.end_to_end_of(cell.bench, workload)}
        values = {k: v for k, v in outcome.end_to_end.items() if k in wanted}
    try:
        line = lastline.build(
            correct=outcome.checks.correct, attempted=outcome.attempted,
            failed=outcome.failed, values=values,
            units={k: declared[k] for k in values}, device=device,
            breakdown=breakdown, compared=outcome.checks.rows)
        parsed = lastline.validate(line, cell.bench, workload, traced)
    except lastline.LastLineError as e:
        raise BenchError(f"no valid last line: {e}")
    # each number compared beside its limit: the last lines on standard
    # error, as they are the last key of the line
    for r in outcome.checks.rows:
        print(f"compared {r['check']} = {r['value']!r} {r['rule']} "
              f"{r['limit']!r}{'' if r['ok'] else '  OUTSIDE'}",
              file=sys.stderr, flush=True)
    emit(line)
    return parsed
