"""The five per-layer metrics that read the certified call's once-a-call
account (``data/call_account_cell.json``: ``host_exposed_ms``,
``reselect_inflight_ms``, ``rank_score_ms``, ``rank_order_ms``,
``rank_buffers_ms``), each a ``span`` reader over one series of the
program's ``knn_tpu_span_seconds``.

They are data that no cell lists yet, and the last case says why: on a
program without the spans (the parent of the PR that brought them) a
reader finds nothing, and the harness prints no traced line that leaves
a listed metric out.  What is held here is what the PR that lists them
will rely on: each file reads its number from a registry recorded on the
chip and from a live one, the series holds one span a CALL however the
call is cut into sub-batches, and with the entries merged into
BENCHMARK.json every cell's traced run prints all five through the
harness as it stands.
"""

import json
import os
import time

import numpy as np
import pytest

import harness
import lastline
import system
import tinyroot

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = tinyroot.load_bench()
with open(os.path.join(HERE, "data", "call_account_cell.json")) as _f:
    ENTRIES = json.load(_f)["per_layer"]
with open(os.path.join(HERE, "data", "v5e_call_account_registry.json")) as _f:
    RECORDED = json.load(_f)
NAMES = ["host_exposed_ms", "reselect_inflight_ms", "rank_score_ms",
         "rank_order_ms", "rank_buffers_ms"]
CELLS = [c["name"] for c in BENCH["workloads"] if c["name"] != "bigann5m.serve"]


def layer_file(metric: str) -> dict:
    with open(os.path.join(tinyroot.BENCH_DIR, "layers",
                           f"{metric}.json")) as f:
        return json.load(f)


def read(name: str, registry: dict):
    outcome = harness.Outcome(attempted=1, failed=0, end_to_end={},
                              checks=None, bench={}, registry=registry,
                              resident_bytes=0)
    return harness.read_metric(layer_file(name),
                               harness.Readings(None, outcome, {}, None))


def series_key(name: str):
    rd = layer_file(name)["reader"]
    return (rd["series"], (("span", rd["labels"]["span"]),))


def recorded_registry(cell: str) -> dict:
    """A window's registry change as ``drivers/sweep*.py`` hand it over,
    from the JSON it was kept as."""
    return {(name, tuple(tuple(kv) for kv in labels)): tuple(value)
            for name, labels, value in RECORDED["cells"][cell]["registry"]}


def test_the_entries_are_the_five_and_fit_the_benchmark():
    assert [e["name"] for e in ENTRIES] == NAMES
    layers = {m["layer"] for m in BENCH["per_layer"]}
    (qps,) = [m for m in BENCH["end_to_end"] if m["name"] == "sweep_qps"]
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for e in ENTRIES:
        f = layer_file(e["name"])
        assert f["metric"] == e["name"]
        for key in ("layer", "unit", "moves", "source", "better"):
            assert f[key] == e[key], (e["name"], key)
        assert f["reader"]["type"] == "span" and f["reader"]["scale"] == 1000
        assert (e["unit"], e["source"], e["moves"]) == (
            "ms", "program_span", "sweep_qps")
        # a layer the benchmark already names, letter for letter
        assert e["layer"] in layers
        # all five cells, each of which reports what the metric moves
        assert e["workloads"] == CELLS == qps["workloads"]
        assert "a CALL" in f["what"]
        # once a PR lists one, it lists it as it stands here
        assert listed.get(e["name"], e) == e
    assert ENTRIES[0]["layer"] == "device (TPU v5e)"
    assert {e["layer"] for e in ENTRIES[1:]} == {
        "host repair (ops/refine.py, ops/certified.py)"}


@pytest.mark.parametrize("cell", sorted(RECORDED["cells"]))
@pytest.mark.parametrize("name", NAMES)
def test_a_layer_file_reads_its_number_from_the_recorded_registry(name,
                                                                  cell):
    """A traced window on the v5e, kept as the harness was handed it."""
    rec = RECORDED["cells"][cell]
    registry = recorded_registry(cell)
    calls, seconds = registry[series_key(name)]
    assert calls == rec["calls"]  # one record a call
    value = read(name, registry)
    assert value == pytest.approx(1e3 * seconds / calls, rel=1e-12)
    assert value == pytest.approx(rec["expect"][name], rel=1e-9)
    assert value >= 0


def test_the_recorded_windows_close_on_themselves():
    """What the account promises, on the chip's own numbers: exposed is
    under the call, the three phases make up rank_correct but for its
    overhead, and the re-select's flight is inside the repair."""
    for cell, rec in RECORDED["cells"].items():
        reg = recorded_registry(cell)

        def ms(span):
            n, s = reg[("knn_tpu_span_seconds", (("span", span),))]
            return 1e3 * s / rec["calls"]

        outer = ("certified.range_call" if cell.endswith("range")
                 else "certified.call")
        assert 0 < ms("certified.exposed") < ms(outer), cell
        union = ms(outer) - ms("certified.exposed")
        flights = sum(ms(f"certified.inflight.{p}")
                      for p in rec["programs"])
        # the call span also holds the account's own recording (these
        # runs wrote the JSONL log too: under a millisecond a call)
        assert 0.97 * union < flights <= union, cell
        parts = (ms("certified.rank_correct.score")
                 + ms("certified.rank_correct.order")
                 + ms("certified.rank_correct.buffers"))
        assert 0.9 * ms("certified.rank_correct") < parts <= ms(
            "certified.rank_correct"), cell
        assert ms("certified.inflight.reselect") <= ms(
            "certified.repair"), cell


@pytest.fixture(scope="module")
def live():
    """The registry's change over two certified calls of two sub-batches
    each, by kind of call; the first call's passes made before."""
    from knn_tpu import obs

    obs.reset(enabled=True)
    rng = np.random.default_rng(2**31 + 37)
    db = rng.random((3000, 32), dtype=np.float32)
    queries = rng.random((64, 32), dtype=np.float32)
    out = {}
    for kind, metric in (("l2", "l2"), ("dot", "dot"), ("range", "l2")):
        prog = system.place({"k": 10, "metric": metric, "train_tile": 1024},
                            db, 1)

        def call():
            if kind == "range":
                return prog.range_search_certified(queries, radius_sq=2.0)
            return prog.search_certified(queries, selector="pallas",
                                         batch_size=32)

        call()
        before = system.registry_snapshot()
        call()
        call()
        out[kind] = system.registry_delta(before, system.registry_snapshot())
    obs.reset()
    return out


@pytest.mark.parametrize("kind", ["l2", "dot", "range"])
@pytest.mark.parametrize("name", NAMES)
def test_a_layer_file_reads_the_live_programs_span(live, name, kind):
    delta = live[kind]
    value = read(name, delta)
    # a number in every kind of call: 0.0, never absent, where the piece
    # did not run
    assert value is not None and 0 <= value < 60_000
    assert delta[series_key(name)][0] == 2  # calls, not sub-batches
    if name == "reselect_inflight_ms":
        (launches,) = delta[("knn_tpu_program_launches_total",
                             (("program", "reselect"),))]
        # 0.0 exactly where no query fell back
        assert (value > 0) == (launches > 0)
    if name == "host_exposed_ms":
        assert value > 0
    if kind != "range":
        per_batch = delta[("knn_tpu_span_seconds",
                           (("span", "certified.rank_correct"),))]
        assert per_batch[0] == 4  # why the stage metrics cannot stay


# --- through the whole harness ----------------------------------------------
@pytest.fixture(scope="module")
def root_with_entries(tmp_path_factory):
    root = tinyroot.make(str(tmp_path_factory.mktemp("account")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [e for e in ENTRIES if e["name"] not in have]
    with open(path, "w") as f:
        json.dump(bench, f)
    return root, bench


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


@pytest.mark.parametrize("workload", CELLS)
def test_a_traced_run_prints_the_five_in_every_cell(root_with_entries,
                                                    cpu_memory_reading,
                                                    workload):
    root, bench = root_with_entries
    lines = []
    out = harness.run_cell(root, workload, 2**31 + 41, 1.5, True,
                           time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], bench, workload, True) == out
    assert out["correct"] is True
    for name in NAMES:
        got = out["metrics"][name]
        assert got["unit"] == "ms" and got["value"] >= 0, name
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the host exposes some of every call, and the window idles
    idle_ms = 1e3 * (out["device"]["window_s"] - out["device"]["busy_s"])
    assert m["host_exposed_ms"] > 0 and idle_ms > 0


def test_on_a_program_without_the_spans_a_reader_finds_nothing():
    """The parent's case, and why no cell lists the five yet: the reader
    returns None and raises nothing, the line would leave the metric
    out, and ``lastline.validate`` refuses a traced line that does."""
    registry = recorded_registry(sorted(RECORDED["cells"])[0])
    for name in NAMES:
        without = {k: v for k, v in registry.items()
                   if k != series_key(name)}
        assert read(name, without) is None
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"] += [e for e in ENTRIES if e["name"] not in
                           {m["name"] for m in bench["per_layer"]}]
    cell = "gist1m.sweep"
    values = {m["name"]: 1.0 for m in lastline.per_layer_of(bench, cell)
              if m["name"] != "host_exposed_ms"}
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    line = lastline.build(
        correct=True, attempted=1, failed=0, values=values,
        units={k: units[k] for k in values},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "window_s": 1.0, "busy_s": 0.5})
    with pytest.raises(lastline.LastLineError, match="host_exposed_ms"):
        lastline.validate(line, bench, cell, True)
