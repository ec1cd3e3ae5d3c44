"""The stage metrics of the certified path that the cells of traffic
kind ``sweep`` list (``dispatch_ms.sweep``, ``device_wait_ms.sweep``,
``d2h_ms.sweep``, ``unpack_ms.sweep``, ``rank_correct_ms.sweep``,
``repair_ms.sweep`` and ``call_ms``): each is a ``span`` reader over one
series of the program's ``knn_tpu_span_seconds``.  What is held here is
that over the registry's change across one call, which is what
``drivers/sweep.py`` hands the harness, each file's reader finds the
program's span and gives a number, that the window's change holds one
span a batch, and that the call closes on its stages.
"""

import json
import os

import numpy as np
import pytest

import harness
import system
import tinyroot

BENCH = tinyroot.load_bench()
CELL = "bigann5m.sweep"
STAGES = {"dispatch_ms.sweep", "device_wait_ms.sweep", "d2h_ms.sweep",
          "unpack_ms.sweep", "rank_correct_ms.sweep", "repair_ms.sweep"}


def layer_file(metric: str) -> dict:
    with open(os.path.join(tinyroot.BENCH_DIR, "layers",
                           f"{metric}.json")) as f:
        return json.load(f)


#: the cell's per-layer entries whose reader is a span's
ENTRIES = [m for m in BENCH["per_layer"] if CELL in m["workloads"]
           and layer_file(m["name"])["reader"]["type"] == "span"]


@pytest.fixture(scope="module")
def window_delta():
    """The registry's change over one tiny certified call, the first
    call's float64 pass made before it, as a sweep's warm-up does."""
    from knn_tpu import obs

    obs.reset(enabled=True)
    rng = np.random.default_rng(2**31 + 17)
    db = rng.random((3000, 32), dtype=np.float32)
    prog = system.place({"k": 10, "metric": "l2", "train_tile": 1024}, db, 1)
    queries = rng.random((64, 32), dtype=np.float32)
    prog.search_certified(queries, selector="pallas")
    before = system.registry_snapshot()
    prog.search_certified(queries, selector="pallas")
    delta = system.registry_delta(before, system.registry_snapshot())
    obs.reset()
    return delta


def read(entry: dict, delta: dict) -> float:
    outcome = harness.Outcome(attempted=64, failed=0, end_to_end={},
                              checks=None, bench={}, registry=delta,
                              resident_bytes=0)
    return harness.read_metric(layer_file(entry["name"]),
                               harness.Readings(None, outcome, {}, None))


def test_the_sweep_cells_list_the_six_stages_and_the_call():
    assert {e["name"] for e in ENTRIES} == STAGES | {"call_ms"}
    for e in ENTRIES:
        assert e["workloads"] == ["bigann5m.sweep", "gist1m.sweep"]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_a_stage_metric_reads_the_programs_span(window_delta, entry):
    layer = layer_file(entry["name"])
    for key in ("layer", "unit", "moves", "source", "better"):
        assert layer[key] == entry[key], key
    # every cell listed reports the end-to-end metric the stage moves
    (moved,) = [m for m in BENCH["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    value = read(entry, window_delta)
    assert value is not None and 0 < value < 60_000  # ms of one tiny call
    # one span a batch in the window, and only the window's
    key = (layer["reader"]["series"],
           (("span", layer["reader"]["labels"]["span"]),))
    assert window_delta[key][0] == 1


@pytest.mark.parametrize("stage", sorted(STAGES))
def test_a_split_entry_is_its_quantitys_own(stage):
    """``<stage>.sweep`` differs from ``<stage>`` in its name, its cells
    and the sentence that says why it is listed apart."""
    base = stage[:-len(".sweep")]
    mine, theirs = layer_file(stage), layer_file(base)
    assert mine["metric"] == stage and theirs["metric"] == base
    assert mine["what"].startswith(theirs["what"])
    for key in set(mine) - {"metric", "what"}:
        assert mine[key] == theirs[key], key
    by = {m["name"]: m for m in BENCH["per_layer"]}
    assert {k: v for k, v in by[stage].items()
            if k not in ("name", "workloads")} == {
        k: v for k, v in by[base].items() if k not in ("name", "workloads")}
    assert not set(by[stage]["workloads"]) & set(by[base]["workloads"])


def test_the_call_closes_on_its_stages(window_delta):
    by = {e["name"]: read(e, window_delta) for e in ENTRIES}
    stages = sum(by[name] for name in STAGES)
    # what is left is certified.prepare and the call's own self time
    assert 0 < stages <= by["call_ms"]
