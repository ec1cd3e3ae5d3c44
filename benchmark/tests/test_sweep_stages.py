"""The six stage metrics of the certified path (``layers/dispatch_ms``,
``device_wait_ms``, ``d2h_ms``, ``unpack_ms``, ``rank_correct_ms``,
``repair_ms``) are data that no cell reads yet: ``drivers/sweep.py``
hands the harness an empty registry.  What is held here is that the day
a driver hands it ``system.registry_delta`` over the window, each file's
reader finds the program's span and gives a number, and that the entries
of ``data/sweep_stages_cell.json`` are the ones the files describe.
"""

import json
import os

import numpy as np
import pytest

import harness
import system
import tinyroot

with open(os.path.join(tinyroot.HERE, "data", "sweep_stages_cell.json")) as f:
    ENTRIES = json.load(f)["per_layer"]


def layer_file(metric: str) -> dict:
    with open(os.path.join(tinyroot.BENCH_DIR, "layers",
                           f"{metric}.json")) as f:
        return json.load(f)


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


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_a_stage_metric_reads_the_programs_span(window_delta, entry):
    layer = layer_file(entry["name"])
    for key in ("layer", "unit", "moves", "source", "better"):
        assert layer[key] == entry[key], key
    assert layer["reader"]["type"] == "span"
    # every cell listed reports the end-to-end metric the stage moves
    (moved,) = [m for m in tinyroot.load_bench()["end_to_end"]
                if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    outcome = harness.Outcome(attempted=64, failed=0, end_to_end={},
                              checks=None, bench={}, registry=window_delta,
                              resident_bytes=0)
    value = harness.read_metric(
        layer, harness.Readings(None, outcome, {}, None))
    assert value is not None and 0 < value < 60_000  # ms of one tiny call
    # one span a batch in the window, and only the window's
    key = (layer["reader"]["series"],
           (("span", layer["reader"]["labels"]["span"]),))
    assert window_delta[key][0] == 1


def test_the_stage_entries_use_layer_names_the_benchmark_has_or_one_new():
    bench = tinyroot.load_bench()
    known = {m["layer"] for m in bench["per_layer"]}
    layers = [e["layer"] for e in ENTRIES]
    assert len(ENTRIES) == 6 and len({e["name"] for e in ENTRIES}) == 6
    assert not {e["name"] for e in ENTRIES} & {
        m["name"] for m in bench["per_layer"]}
    new = set(layers) - known
    assert len(new) == 1  # the host-transfer layer; PERF.md section 3
    assert layers.count("host repair (ops/refine.py, ops/certified.py)") == 3
