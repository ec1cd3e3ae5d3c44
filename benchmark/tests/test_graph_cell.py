"""The cell ``deep5m-knng.build`` through the whole harness at a tiny
size on the CPU (``test_cells.py`` runs it traced and untraced with
every other cell and breaks its answers by ``tiny_graph``'s breaker,
which puts every row's own id back), and what is this cell's own: the
reference against the semantics spelled out with the contract's edges
in (the row itself out by id, exact copies kept in id order, a family of
copies longer than k), every control named by the configuration coming
out not correct by the limits it names, the generator's pairs, a traced
run's two new per-layer metrics, and a tree without the path refused
before a row is drawn.  Tier-1's ``tests/test_deep_knng.py`` holds the
PROGRAM to the reference; this file holds the yardstick.

Importing this module gives ``tinyroot``, ``test_cells`` and
``test_call_account`` their ``graph_build`` entries (``tiny_graph.py``
says why).
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_graph
import test_call_account
import test_cells

tiny_graph.break_the_graph(test_cells)
tiny_graph.join_the_call_account(test_call_account)

import datagen  # noqa: E402
import datagen_graph  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_graph  # noqa: E402
from tiny_graph import CELL  # noqa: E402

BENCH = tinyroot.load_bench()
NEW = {"join_exposed_ms", "join_block_ms"}
#: the metrics of the older cells that the cell is appended to
APPENDED = {"kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
            "rank_corrected_pct", "idle_pct.sweep", "dispatch_ms",
            "device_wait_ms", "d2h_ms", "unpack_ms", "rank_correct_ms",
            "repair_ms"}


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


#: BENCHMARK.json as it stands (``BENCH`` has the serve cell's entries
#: after it)
REAL = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", "deep5m-knng.json")
TRAFFIC = _json("benchmark", "traffic", "graph_build.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_graph")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 51):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


def test_the_entries_are_appended_and_within_the_form():
    assert REAL["configs"][-1]["name"] == "deep5m-knng"
    assert REAL["configs"][-1]["reduced"] == ["rows_n"]
    assert REAL["configs"][-1]["source"] == CONFIG["source"]
    assert REAL["workloads"][-1]["name"] == CELL
    cell = REAL["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deep5m-knng", "graph_build", 1)
    for text in (cell["why"], REAL["configs"][-1]["why"],
                 REAL["configs"][-1]["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text
    assert [m["name"] for m in REAL["per_layer"][-2:]] == sorted(
        NEW, reverse=True)
    for m in REAL["per_layer"][-2:]:
        assert m["workloads"] == [CELL]
        layer = _json("benchmark", "layers", f"{m['name']}.json")
        assert {k: layer[k] for k in ("unit", "better", "source", "moves",
                                      "layer")} == {
            k: m[k] for k in ("unit", "better", "source", "moves", "layer")}
    assert len({m["layer"] for m in REAL["per_layer"][-2:]}) == 1
    listed = {m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"]}
    assert listed == NEW | APPENDED
    for m in REAL["per_layer"]:
        if m["name"] in APPENDED:
            assert m["workloads"][-1] == CELL
    (qps,) = [m for m in REAL["end_to_end"] if m["name"] == "sweep_qps"]
    assert qps["workloads"][-1] == CELL


def test_the_traffic_file_holds_what_the_issue_names():
    assert TRAFFIC["kind"] == "graph_build"
    assert (TRAFFIC["call_rows"], TRAFFIC["block_rows"]) == (131072, 4096)
    assert TRAFFIC["batch_rows"] == TRAFFIC["block_rows"]
    assert TRAFFIC["call_rows"] == 32 * TRAFFIC["block_rows"]
    assert (TRAFFIC["selector"], TRAFFIC["check_rows"],
            TRAFFIC["check_copied_share"], TRAFFIC["trace_seconds"]) == (
        "pallas", 64, 0.5, 4)
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["k"], CONFIG["metric"],
            CONFIG["train_tile"], CONFIG["reference"]) == (
        5_000_000, 96, 10, "l2", 131072, "graph")
    assert set(CONFIG["controls"]) == set(reference_graph.CONTROLS)


def test_a_traced_run_reads_the_two_new_metrics(root):
    out = run(root, True)
    assert out["correct"] is True
    assert set(out["metrics"]) == NEW | APPENDED
    for name in NEW:
        assert out["metrics"][name]["value"] > 0, name
    # a tiny call is one block: the block's span holds the whole call
    # but its set-up, and the exposed part is inside it
    assert (out["metrics"]["join_exposed_ms"]["value"] * 32
            < 2 * out["metrics"]["join_block_ms"]["value"])


def test_an_untraced_run_counts_rows_and_checks_copied_ones(root):
    out = run(root)
    assert out["correct"] is True
    assert out["attempted"] % 500 == 0 and out["failed"] == 0
    rows = out["compared"]
    assert rows["mismatched_ids"]["value"] == 0
    assert rows["checked_copied_rows"]["value"] >= 4
    assert rows["self_in_answers"]["value"] == 0
    assert rows["self_not_excluded"]["value"] == 0
    assert rows["compiles_in_window"]["value"] == 0


def test_the_windows_first_row_is_a_seeded_block_offset():
    seen = {test_first for test_first in (
        harness._module("graph_build", "drivers").first_row(
            seed, 5_000_000, 4096) for seed in range(40))}
    assert len(seen) > 30 and all(s % 4096 == 0 and 0 <= s < 5_000_000
                                  for s in seen)


def _edges():
    """Forty rows on a line with a family of 13 copies of row 3 among
    them and a pair elsewhere: ids 3, 5, 7, ... hold the family."""
    db = np.arange(40, dtype=np.float32)[:, None] * np.ones(
        (1, 4), np.float32)
    family = np.arange(3, 29, 2)
    db[family] = db[3]
    db[30] = db[31]
    return db, family


def test_the_reference_on_the_contracts_edges():
    db, family = _edges()
    k = 10
    at = np.array([3, 9, 27, 31, 30, 0])
    ids, d = reference_graph.oracle_graph(db, at, k)
    # no row names itself, whatever its copies
    assert not (ids == at[:, None]).any()
    # a member of the family of 13: the 10 lowest ids of the 12 others,
    # at distance 0, in id order (the last member sees the first ten)
    assert list(ids[0]) == [5, 7, 9, 11, 13, 15, 17, 19, 21, 23]
    assert list(ids[1]) == [3, 5, 7, 11, 13, 15, 17, 19, 21, 23]
    assert list(ids[2]) == [3, 5, 7, 9, 11, 13, 15, 17, 19, 21]
    assert (d[:3] == 0).all()
    # a pair: each is the other's first neighbour, at 0
    assert (ids[3][0], d[3][0], ids[4][0], d[4][0]) == (30, 0.0, 31, 0.0)
    # a row with no copy: its neighbours by distance, the family (all at
    # row 3's place) in id order among them
    assert list(ids[5][:5]) == [1, 2, 3, 5, 7] and d[5][0] == 4.0
    # brute force, the plain statement
    full = ((db[at][:, None].astype(np.float64) - db[None]) ** 2).sum(-1)
    full[np.arange(at.size), at] = np.inf
    want = np.lexsort((np.broadcast_to(np.arange(40), full.shape), full),
                      axis=1)[:, :k]
    assert np.array_equal(ids, want)
    # drop_zero loses the copies, keep_self keeps the row
    zi, zd = reference_graph.control(db, at, k, "drop_zero")
    assert (zd[:5] > 0).all() and np.array_equal(zi[5], ids[5])
    si, _ = reference_graph.control(db, at, k, "keep_self")
    assert (si[3:, :2] == at[3:, None]).any(axis=1).all() and at[0] in si[0]


@pytest.fixture(scope="module")
def drawn():
    spec = {**CONFIG["rows"], "clusters": 64}
    db, pairs = datagen_graph.draw_rows(spec, 100_000, 96, 2**31 + 7,
                                        datagen.STREAM_ROWS)
    at = datagen_graph.check_rows(pairs, 8192, 8192 + 32768, 64, 2**31 + 7,
                                  datagen.STREAM_SAMPLE)
    return db, pairs, at


def test_the_generators_rows_are_unit_and_its_copies_exact(drawn):
    db, pairs, at = drawn
    assert pairs.shape == (1000, 2) and np.unique(pairs).size == 2000
    assert np.array_equal(db[pairs[:, 0]], db[pairs[:, 1]])
    norms = np.sqrt((db.astype(np.float64) ** 2).sum(1))
    assert np.abs(norms - 1).max() < 3e-7
    # half of the checked rows have a copy, all lie in the call
    assert np.isin(at, pairs).sum() == 32 and np.unique(at).size == 64
    assert at.min() >= 8192 and at.max() < 8192 + 32768
    again, pairs2 = datagen_graph.draw_rows(
        {**CONFIG["rows"], "clusters": 64}, 100_000, 96, 2**31 + 7,
        datagen.STREAM_ROWS)
    assert np.array_equal(again, db) and np.array_equal(pairs2, pairs)


@pytest.mark.parametrize("how", reference_graph.CONTROLS)
def test_a_control_is_not_correct(drawn, how):
    db, pairs, at = drawn
    want_i, want_d = reference_graph.oracle_graph(db, at, 10)
    cmp = reference_graph.compare(
        *reference_graph.control(db, at, 10, how), want_i, want_d)
    broke = {name for name, limit in CONFIG["limits"].items()
             if not cmp[name] <= limit}
    assert set(CONFIG["controls"][how]) <= broke, (how, cmp)
    if how == "drop_zero":
        # wrong exactly on the rows that have a copy
        assert cmp["mismatched_rows"] == 32
    # and the oracle itself is inside every limit
    same = reference_graph.compare(want_i, want_d, want_i, want_d)
    assert all(same[name] <= limit
               for name, limit in CONFIG["limits"].items())


def test_a_tree_without_the_path_is_refused_before_a_row_is_drawn(
        root, monkeypatch):
    import knn_tpu.join

    monkeypatch.delattr(knn_tpu.join, "knn_self_join")
    monkeypatch.setattr(datagen_graph, "draw_rows",
                        lambda *a, **kw: pytest.fail("rows were drawn"))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="no knn_self_join"):
        harness.run_cell(root, CELL, 1, 1.0, False, time.perf_counter())
    assert time.perf_counter() - t0 < 1.0
