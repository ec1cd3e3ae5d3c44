"""The cell ``imagenet-knn768.sweep_vote`` through the whole harness at a
tiny size on the CPU (``test_cells.py`` runs it traced and untraced with
every other cell and breaks its answers by ``tiny_vote``'s breaker), and
what is this cell's own: the reference against the semantics spelled
out with the contract's edges in (equal totals, fewer classes than
asked, a zero row, duplicates), every control named by the
configuration coming out not correct, a traced run's three new
per-layer metrics, and a tree without the path refused before a row is
drawn.  Tier-1's ``tests/test_imagenet_vote.py`` holds the PROGRAM to
the reference; this file holds the yardstick.

Importing this module gives ``tinyroot``, ``test_cells`` and
``test_call_account`` their ``sweep_vote`` entries (``tiny_vote.py``
says why).
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_vote
import test_call_account
import test_cells

tiny_vote.break_the_vote(test_cells)
tiny_vote.join_the_call_account(test_call_account)

import datagen  # noqa: E402
import datagen_labels  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_vote  # noqa: E402
from tiny_vote import CELL  # noqa: E402

BENCH = tinyroot.load_bench()
NEW = {"vote_repair_ms", "vote_boundary_pct", "vote_margin_pct"}


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "imagenet-knn768.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_vote")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 48):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


def test_the_entries_are_appended_and_within_the_form():
    assert BENCH["configs"][-1]["name"] == "imagenet-knn768"
    assert BENCH["configs"][-1]["reduced"] == []
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "imagenet-knn768", "sweep_vote", 1)
    for text in (cell["why"], BENCH["configs"][-1]["why"],
                 BENCH["configs"][-1]["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    assert [m["workloads"] for m in new] == [[CELL]] * 3
    assert len({m["layer"] for m in new}) == 1
    for m in new:
        layer = _json("benchmark", "layers", f"{m['name']}.json")
        assert len(layer["what"]) <= 200
        assert {k: layer[k] for k in ("unit", "better", "source", "moves",
                                      "layer")} == {
            k: m[k] for k in ("unit", "better", "source", "moves", "layer")}


def test_a_traced_run_reads_the_three_new_metrics(root):
    out = run(root, True)
    assert out["correct"] is True
    for name in NEW:
        assert out["metrics"][name]["value"] >= 0, name
    assert out["metrics"]["vote_repair_ms"]["unit"] == "ms"


def _edges():
    """Twelve rows in the plane and three queries: equal totals (rows
    0 and 1 are one row under classes 4 and 2: the lower id first),
    fewer classes than asked, a zero row (cosine 0), duplicates under
    two labels."""
    ang = np.deg2rad([5, 5, 10, 20, 30, 40, 50, 60, 70, 80])
    db = np.stack([np.cos(ang), np.sin(ang)], 1).astype(np.float32)
    db[2:] *= np.linspace(0.5, 3.0, len(db) - 2, dtype=np.float32)[:, None]
    db = np.concatenate([db, np.zeros((1, 2), np.float32), db[3:4]])
    labels = np.array([4, 2, 7, 7, 1, 1, 1, 3, 3, 3, 9, 8], np.int32)
    q = np.array([[2.0, 0.0], [0.0, 0.5], [0.0, 0.0]], np.float32)
    return db, labels, q


def test_the_reference_on_the_contracts_edges():
    db, labels, q = _edges()
    classes, totals, idx = reference_vote.oracle(db, labels, q, 4, 0.07,
                                                 10, 5)
    # rows 0 and 1 are one row: equal cosines to the bit, index order,
    # and equal totals under classes 4 and 2
    assert list(idx[0][:2]) == [0, 1] and totals[0][0] > 0
    two = list(classes[0])
    assert two.index(2) < two.index(4)
    assert totals[0][two.index(2)] == totals[0][two.index(4)]
    # three classes among four neighbours: padded
    assert (classes[0] >= 0).sum() == 3 and classes[0][3] == -1
    assert totals[0][3] == 0 and totals[0][4] == 0
    # a zero query: cosine 0 to every row, the first rows by index, each
    # at weight exp(0)
    assert list(idx[2]) == [0, 1, 2, 3] and totals[2].sum() == 4
    # the duplicate of row 3 (index 11) follows it
    near = list(reference_vote.oracle(db, labels, db[3:4], 2, 0.07, 10,
                                      5)[2][0])
    assert near == [3, 11]


@pytest.mark.parametrize("how", reference_vote.CONTROLS)
def test_a_control_is_not_correct(how):
    spec = {**CONFIG["rows"], "classes": 100, "groups": 5}
    db, labels = datagen_labels.draw_rows(spec, 20_000, 768, 2**31 + 7,
                                          datagen.STREAM_ROWS)
    q, _ = datagen_labels.draw_queries(spec, 64, 768, 2**31 + 7,
                                       datagen.STREAM_QUERIES)
    args = (db, labels, q, 20, 0.07, 100, 5)
    want_c, want_t, _ = reference_vote.oracle(*args)
    cmp = reference_vote.compare(*reference_vote.control(*args, how),
                                 want_c, want_t)
    broke = {name for name, limit in CONFIG["limits"].items()
             if not cmp[name] <= limit}
    assert set(CONFIG["controls"][how]) <= broke, (how, cmp)
    # and the oracle itself is inside every limit
    same = reference_vote.compare(want_c, want_t, want_c, want_t)
    assert all(same[name] <= limit
               for name, limit in CONFIG["limits"].items())


def test_a_tree_without_the_path_is_refused_before_a_row_is_drawn(
        root, monkeypatch):
    from knn_tpu.parallel import ShardedKNN

    def parents(self, queries, *, margin=28, selector="approx"):
        raise AssertionError("never called")

    monkeypatch.setattr(ShardedKNN, "predict_certified", parents)
    monkeypatch.setattr(datagen_labels, "draw_rows",
                        lambda *a, **kw: pytest.fail("rows were drawn"))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="no weighted vote"):
        harness.run_cell(root, CELL, 1, 1.0, False, time.perf_counter())
    assert time.perf_counter() - t0 < 1.0
