"""The cell ``knnlm1m.sweep_k1024`` through the whole harness at a tiny
size on the CPU (``test_cells.py`` runs it traced and untraced with every
other cell and breaks its answers by the sweep's own breaker), and what
is this cell's own: the reference at any k against brute force spelled
out, with its blocks and its ties; both controls coming out not correct
by the limits the configuration names; the traced run's three new
per-layer metrics; a tree without ``certified_plan`` refused before a
row is drawn, and a plan outside ``require.plan`` refused before a call.
Tier-1's ``tests/test_knnlm_topk.py`` holds the PROGRAM to the
reference; this file holds the yardstick.

Importing this module gives ``tinyroot``, ``test_cells`` and
``test_call_account`` their ``sweep_topk`` entries (``tiny_topk.py``
says why).
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_topk
import test_call_account
import test_cells

tiny_topk.break_the_topk(test_cells)
tiny_topk.join_the_call_account(test_call_account)

import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_topk  # noqa: E402
from tiny_topk import CELL  # noqa: E402

BENCH = tinyroot.load_bench()
NEW = {"select_final_ms", "launches_per_call", "survivor_overflow_pct"}
#: the metrics of the older cells that the cell is appended to
APPENDED = {"kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
            "rank_corrected_pct", "idle_pct.sweep", "dispatch_ms",
            "device_wait_ms", "d2h_ms", "unpack_ms", "rank_correct_ms",
            "repair_ms", "host_exposed_ms", "reselect_inflight_ms",
            "rank_score_ms", "rank_order_ms", "rank_buffers_ms",
            "rank_members_per_query", "repair_refine_ms"}


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


#: BENCHMARK.json as it stands (``BENCH`` has the serve cell's entries
#: after it)
REAL = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", "knnlm1m.json")
TRAFFIC = _json("benchmark", "traffic", "sweep_k1024.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_topk")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 55):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


def test_the_entries_are_appended_and_within_the_form():
    cfg = [c for c in REAL["configs"] if c["name"] == "knnlm1m"]
    assert len(cfg) == 1 and cfg[0]["reduced"] == ["rows_n"]
    assert cfg[0]["source"] == CONFIG["source"]
    assert cfg[0]["file"] == "benchmark/configs/knnlm1m.json"
    (cell,) = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "knnlm1m", "sweep_k1024", 1)
    for text in (cell["why"], cfg[0]["why"], cfg[0]["source"]):
        assert 1 <= len(text) <= 200 and "\t" not in text and "\n" not in text
    mine = [m for m in REAL["per_layer"] if m["name"] in NEW]
    assert {m["name"] for m in mine} == NEW
    layers = {m["layer"] for m in REAL["per_layer"] if m["name"] not in NEW}
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["layer"] in layers  # a layer the benchmark already names
        layer = _json("benchmark", "layers", f"{m['name']}.json")
        assert {k: layer[k] for k in ("unit", "better", "source", "moves",
                                      "layer")} == {
            k: m[k] for k in ("unit", "better", "source", "moves", "layer")}
    listed = {m["name"] for m in REAL["per_layer"]
              if CELL in m["workloads"]}
    assert listed == NEW | APPENDED
    (qps,) = [m for m in REAL["end_to_end"] if m["name"] == "sweep_qps"]
    assert CELL in qps["workloads"]
    # at most a quarter of the cells on four chips, as it was: one
    assert sum(c["chips"] == 4 for c in REAL["workloads"]) == 1


def test_the_files_hold_what_the_issue_names():
    assert TRAFFIC["kind"] == "sweep_topk"
    assert (TRAFFIC["batch_rows"], TRAFFIC["pool_batches"],
            TRAFFIC["selector"], TRAFFIC["check_rows"],
            TRAFFIC["trace_seconds"]) == (4096, 8, "pallas", 64, 4)
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["k"], CONFIG["metric"],
            CONFIG["reference"]) == (1_000_000, 1024, 1024, "l2", "topk")
    assert "queries" not in CONFIG
    openai = _json("benchmark", "configs", "openai500k.json")
    gist = _json("benchmark", "configs", "gist1m.json")
    assert CONFIG["rows"] == openai["rows"]
    assert CONFIG["limits"] == {
        key: gist["limits"][key] for key in ("mismatched_rows",
                                             "dist_rel_err_max")}
    assert CONFIG["require"] == {
        "tuning_source": "default", "interpret": False,
        "plan": {"overflow_share_max": 0.05, "fits": True}}
    assert list(CONFIG["reduced_from_source"]) == ["rows_n"]
    assert set(CONFIG["controls"]) == set(reference_topk.PRECISIONS)
    assert set(CONFIG["limits_why"]) == set(CONFIG["limits"])


def test_a_traced_run_reads_the_three_new_metrics(root):
    out = run(root, True)
    assert out["correct"] is True
    assert set(out["metrics"]) == NEW | APPENDED
    assert out["metrics"]["select_final_ms"]["value"] > 0
    # a tiny call is one launch; 1,024 of 3,000 rows crowd every bin
    assert out["metrics"]["launches_per_call"]["value"] == 1.0
    assert (0 < out["metrics"]["survivor_overflow_pct"]["value"]
            <= out["metrics"]["fallback_pct"]["value"])


def test_an_untraced_run_answers_a_thousand_neighbours(root, capfd):
    out = run(root)
    assert out["correct"] is True
    assert out["attempted"] % 64 == 0 and out["failed"] == 0
    rows = out["compared"]
    assert rows["mismatched_rows"]["value"] == 0
    assert rows["dist_rel_err_max"]["value"] <= CONFIG["limits"][
        "dist_rel_err_max"]
    assert rows["compiles_in_window"]["value"] == 0
    said = capfd.readouterr().out
    # the plan is printed before the first batch, the answer's bytes after
    assert said.index("set-up: plan of a 64-query call") < said.index(
        "set-up: first batch")
    assert "'survivor_depth': " in said and "'k': 1024" in said
    assert f"{64 * 1024 * (8 + 8):,} bytes an answer" in said


def test_a_tree_without_the_plan_is_refused_before_a_row_is_drawn(
        root, monkeypatch):
    from knn_tpu.parallel import ShardedKNN

    monkeypatch.delattr(ShardedKNN, "certified_plan")
    monkeypatch.setattr(datagen_mix, "draw",
                        lambda *a, **kw: pytest.fail("rows were drawn"))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="no certified_plan"):
        harness.run_cell(root, CELL, 1, 1.0, False, time.perf_counter())
    assert time.perf_counter() - t0 < 1.0


def test_a_plan_outside_the_configurations_word_is_refused_before_a_call(
        root, tmp_path, monkeypatch):
    import shutil

    from knn_tpu.parallel import ShardedKNN

    held = str(tmp_path / "root")
    shutil.copytree(root, held)
    path = os.path.join(held, "benchmark", "configs", "knnlm1m.json")
    with open(path) as f:
        cfg = json.load(f)
    # the tiny corpus's bins are crowded at any depth: the real limit
    cfg["require"]["plan"] = CONFIG["require"]["plan"]
    with open(path, "w") as f:
        json.dump(cfg, f)
    monkeypatch.setattr(ShardedKNN, "search_certified",
                        lambda *a, **kw: pytest.fail("a call was made"))
    with pytest.raises(harness.BenchError, match="full-bin fallback share"):
        harness.run_cell(held, CELL, 1, 1.0, False, time.perf_counter())
    driver = harness._module("sweep_topk", "drivers")
    plan = {"overflow_share": 0.002, "survivor_depth": 4,
            "sub_batch_rows": 512, "launch_bytes": 10, "room_bytes": 9}
    with pytest.raises(harness.BenchError, match="beside the placement"):
        driver.hold_plan(plan, CONFIG["require"]["plan"])
    driver.hold_plan({**plan, "room_bytes": 10}, CONFIG["require"]["plan"])
    driver.hold_plan({**plan, "room_bytes": 0}, CONFIG["require"]["plan"])


# --- the reference --------------------------------------------------------
def brute(db, q, k):
    d = ((q.astype(np.float64)[:, None, :]
          - db.astype(np.float64)[None]) ** 2).sum(-1)
    order = np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d.shape), d),
                       axis=1)[:, :k]
    return order, np.take_along_axis(d, order, axis=1)


@pytest.mark.parametrize("k", [1, 37, 600, 1500])
def test_the_reference_is_brute_force_at_any_k(k, monkeypatch):
    rng = np.random.default_rng(k)
    db = rng.integers(0, 4, (1500, 6)).astype(np.float32)  # ties everywhere
    q = rng.integers(0, 4, (9, 6)).astype(np.float32)
    monkeypatch.setattr(reference_topk, "BLOCK", 256)  # six blocks
    ids, d = reference_topk.oracle_topk(db, q, k)
    want_i, want_d = brute(db, q, k)
    assert ids.dtype == np.int64 and ids.shape == (9, k)
    np.testing.assert_array_equal(ids, want_i)
    np.testing.assert_array_equal(d, want_d)
    same = reference_topk.compare(ids, d, want_i, want_d, db, q)
    assert (same["mismatched_rows"], same["dist_rel_err_max"],
            same["recall"]) == (0, 0.0, 1.0)
    with pytest.raises(ValueError):
        reference_topk.oracle_topk(db, q, 1501)


def test_the_comparison_counts_what_differs():
    want_i = np.arange(12).reshape(2, 6)
    want_d = np.arange(1.0, 13.0).reshape(2, 6)
    got_i = want_i.copy()
    got_i[1, [4, 5]] = got_i[1, [5, 4]]  # one swap in one row
    cmp = reference_topk.compare(got_i, want_d * (1 + 2e-6), want_i, want_d)
    assert cmp["mismatched_rows"] == 1 and cmp["recall"] == 1.0
    assert cmp["dist_rel_err_max"] == pytest.approx(2e-6, rel=1e-6)
    bad = want_d.copy()
    bad[0, 0] = np.inf
    assert reference_topk.compare(want_i, bad, want_i, want_d)[
        "dist_rel_err_max"] == np.inf
    with pytest.raises(ValueError):
        reference_topk.compare(want_i[:, :5], want_d, want_i, want_d)


@pytest.fixture(scope="module")
def drawn():
    spec = {**CONFIG["rows"], "clusters": 16}
    db = datagen_mix.draw(spec, 20_000, 1024, 2**31 + 7, datagen.STREAM_ROWS)
    q = datagen_mix.draw(spec, 8, 1024, 2**31 + 7, datagen.STREAM_QUERIES)
    return db, q, reference_topk.oracle_topk(db, q, 1024)


@pytest.mark.parametrize("precision", reference_topk.PRECISIONS)
def test_a_control_is_not_correct(drawn, precision):
    db, q, (want_i, want_d) = drawn
    cmp = reference_topk.compare(
        *reference_topk.lowprec_topk(db, q, 1024, precision),
        want_i, want_d, db, q)
    broke = {name for name, limit in CONFIG["limits"].items()
             if not cmp[name] <= limit}
    assert set(CONFIG["controls"][precision]) <= broke, (precision, cmp)
    # and the oracle itself is inside every limit
    same = reference_topk.compare(want_i, want_d, want_i, want_d)
    assert all(same[name] <= limit
               for name, limit in CONFIG["limits"].items())


def test_the_control_script_reads_the_configurations_word(root):
    import subprocess
    import sys

    script = os.path.join(tinyroot.BENCH_DIR, "control_topk.py")
    res = subprocess.run(
        [sys.executable, script, "--workload", CELL, "--precision", "bf16",
         "--seeds", "3,4", "--root", root],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stdout + res.stderr
    last = json.loads(res.stdout.splitlines()[-1])
    assert last["control_came_out_sound_on_some_seed"] is False
    assert last["closest_to_sound"]["mismatched_rows"] >= 1
    refused = subprocess.run(
        [sys.executable, script, "--workload", "gist1m.sweep", "--precision",
         "f32", "--seeds", "3", "--root", root],
        capture_output=True, text=True, timeout=300)
    assert refused.returncode != 0 and "no sweep_topk cell" in refused.stderr
