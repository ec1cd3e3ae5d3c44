"""The cell ``openai500k-intfilter.sweep_cos_filter`` through the whole
harness at a tiny size on the CPU (``test_cells.py`` runs it traced and
untraced with every other cell and breaks its answers as it breaks a
plain sweep's), and what is this cell's own: the entries looked up BY
NAME, the files against what the issue names, the traced run's two new
per-layer metrics, two broken TIMED paths that each have to come out
``correct: false`` (a post-filter of the unfiltered answer; words made
for the ranges of another batch), a tree whose ``ShardedKNN`` takes no
``row_attr`` refused before a row is drawn, the reference against brute
force, and the controls through the script.  Tier-1's
``tests/test_cos_filter.py`` holds the PROGRAM to the reference; this
file holds the yardstick.

Importing this module gives ``tinyroot``, ``test_cells`` and
``test_call_account`` their ``sweep_cos_filter`` entries
(``tiny_cosfilter.py`` says why).
"""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import tinyroot
import tiny_cosfilter
import test_call_account
import test_cells

tiny_cosfilter.break_like_sweep(test_cells)
tiny_cosfilter.join_the_call_account(test_call_account)

import datagen_mix  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_cos  # noqa: E402
import reference_cosfilter  # noqa: E402
from tiny_cosfilter import CELL, CONFIG as CONFIG_NAME  # noqa: E402

BENCH = tinyroot.load_bench()
NEW = {"range_mask_ms", "range_mask_roofline"}
#: the metrics of the older cells that the cell is appended to
APPENDED = {"kernel_ms", "tail_ms", "fallback_pct", "rank_corrected_pct",
            "idle_pct.sweep", "dispatch_ms", "device_wait_ms", "d2h_ms",
            "unpack_ms", "rank_correct_ms", "repair_ms", "repair_refine_ms",
            "host_exposed_ms", "reselect_inflight_ms", "rank_score_ms",
            "rank_order_ms", "rank_buffers_ms", "rank_members_per_query",
            "slack_fallback_pct", "pallas_knn_masked_roofline"}


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


#: BENCHMARK.json as it stands (``BENCH`` has the serve cell's entries
#: after it)
REAL = _json("BENCHMARK.json")
CONFIG = _json("benchmark", "configs", f"{CONFIG_NAME}.json")
TRAFFIC = _json("benchmark", "traffic", "sweep_cos_filter.json")
DRIVER = harness._module("sweep_cos_filter", "drivers")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_cosfilter")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 57):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


# --- the files ----------------------------------------------------------------
def test_the_entries_are_appended_by_name_and_within_the_form():
    (cfg,) = [c for c in REAL["configs"] if c["name"] == CONFIG_NAME]
    assert cfg["reduced"] == [] == list(CONFIG["reduced_from_source"])
    assert cfg["source"] == CONFIG["source"]
    assert cfg["file"] == f"benchmark/configs/{CONFIG_NAME}.json"
    (cell,) = [c for c in REAL["workloads"] if c["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG_NAME, "sweep_cos_filter", 1)
    for text in (cell["why"], cfg["why"], cfg["source"]):
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
    # the tag maker's metrics are the tag cell's alone
    assert not {n for n in listed if n.startswith("filter_")}
    (qps,) = [m for m in REAL["end_to_end"] if m["name"] == "sweep_qps"]
    assert CELL in qps["workloads"]
    # at most a quarter of the cells on four chips, as it was: one
    assert sum(c["chips"] == 4 for c in REAL["workloads"]) == 1


def test_the_files_hold_what_the_issue_names():
    assert TRAFFIC["kind"] == "sweep_cos_filter"
    assert (TRAFFIC["batch_rows"], TRAFFIC["pool_batches"],
            TRAFFIC["selector"], TRAFFIC["check_rows"],
            TRAFFIC["trace_seconds"], TRAFFIC["filter_from"],
            TRAFFIC["check_flagged_rows"]) == (
        4096, 8, "pallas", 64, 4, [5000, 495000], 16)
    ranges = DRIVER.batch_ranges(TRAFFIC["filter_from"], 4096, 500_000)
    assert (ranges[:, 1] == 500_000).all()
    assert (ranges[0::2, 0] == 5000).all() and (
        ranges[1::2, 0] == 495_000).all()
    # each of a call's four launches holds 512 of each rate
    for lo in range(0, 4096, 1024):
        assert (ranges[lo:lo + 1024, 0] == 5000).sum() == 512
    openai = _json("benchmark", "configs", "openai500k.json")
    for key in ("rows_n", "dim", "metric", "k", "rows", "train_tile",
                "require"):
        assert CONFIG[key] == openai[key], key
    assert "queries" not in CONFIG
    assert CONFIG["reference"] == "cosfilter"
    assert CONFIG["limits"] == {
        "mismatched_rows": 0, "invalid_returned": 0,
        "dist_err_max": openai["limits"]["dist_err_max"]} and CONFIG[
        "limits"]["dist_err_max"] == 2.0 ** -21
    assert set(CONFIG["limits_why"]) == set(CONFIG["limits"])
    assert CONFIG["controls"] == {
        "post_filter": ["mismatched_rows"], "f32": ["dist_err_max"],
        "bf16": ["mismatched_rows", "dist_err_max"]}
    assert set(openai["assumed"]) < set(CONFIG["assumed"])
    ids = DRIVER.row_ids(7)
    assert ids.tolist() == list(range(7)) and ids.dtype == np.int64


def test_the_work_function_counts_a_launchs_reads_and_the_words():
    cell = harness.load_cell(tinyroot.ROOT, CELL)
    peaks = cell.peaks_table["kinds"]["TPU v5 lite"]
    work = harness._module("range_mask", "work")
    q, n = 4096, 500_000
    assert work.ops_bytes(cell.config, cell.traffic) == (
        0.0, 4 * 4.0 * n + q * n / 8)
    assert work.ops_bytes(cell.config, {"batch_rows": 64}) == (
        0.0, 4.0 * n + 64 * n / 8)
    assert work.least_seconds(cell.config, cell.traffic, peaks) == (
        4 * 4.0 * n + q * n / 8) / peaks["hbm_bytes_per_s"]


# --- the cell -----------------------------------------------------------------
@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_and_compares_both_rates(root, traced, capfd):
    out = run(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    rows = out["compared"]
    assert rows["mismatched_rows"]["value"] == 0
    assert rows["invalid_returned"]["value"] == 0
    assert rows["dist_err_max"]["value"] <= CONFIG["limits"]["dist_err_max"]
    said = capfd.readouterr().out
    # the tiny 99 % rate leaves 30 rows for k = 100: half of every batch
    # comes back short, and the sample holds some of them (four of its
    # eight where no repaired query takes a seeded draw's place)
    assert "'filter': 'range', 'short': 32, 'empty': 0" in said
    short, empty = re.search(
        r"'short_rows': (\d+), 'empty_rows': (\d+)", said).groups()
    assert 2 <= int(short) <= 6 and int(empty) == 0
    if traced:
        m = out["metrics"]
        assert set(m) == NEW | APPENDED
        assert m["range_mask_ms"]["value"] > 0
        assert 0 < m["range_mask_roofline"]["value"] <= 100
        assert 0 < m["pallas_knn_masked_roofline"]["value"] <= 100


def test_repaired_queries_are_among_the_compared(root, capfd, monkeypatch):
    """A batch whose last call fell back hands the sample its repaired
    queries, up to the traffic file's count, in the seeded draws'
    place."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN._certify_pallas

    def flag_some(self, batches, bs, d, i, q_np, *a, **kw):
        bad, n_corrected, by_slack = real(self, batches, bs, d, i, q_np,
                                          *a, **kw)
        return (np.union1d(bad, np.arange(3, 64, 9)), n_corrected, by_slack)

    monkeypatch.setattr(ShardedKNN, "_certify_pallas", flag_some)
    out = run(root)
    assert out["correct"] is True
    said = capfd.readouterr().out
    assert "on 8 queries, 4 of them repaired ones of pool batch" in said
    pick_b, pick_r = DRIVER.pick(5, [0, 1], 64, 2, 8, ([1] * 3, [3, 12, 21]))
    assert pick_b[:3].tolist() == [1, 1, 1]
    assert pick_r[:3].tolist() == [3, 12, 21]
    # the rest in equal shares of the two rates (3 and 2 of 5)
    assert (pick_r[3:] % 2).tolist() == [0, 0, 0, 1, 1]


def _break_post_filter(monkeypatch):
    """The unfiltered answer with the rows outside each query's range
    dropped and the rest padded: what a post-filter gives."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def broken(self, queries, *, filter_range=None, **kw):
        d, i, stats = real(self, queries, **kw)
        d, i = np.array(d), np.array(i)
        ok = reference_cosfilter.in_range(self._row_attr, filter_range)
        keep = np.take_along_axis(ok, i, axis=1)
        order = np.argsort(~keep, axis=1, kind="stable")
        d = np.where(np.take_along_axis(keep, order, axis=1),
                     np.take_along_axis(d, order, axis=1), np.inf)
        i = np.where(np.isfinite(d), np.take_along_axis(i, order, axis=1), -1)
        return d, i, {**stats, "fallback_positions": []}

    monkeypatch.setattr(ShardedKNN, "search_certified", broken)


def _break_words_of_other_queries(monkeypatch):
    """Every query answered under its neighbour's range: rows outside a
    query's own range come back."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def broken(self, queries, *, filter_range=None, **kw):
        return real(self, queries, filter_range=np.roll(filter_range, 1,
                                                        axis=0), **kw)

    monkeypatch.setattr(ShardedKNN, "search_certified", broken)


@pytest.mark.parametrize("breaker,by", [
    (_break_post_filter, "mismatched_rows"),
    (_break_words_of_other_queries, "invalid_returned")])
def test_a_broken_filter_reads_not_correct(root, monkeypatch, breaker, by):
    breaker(monkeypatch)
    out = run(root)
    assert out["correct"] is False
    assert out["compared"][by]["value"] > 0


def test_a_tree_without_row_attr_is_refused_before_a_row_is_drawn(
        root, monkeypatch):
    monkeypatch.setattr(DRIVER, "takes_row_attr", lambda: False)
    monkeypatch.setattr(datagen_mix, "draw",
                        lambda *a, **kw: pytest.fail("rows were drawn"))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="takes no row_attr"):
        harness.run_cell(root, CELL, 1, 1.0, False, time.perf_counter())
    assert time.perf_counter() - t0 < 1.0


# --- the reference ------------------------------------------------------------
def brute(db, attr, q, ranges, k):
    """The contract spelled out: a float64 argsort over every row, the
    rows out of range at +inf, einsum's own loop so that equal rows tie
    to the bit."""
    d64, q64 = db.astype(np.float64), q.astype(np.float64)
    den = (np.sqrt((q64 * q64).sum(-1))[:, None]
           * np.sqrt((d64 * d64).sum(-1))[None, :])
    cos = np.zeros_like(den)
    np.divide(np.einsum("qd,nd->qn", q64, d64), den, out=cos, where=den > 0)
    c = np.where((attr[None, :] >= ranges[:, :1])
                 & (attr[None, :] < ranges[:, 1:]), 1.0 - cos, np.inf)
    idx = np.broadcast_to(np.arange(db.shape[0]), c.shape)
    order = np.lexsort((idx, c), axis=-1)[:, :k]
    c = np.take_along_axis(c, order, axis=1)
    return np.where(np.isfinite(c), order, -1), c


def test_the_reference_is_brute_force_with_the_edges_in(monkeypatch):
    rng = np.random.default_rng(57)
    db = rng.integers(-2, 3, (900, 5)).astype(np.float32)  # ties, zero rows
    db[7] = 0.0
    attr = rng.permutation(900).astype(np.int64) - 300
    q = rng.integers(-2, 3, (11, 5)).astype(np.float32)
    q[4] = 0.0
    ranges = np.stack([rng.integers(-350, 500, 11),
                       rng.integers(-350, 700, 11)], axis=1)
    ranges[0], ranges[1], ranges[2] = [-300, 600], [5, 5], [-300, -294]
    monkeypatch.setattr(reference_cosfilter, "CHUNK", 128)  # eight blocks
    for k in (1, 10, 100):
        ids, c = reference_cosfilter.oracle_topk(db, attr, q, ranges, k)
        want_i, want_c = brute(db, attr, q, ranges, k)
        assert ids.dtype == np.int64 and ids.shape == (11, k)
        np.testing.assert_array_equal(ids, want_i)
        np.testing.assert_array_equal(c, want_c)
        same = reference_cosfilter.compare(ids, c, want_i, want_c, attr,
                                           ranges)
        assert (same["mismatched_rows"], same["invalid_returned"],
                same["dist_err_max"]) == (0, 0, 0.0)
    # a range that keeps every row is the cosine cell's own oracle
    every = np.tile([[-300, 600]], (11, 1))
    ids, c = reference_cosfilter.oracle_topk(db, attr, q, every, 10)
    plain_i, plain_c = reference_cos.oracle_topk(db, q, 10)
    np.testing.assert_array_equal(ids, plain_i)
    np.testing.assert_array_equal(c, plain_c)


def test_the_comparison_counts_what_differs():
    attr = np.arange(20)
    ranges = np.asarray([[0, 10], [5, 20]])
    want_i = np.asarray([[1, 2, 3], [6, 7, -1]])
    want_d = np.asarray([[0.1, 0.2, 0.3], [0.1, 0.2, np.inf]])
    same = reference_cosfilter.compare(want_i, want_d, want_i, want_d, attr,
                                       ranges)
    assert (same["mismatched_rows"], same["invalid_returned"],
            same["dist_err_max"], same["short_rows"],
            same["empty_rows"]) == (0, 0, 0.0, 1, 0)
    got_i = np.asarray([[1, 2, 12], [6, 4, 25]])  # out of range, no row
    got_d = np.asarray([[0.1, 0.2 * (1 + 1e-6), 0.3], [0.1, 0.2, 0.5]])
    cmp = reference_cosfilter.compare(got_i, got_d, want_i, want_d, attr,
                                      ranges)
    assert cmp["mismatched_rows"] == 2 and cmp["invalid_returned"] == 3
    assert cmp["dist_err_max"] == np.inf  # finite on one side only
    cmp = reference_cosfilter.compare(want_i, got_d[:, :3] * [1, 1, np.inf],
                                      want_i, want_d, attr, ranges)
    assert cmp["dist_err_max"] == np.inf
    with pytest.raises(ValueError):
        reference_cosfilter.compare(want_i[:, :2], want_d, want_i, want_d,
                                    attr, ranges)


def test_the_control_script_reads_the_configurations_word(root):
    script = os.path.join(tinyroot.BENCH_DIR, "control_cosfilter.py")
    res = subprocess.run(
        [sys.executable, script, "--workload", CELL, "--seeds", "3,4",
         "--root", root],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    last = json.loads(res.stdout.splitlines()[-1])
    # at 3,000 rows the post-filter and bfloat16 break what the
    # configuration names on every seed; float32's distances need the
    # cell's own size to leave the limit (PERF.md section 4), and the
    # script's exit code says that some control did not
    assert last["closest_to_sound"]["post_filter"]["mismatched_rows"] >= 1
    assert last["closest_to_sound"]["bf16"]["mismatched_rows"] >= 1
    assert last["closest_to_sound"]["bf16"]["dist_err_max"] > CONFIG[
        "limits"]["dist_err_max"]
    assert res.returncode == int(
        not last["every_seed_broke_what_the_configuration_names"])
    refused = subprocess.run(
        [sys.executable, script, "--workload", "openai500k.sweep_cos",
         "--seeds", "3", "--root", root],
        capture_output=True, text=True, timeout=300)
    assert refused.returncode != 0
    assert "no sweep_cos_filter cell" in refused.stderr
