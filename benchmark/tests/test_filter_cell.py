"""The cell ``yfcc2m5.sweep_filter`` through the whole harness at a tiny
size on the CPU (``test_cells.py`` runs it traced and untraced with
every other cell, and breaks its answers as it breaks a plain sweep's),
and what is this cell's own: three broken TIMED paths that each have to
come out ``correct: false`` (an unfiltered search post-filtered to the
top 10; OR in AND's place; the validity words of the previous batch
reused), a tree that cannot take ``row_tags`` refused before a row is
drawn, the generator's bands, and the reference against brute force.

Importing this module gives ``tinyroot``, ``test_cells`` and
``test_call_account`` their ``sweep_filter`` entries (``tiny_filter.py``
says why).
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_filter
import test_call_account
import test_cells

tiny_filter.break_like_sweep(test_cells)
tiny_filter.join_the_call_account(test_call_account)

import datagen  # noqa: E402
import datagen_tags  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_filter  # noqa: E402

CELL = "yfcc2m5.sweep_filter"
BENCH = tinyroot.load_bench()


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "yfcc2m5.json")
TRAFFIC = _json("benchmark", "traffic", "sweep_filter.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_filter")))


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 40):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


# --- the files ----------------------------------------------------------------
def test_the_cells_files_agree():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "yfcc2m5"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["rows_n"] == list(
        CONFIG["reduced_from_source"])
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yfcc2m5", "sweep_filter", 1)
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["k"], CONFIG["metric"],
            CONFIG["vocabulary"]) == (2_500_000, 192, 10, "l2", 200_386)
    assert CONFIG["tags"]["vocabulary"] == CONFIG["vocabulary"]
    assert CONFIG["limits"] == {"mismatched_rows": 0, "invalid_returned": 0,
                                "dist_rel_err_max": 2.0 ** -18}
    strata = TRAFFIC["strata"]
    assert [s[0] for s in strata] == [0, 1, 10, 100, 1000, 10000, 100000]
    assert [s[1] for s in strata] == [64, 448, 768, 896, 896, 640, 384]
    assert sum(s[1] for s in strata) == TRAFFIC["batch_rows"] == 4096
    assert TRAFFIC["check_rows_a_band"] * len(strata) <= TRAFFIC["check_rows"]
    tiny = tiny_filter.TINY_SWEEP_FILTER
    assert sum(s[1] for s in tiny["strata"]) == tiny["batch_rows"]
    listed = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "kernel_ms", "tail_ms", "fallback_pct", "rank_corrected_pct",
        "idle_pct.sweep", "dispatch_ms", "device_wait_ms", "d2h_ms",
        "unpack_ms", "rank_correct_ms", "repair_ms",
        "pallas_knn_masked_roofline", "filter_mask_ms",
        "filter_mask_roofline", "filter_short_pct",
        "filter_list_ids_per_query"}
    # the unmasked scan's share is not this cell's: its work function
    # does not count the words
    assert "pallas_knn_roofline" not in listed


def test_the_work_functions_count_the_words():
    cell = harness.load_cell(tinyroot.ROOT, CELL)
    peaks = cell.peaks_table["kinds"]["TPU v5 lite"]
    scan = harness._module("knn_scan", "work")
    masked = harness._module("knn_scan_masked", "work")
    mask = harness._module("filter_mask", "work")
    q, n = 4096, 2_500_000
    ops, nbytes = scan.ops_bytes(cell.config, cell.traffic)
    assert masked.ops_bytes(cell.config, cell.traffic) == (
        ops, nbytes + q * n / 8)
    assert mask.ops_bytes(cell.config, cell.traffic) == (0.0, 3 * q * n / 8)
    assert masked.least_seconds(cell.config, cell.traffic, peaks) \
        >= scan.least_seconds(cell.config, cell.traffic, peaks)


# --- the cell -----------------------------------------------------------------
@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_and_fills_its_bands(root, traced):
    out = run(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        m = out["metrics"]
        # the tiny strata: 8 empty and 24 short of 64
        assert m["filter_short_pct"]["value"] == 50.0
        assert m["filter_list_ids_per_query"]["value"] >= 0
        assert m["filter_mask_ms"]["value"] > 0
        assert 0 < m["pallas_knn_masked_roofline"]["value"]


def _break_post_filter(monkeypatch):
    """The unfiltered top-10, with the rows that lack a tag dropped and
    the rest padded: what a post-filter gives."""
    from knn_tpu.ops import tagfilter
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def post(self, queries, *, filter_tags, **kw):
        d, i, stats = real(self, queries, **kw)
        inv = tagfilter.invert_bags(*self._row_tags)
        d, i = np.array(d), np.array(i)
        for row, tags in enumerate(filter_tags):
            ok = np.isin(i[row], tagfilter.valid_rows(
                *inv, self.n_train, *tags))
            keep = np.flatnonzero(ok)
            i[row] = np.concatenate([i[row][keep], np.full(
                (~ok).sum(), -1)])
            d[row] = np.concatenate([d[row][keep], np.full(
                (~ok).sum(), np.inf)])
        return d, i, stats

    monkeypatch.setattr(ShardedKNN, "search_certified", post)


def _break_or(monkeypatch):
    """OR in AND's place: the best ten of the rows that hold EITHER
    tag."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def either(self, queries, *, filter_tags, **kw):
        ft = np.asarray(filter_tags)
        one = np.stack([ft[:, 0], np.full(len(ft), -1)], axis=1)
        two = np.stack([np.where(ft[:, 1] >= 0, ft[:, 1], ft[:, 0]),
                        np.full(len(ft), -1)], axis=1)
        d1, i1, stats = real(self, queries, filter_tags=one, **kw)
        d2, i2, _ = real(self, queries, filter_tags=two, **kw)
        d, i = np.concatenate([d1, d2], 1), np.concatenate([i1, i2], 1)
        i = np.where(i < 0, np.iinfo(np.int64).max, i)
        out_d, out_i = np.empty_like(d1), np.empty_like(i1)
        for row in range(len(ft)):
            _, first = np.unique(i[row], return_index=True)
            order = first[np.lexsort((i[row][first], d[row][first]))][:10]
            pad = 10 - order.size
            out_d[row] = np.concatenate([d[row][order], np.full(pad, np.inf)])
            out_i[row] = np.concatenate([i[row][order], np.full(
                pad, np.iinfo(np.int64).max)])
        return out_d, np.where(np.isinf(out_d), -1, out_i), stats

    monkeypatch.setattr(ShardedKNN, "search_certified", either)


def _break_stale_words(monkeypatch):
    """Every batch after the first is held to the validity words of the
    batch before it."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN._filter_words
    kept = {}

    def stale(self, *a, **kw):
        make = real(self, *a, **kw)

        def mask(lo, take, rows):
            words = make(lo, take, rows)
            if words.shape[0] != kept.get("words", words).shape[0]:
                return words  # the repair's small batch
            before, kept["words"] = kept.get("words", words), words
            return before

        return mask

    monkeypatch.setattr(ShardedKNN, "_filter_words", stale)


@pytest.mark.parametrize("breaker", [_break_post_filter, _break_or,
                                     _break_stale_words])
def test_a_broken_filter_reads_not_correct(root, monkeypatch, breaker):
    breaker(monkeypatch)
    out = run(root)
    assert out["correct"] is False
    compared = out["compared"]
    assert compared["mismatched_rows"]["value"] > 0
    if breaker is not _break_post_filter:  # a post-filter returns no
        # invalid row: it loses valid ones
        assert compared["invalid_returned"]["value"] > 0


def test_a_tree_without_row_tags_is_refused_before_a_row_is_drawn(
        root, monkeypatch):
    driver = harness._module("sweep_filter", "drivers")
    monkeypatch.setattr(driver, "takes_row_tags", lambda: False)
    monkeypatch.setattr(datagen_tags, "draw", lambda *a, **kw: pytest.fail(
        "rows were drawn"))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="row_tags"):
        run(root)
    assert time.perf_counter() - t0 < 5


# --- the generator and the reference ------------------------------------------
def test_the_generator_fills_fixed_bands_from_the_seed():
    rows, tags = CONFIG["rows"], CONFIG["tags"]
    strata = tiny_filter.TINY_SWEEP_FILTER["strata"]
    out = []
    for _ in range(2):
        db, cluster = datagen_tags.draw(rows, 3000, 16, 2**31 + 5,
                                        datagen.STREAM_ROWS)
        indptr, flat = datagen_tags.draw_bags(tags, rows["clusters"],
                                              cluster, 2**31 + 5)
        inv = datagen_tags.Inverted(indptr, flat, tags["vocabulary"])
        out.append((db, indptr, flat, *datagen_tags.draw_queries(
            rows, CONFIG["queries"], tags, inv, 16, 2**31 + 5, 64, 3,
            strata)))
    for a, b in zip(*out):
        np.testing.assert_array_equal(a, b)  # the seed, nothing else
    db, indptr, flat, q, ft, matches, held = out[0]
    assert (db == np.rint(db)).all() and db.min() >= 0 and db.max() <= 255
    assert (np.diff(indptr) > 0).mean() > 0.99
    assert 8 < flat.size / 3000 < 13  # about eleven tags a row
    for row in range(0, 3000, 500):  # sorted sets
        bag = flat[indptr[row]:indptr[row + 1]]
        assert (np.diff(bag) > 0).all()
    np.testing.assert_array_equal(held, [[8, 24, 24, 8]] * 3)
    for pos in range(0, len(ft), 7):  # the counts are exact
        assert matches[pos] == reference_filter.valid_rows(
            indptr, flat, ft[pos]).size
    assert ((ft[:, 1] >= 0).mean() > 0.2) and (ft[:, 0] >= 0).all()


def test_the_reference_is_brute_force_over_the_valid_rows():
    rng = np.random.default_rng(3)
    n = 400
    db = rng.integers(0, 256, size=(n, 8)).astype(np.float32)
    db[7] = db[3]  # a tie across rows
    q = np.concatenate([db[3:4], rng.integers(0, 256, size=(5, 8))]
                       ).astype(np.float32)
    bags = [np.unique(rng.integers(0, 6, size=2)) for _ in range(n)]
    indptr = np.concatenate([[0], np.cumsum([b.size for b in bags])])
    tags = np.concatenate(bags).astype(np.int32)
    ft = np.asarray([[0, -1], [0, 1], [5, 4], [7, -1], [-1, -1], [2, 2]],
                    np.int32)
    got_i, got_d = reference_filter.oracle_topk(db, indptr, tags, q, ft, 10)
    d = ((q[:, None].astype(np.float64) - db[None]) ** 2).sum(-1)
    for row, pair in enumerate(ft):
        ok = np.asarray([all(t < 0 or t in bags[r] for t in pair)
                         for r in range(n)])
        rows = np.flatnonzero(ok)
        order = rows[np.lexsort((rows, d[row][rows]))][:10]
        np.testing.assert_array_equal(got_i[row][:order.size], order)
        assert (got_i[row][order.size:] == -1).all()
        np.testing.assert_array_equal(got_d[row][:order.size],
                                      d[row][order])
    same = reference_filter.compare(got_i, got_d, got_i, got_d, indptr,
                                    tags, ft)
    assert (same["mismatched_rows"], same["invalid_returned"],
            same["dist_rel_err_max"]) == (0, 0, 0.0)
    assert same["empty_rows"] == 1  # tag 7: no row
    wrong = got_i.copy()
    wrong[0, 0] = next(r for r in range(n) if 0 not in bags[r])
    broken = reference_filter.compare(wrong, got_d, got_i, got_d, indptr,
                                      tags, ft)
    assert (broken["mismatched_rows"], broken["invalid_returned"]) == (1, 1)
    short = got_d.copy()
    short[4, -1] = np.inf  # a full answer cut short
    assert reference_filter.compare(got_i, short, got_i, got_d, indptr,
                                    tags, ft)["dist_rel_err_max"] == np.inf
