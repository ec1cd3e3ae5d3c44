"""Exact range search (``ssnpp2m5``):
``ShardedKNN.range_search_certified``, held to its contract: for every
query exactly ``{t : d64(q, t) <= radius_sq}``, inclusive, in (distance,
index) order, nothing capped and nothing dropped.  On the CPU, the
kernel interpreted, at sizes a test can hold:

- the system against the plain reference (``benchmark/reference_range.py``)
  on seeded ``datagen_dup`` near-duplicate bytes and on float rows, on
  one device and db-sharded over four;
- planted pairs at exactly ``radius_sq`` and at ``radius_sq + 1``; queries
  with 0, fewer than k, exactly k, more than k and more than the collect
  width's results;
- the completion's device pieces (``ops.radius``) on their own;
- the spans, the counters and ``stats["range"]``;
- the reference itself, its broken forms and ``compare``; ``datagen_dup``
  whatever the thread count, what it draws and its boundary queries;
- the cell ``ssnpp2m5.sweep_range`` through the whole benchmark harness,
  traced and not, and the broken timed paths coming out
  ``correct: false``; the cell's data files;
- two review findings on the bounded radius path.
"""

import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops import radius as rad
from knn_tpu.ops import refine
from knn_tpu.parallel import ShardedKNN, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import datagen  # noqa: E402  (benchmark/)
import datagen_dup  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_range  # noqa: E402
import tinyroot  # noqa: E402  (benchmark/tests/)

CELL = "ssnpp2m5.sweep_range"
#: 96,237 = 255^2 + 176^2 + 15^2 + 3^2 + 1 + 1: a byte pair can lie at
#: exactly the source's radius
RADIUS_SQ = 96237
AT_RADIUS = (255, 176, 15, 3, 1, 1)
K = 10
TILE = 1024


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "ssnpp2m5.json")
TRAFFIC = _json("benchmark", "traffic", "sweep_range.json")
#: the traffic's shares at a batch of 64
TINY_SHARES = {"unrelated": 44, "small_family": 16, "heavy_family": 4}


def mesh(db_shards: int = 1):
    return make_mesh(1, db_shards, devices=jax.devices()[:db_shards])


def brute(db, q, radius_sq, strict=False):
    """Per query ``(idx, d)`` by a float64 argsort of direct
    differences: the oracle's oracle."""
    out = []
    for row in q.astype(np.float64):
        d = ((db.astype(np.float64) - row) ** 2).sum(-1)
        idx = np.flatnonzero(d < radius_sq if strict else d <= radius_sq)
        idx = idx[np.lexsort((idx, d[idx]))]
        out.append((idx, d[idx]))
    return out


def assert_equals(got, want):
    lims, idx, dist = got[:3]
    assert lims.dtype == idx.dtype == np.int64 and dist.dtype == np.float64
    assert lims.shape == (len(want) + 1,) and lims[0] == 0
    for i, (wi, wd) in enumerate(want):
        np.testing.assert_array_equal(idx[lims[i]:lims[i + 1]], wi, str(i))
        np.testing.assert_array_equal(dist[lims[i]:lims[i + 1]], wd, str(i))


def near_dups(n, batch=64, n_batches=1, seed=2**31 + 34, shares=None,
              boundary_pairs=0):
    rows = datagen_dup.draw(CONFIG["rows"], n, 256, seed, datagen.STREAM_ROWS)
    q, kinds = datagen_dup.draw_queries(
        CONFIG["rows"], n, 256, seed, batch, n_batches,
        shares or TINY_SHARES, TRAFFIC["small_max"], TRAFFIC["heavy_min"],
        RADIUS_SQ, boundary_pairs)
    return rows, q, kinds


# --- the system against the plain reference ----------------------------------
@pytest.mark.parametrize("selector", ["pallas", "approx"])
@pytest.mark.parametrize("shards,n", [(1, 3000), (4, 4099)])
def test_range_search_equals_the_oracle_on_near_duplicate_bytes(
        shards, n, selector):
    db, q, kinds = near_dups(n)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=TILE)
    got = prog.range_search_certified(q, radius_sq=RADIUS_SQ,
                                      selector=selector)
    want = reference_range.oracle_range(db, q, RADIUS_SQ)
    cmp = reference_range.compare(got[:3], want)
    assert cmp["mismatched_rows"] == 0 and cmp["dist_rel_err_max"] == 0
    assert_equals(got, brute(db, q, RADIUS_SQ))
    # the shares show: unrelated queries find nothing, the long ones pass k
    sizes = np.diff(got[0])
    assert (sizes[kinds == 0] == 0).all() and (sizes[kinds > 0] > 0).all()
    assert sizes[kinds == 2].min() > K
    stats = got[3]
    assert stats["range"]["truncated"] >= 4 and stats["range"]["host_scan"] == 0
    assert stats["range"]["results"] == sizes.sum()
    if selector == "pallas":
        assert stats["tuning"]["source"] == "default"
        assert stats["pallas_knobs"]["terms"] == "hh"


@pytest.mark.parametrize("shards", [1, 4])
def test_range_search_equals_the_oracle_on_float_rows(shards):
    """Float rows: membership at the boundary is float64's, whatever the
    float32 passes read, and every distance is float64 per pair."""
    rng = np.random.default_rng(34)
    db = rng.random((2500, 24), dtype=np.float32)
    q = rng.random((48, 24), dtype=np.float32)
    d = ((db[None].astype(np.float64) - q[:, None]) ** 2).sum(-1)
    # a radius that IS the 40th distance of one query, to float64's bit:
    # inclusive by the contract, and inside float32's band of itself
    radius_sq = float(np.sort(d[3])[39])
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=TILE)
    got = prog.range_search_certified(q, radius_sq=radius_sq)
    assert_equals(got, brute(db, q, radius_sq))
    assert got[0][4] - got[0][3] == 40
    sizes = np.diff(got[0])
    assert sizes.min() < K < sizes.max()
    cmp = reference_range.compare(
        got[:3], reference_range.oracle_range(db, q, radius_sq))
    assert cmp["mismatched_rows"] == 0 and cmp["dist_rel_err_max"] < 2**-40


def planted(n=3000, dim=256, seed=34):
    """Uniform byte rows far from everything, and six queries built to
    have 0, fewer than k, exactly k, more than k and more than the
    collect width's results, with rows at exactly the radius and one past it."""
    rng = np.random.default_rng(seed)
    db = rng.integers(0, 256, (n, dim)).astype(np.float32)
    free = iter(rng.permutation(n))

    def at(base, cols, step):
        row = base.copy()
        row[list(cols)] += np.asarray(step, np.float32)
        return row

    q = np.zeros((6, dim), np.float32)
    want_sizes = []
    for j, count in enumerate((0, K - 3, K, K + 1, 40, 600)):
        base = rng.integers(16, 240, dim).astype(np.float32)
        base[:8] = 0
        q[j] = base
        for c in range(count):
            # distinct rows inside the radius: one column moved by c + 1
            db[next(free)] = at(base, [8 + c % 200], [1 + c // 200])
        if count:
            # the boundary: a row at exactly radius_sq (in), one at
            # radius_sq + 1 (out: the seventh column moved by 1 more)
            db[next(free)] = at(base, range(6), AT_RADIUS)
            db[next(free)] = at(base, range(7), AT_RADIUS + (1,))
        want_sizes.append(count + bool(count))
    return db, q, want_sizes


@pytest.mark.parametrize("shards", [1, 4])
def test_every_truncation_case_and_the_inclusive_boundary(shards):
    db, q, want_sizes = planted()
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=TILE)
    got = prog.range_search_certified(q, radius_sq=RADIUS_SQ)
    assert np.diff(got[0]).tolist() == want_sizes == [0, 8, 11, 12, 41, 601]
    want = brute(db, q, RADIUS_SQ)
    assert_equals(got, want)
    # the last row of every non-empty list is the one AT the radius; the
    # one a unit past it is nowhere
    lims, _, dist, stats = got
    assert (dist[lims[2:] - 1] == RADIUS_SQ).all()
    assert dist.max() == RADIUS_SQ
    strict = brute(db, q, RADIUS_SQ, strict=True)
    assert [len(i) for i, _ in strict] == [0, 7, 10, 11, 40, 600]
    # fewer than k is complete from the first pass (k-th over the radius);
    # exactly k and more are finished by the completion; more than the
    # collect width (512 at k = 10) by the host scan
    assert rad.range_width(K) == 512
    assert stats["range"] == {
        "queries": 6, "radius_sq": float(RADIUS_SQ), "complete": 2,
        "truncated": 3, "host_scan": 1, "results": sum(want_sizes),
        "width": 512, "sub_batches": 1}


def test_other_metrics_and_bad_radii_are_refused_loudly():
    db = np.random.default_rng(0).random((64, 8), dtype=np.float32)
    for metric in ("cosine", "dot", "l1"):
        prog = ShardedKNN(db, mesh=mesh(), k=4, metric=metric)
        with pytest.raises(ValueError, match="l2 family only"):
            prog.range_search_certified(db[:2], radius_sq=1.0)
    prog = ShardedKNN(db, mesh=mesh(), k=4)
    for bad in (-1.0, float("inf"), float("nan")):
        with pytest.raises(ValueError, match="radius_sq"):
            prog.range_search_certified(db[:2], radius_sq=bad)


def test_all_the_rows_there_are_leave_nothing_to_complete():
    db = np.random.default_rng(1).integers(0, 4, (8, 4)).astype(np.float32)
    prog = ShardedKNN(db, mesh=mesh(), k=8)
    got = prog.range_search_certified(db[:3], radius_sq=1e6,
                                      selector="exact")
    assert np.diff(got[0]).tolist() == [8, 8, 8]
    assert got[3]["range"]["truncated"] == 0
    assert_equals(got, brute(db, db[:3], 1e6))


# --- the completion's device pieces ------------------------------------------
@pytest.mark.parametrize("n,tile", [(3000, 1024), (750, 1024), (20, 1024),
                                    (4096, 4096), (5000, 2048)])
def test_within_words_marks_what_compact_and_decode_give_back(n, tile):
    rng = np.random.default_rng(n)
    db = rng.integers(0, 256, (n, 16)).astype(np.float32)
    q = db[:8] + rng.integers(-3, 4, (8, 16)).astype(np.float32)
    d = ((q[:, None].astype(np.float64) - db[None]) ** 2).sum(-1)
    thr = np.quantile(d, 0.05, axis=1).astype(np.float32)
    valid = n - 3  # the last rows are padding: never marked
    counts, words = jax.jit(lambda a, b, t: rad.within_words(
        a, b, t, tile=tile, n_valid=valid))(db, q, thr)
    rows, tile_eff, n_tiles = rad.words_geometry(n, tile)
    assert words.shape == (8, n_tiles * tile_eff // rad.WORD_BITS)
    assert rows >= n and tile_eff % rad.WORD_BITS == 0
    qi, ri = rad.decode_words(np.asarray(rad.compact_words(words, 4096)),
                              n, tile)
    want = [(a, b) for a in range(8) for b in range(valid)
            if d[a, b] <= thr[a]]
    assert sorted(zip(qi.tolist(), ri.tolist())) == want
    assert np.asarray(counts).tolist() == np.bincount(
        [a for a, _ in want], minlength=8).tolist()


def test_a_negative_threshold_marks_nothing():
    db = np.zeros((64, 4), np.float32)
    counts, words = rad.within_words(db, db[:2], np.float32([-1.0, 0.0]),
                                     tile=64)
    assert np.asarray(counts).tolist() == [0, 64]
    assert not np.asarray(words)[0].any()


def test_the_collect_width_is_read_off_k():
    assert rad.range_width(100) == rad.range_width(128) == 4096
    assert rad.range_width(129) == 8192
    assert rad.range_width(10) == 512
    assert rad.range_width(1) == 32


def test_exact_pair_scores_is_the_flat_form_of_exact_scores():
    rng = np.random.default_rng(2)
    db = rng.random((500, 12), dtype=np.float32)
    q = rng.random((7, 12), dtype=np.float32)
    idx = rng.integers(0, 500, (7, 9))
    for metric in ("l2", "dot"):
        flat = refine.exact_pair_scores(
            db, q, np.repeat(np.arange(7), 9), idx.reshape(-1), metric)
        np.testing.assert_array_equal(
            flat.reshape(7, 9), refine.exact_scores(db, q, idx, metric))
    qi, ti, d = refine.host_exact_range(db, q, 0.9)
    want = brute(db, q, 0.9)
    assert d.size == sum(len(i) for i, _ in want)
    for a, (wi, wd) in enumerate(want):
        order = np.argsort(ti[qi == a])
        np.testing.assert_array_equal(ti[qi == a][order], np.sort(wi))


# --- the spans, the counters and the stats ------------------------------------
@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def series(name):
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in obs.snapshot().get(name, {"series": []})["series"]}


def test_the_range_call_is_one_tree_of_spans_with_its_counters(
        fresh_registry):
    db, q, want_sizes = planted()
    prog = ShardedKNN(db, mesh=mesh(), k=K, train_tile=TILE)
    # a call with nothing to complete: every series is there, at 0
    prog.range_search_certified(q[:2], radius_sq=RADIUS_SQ)
    assert series(mn.RANGE_QUERIES) == {
        (("outcome", "complete"),): 2.0, (("outcome", "truncated"),): 0.0,
        (("outcome", "host_scan"),): 0.0}
    assert series(mn.RANGE_RESULTS) == {(): 8.0}
    obs.reset_event_log(None)
    _, _, _, stats = prog.range_search_certified(q, radius_sq=RADIUS_SQ)
    assert series(mn.RANGE_QUERIES) == {
        (("outcome", "complete"),): 4.0, (("outcome", "truncated"),): 3.0,
        (("outcome", "host_scan"),): 1.0}
    assert series(mn.RANGE_RESULTS) == {(): 8.0 + sum(want_sizes)}
    spans = [e for e in obs.get_event_log().recent() if e.get("span")]
    (call,) = [e for e in spans if e["span"] == "certified.range_call"]
    assert (call["complete"], call["truncated"], call["host_scan"],
            call["results"], call["radius_sq"]) == (
        2, 3, 1, sum(want_sizes), float(RADIUS_SQ))
    # ONE trace id: the first pass's tree hangs under the range call
    assert {e["trace_id"] for e in spans} == {call["trace_id"]}
    by = {e["span"]: e for e in spans}
    assert by["certified.call"]["parent"] == "certified.range_call"
    assert by["certified.prepare"]["parent"] == "certified.call"
    done = by["certified.range_complete"]
    assert (done["parent"], done["queries"], done["rung"],
            done["sub_batches"], done["host_scan_queries"]) == (
        "certified.range_call", 4, 512, 1, 1)
    assert done["rows_returned"] == sum(want_sizes[2:])
    pack = by["certified.range_pack"]
    assert pack["parent"] == "certified.range_call"
    # the pack scores the complete queries' prefixes; the call has the sum
    assert pack["rows_returned"] == sum(want_sizes[:2])
    hist = series(mn.SPAN_SECONDS)
    for name in ("certified.range_call", "certified.range_complete",
                 "certified.range_pack"):
        assert hist[(("span", name),)]["count"] == 2, name
    # a plain search_certified call has no such parent
    obs.reset_event_log(None)
    prog.search_certified(q, selector="pallas")
    (plain,) = [e for e in obs.get_event_log().recent()
                if e.get("span") == "certified.call"]
    assert "parent" not in plain and plain["trace_id"] != call["trace_id"]
    assert stats["range"]["truncated"] == 3


def test_the_completion_is_one_program_sent_only_for_truncated_queries(
        fresh_registry):
    """One collect width, so one program whatever the counts; a call
    with nothing truncated sends none."""
    from knn_tpu.parallel import sharded as sh

    db, q, _ = planted(n=1200, seed=35)
    prog = ShardedKNN(db, mesh=mesh(), k=K, train_tile=TILE)
    before = sh._range_program.cache_info()
    _, _, _, stats = prog.range_search_certified(q[:2], radius_sq=RADIUS_SQ)
    assert (stats["range"]["truncated"], stats["range"]["width"],
            stats["range"]["sub_batches"]) == (0, 0, 0)
    assert sh._range_program.cache_info().misses == before.misses
    for part in (q[2:4], q):  # a few marked rows, then hundreds
        prog.range_search_certified(part, radius_sq=RADIUS_SQ)
    assert sh._range_program.cache_info().misses == before.misses + 1


# --- the reference, its broken forms and the comparison ----------------------
def test_the_oracle_is_a_float64_argsort():
    db, q, _ = planted(n=1500)
    assert_equals(reference_range.oracle_range(db, q, RADIUS_SQ),
                  brute(db, q, RADIUS_SQ))
    rng = np.random.default_rng(5)
    fdb = rng.random((70_000, 6), dtype=np.float32)  # more than one block
    fq = rng.random((5, 6), dtype=np.float32)
    assert_equals(reference_range.oracle_range(fdb, fq, 0.05),
                  brute(fdb, fq, 0.05))


@pytest.mark.parametrize("broken,why", [
    ("topk_only", "the long lists are cut at the first pass's k"),
    ("exclusive", "the rows at exactly the radius are left out"),
    ("int4", "4-bit rows move every distance"),
])
def test_the_broken_references_fail_the_comparison(broken, why):
    db, q, _ = planted()
    want = reference_range.oracle_range(db, q, RADIUS_SQ)
    sound = reference_range.compare(want, want)
    assert sound["mismatched_rows"] == 0 and sound["dist_rel_err_max"] == 0
    assert (sound["rows"], sound["results"], sound["most_results"],
            sound["empty_rows"]) == (6, 673, 601, 1)
    got = reference_range.oracle_range(db, q, RADIUS_SQ, broken=broken, cap=K)
    cmp = reference_range.compare(got, want)
    assert cmp["mismatched_rows"] > CONFIG["limits"]["mismatched_rows"], why
    if broken == "topk_only":
        assert cmp["mismatched_rows"] == 4 and np.diff(got[0]).max() == K
    if broken == "exclusive":
        assert cmp["mismatched_rows"] == 5


def test_compare_reads_lengths_indices_and_distances():
    want = (np.array([0, 2, 2, 3]), np.array([5, 7, 9]),
            np.array([1.0, 2.0, 4.0]))
    same = reference_range.compare(want, want)
    assert same["mismatched_rows"] == 0 and same["empty_rows"] == 1
    swapped = (want[0], np.array([7, 5, 9]), want[2])
    assert reference_range.compare(swapped, want)["mismatched_rows"] == 1
    shorter = (np.array([0, 1, 1, 2]), np.array([5, 9]), np.array([1.0, 4.0]))
    assert reference_range.compare(shorter, want)["mismatched_rows"] == 1
    off = (want[0], want[1], np.array([1.0, 2.0, 4.0 * (1 + 2**-10)]))
    cmp = reference_range.compare(off, want)
    assert cmp["mismatched_rows"] == 0
    assert cmp["dist_rel_err_max"] == pytest.approx(2**-10)
    nan = (want[0], want[1], np.array([1.0, np.nan, 4.0]))
    assert reference_range.compare(nan, want)["dist_rel_err_max"] == np.inf
    # take and concat are each other's inverse over a batch's answer
    picked = reference_range.concat(
        reference_range.take(want, [r]) for r in (2, 0, 1))
    assert picked[0].tolist() == [0, 1, 3, 3]
    assert picked[1].tolist() == [9, 5, 7]
    with pytest.raises(ValueError):
        reference_range.compare((want[0][:-1], want[1], want[2]), want)


# --- the generator ------------------------------------------------------------
@pytest.mark.parametrize("threads", [1, 3])
def test_datagen_dup_gives_the_same_values_whatever_the_thread_count(
        monkeypatch, threads):
    n = 2 * datagen.CHUNK_ROWS + 77
    want = datagen_dup.draw(CONFIG["rows"], n, 16, 7, datagen.STREAM_ROWS)
    monkeypatch.setattr(os, "cpu_count", lambda: threads + 1)
    got = datagen_dup.draw(CONFIG["rows"], n, 16, 7, datagen.STREAM_ROWS)
    np.testing.assert_array_equal(got, want)
    assert not np.array_equal(
        datagen_dup.draw(CONFIG["rows"], n, 16, 8, datagen.STREAM_ROWS), want)


def test_datagen_dup_draws_what_the_configuration_says():
    n = 40_000
    spec = CONFIG["rows"]
    db, q, kinds = near_dups(n, batch=4096, n_batches=2,
                             shares=TRAFFIC["shares"])
    assert db.dtype == np.float32 and db.min() >= 0 and db.max() <= 255
    assert np.array_equal(db, np.rint(db)) and np.array_equal(q, np.rint(q))
    sizes, family_of = datagen_dup.layout(spec, n, 2**31 + 34)
    # 15 % of the rows in families of 2 to 2,000, largest first; the
    # original is a member; members at positions that say nothing
    assert sizes.sum() == int(0.15 * n) == (family_of != -1).sum()
    assert sizes.min() >= 2 and sizes.max() <= 2000
    assert (np.diff(sizes) <= 0).all()
    assert (family_of <= -2).sum() == sizes.size
    members = np.flatnonzero(family_of == 0)
    assert members.size == sizes[0] - 1
    assert abs(members.mean() / n - 0.5) < 0.2
    # a copy lies 256 x 4^2 ... 256 x 16^2 from its original, plus rounding
    orig = db[np.flatnonzero(family_of == -2)[0]]
    d = ((db[members] - orig) ** 2).sum(-1)
    assert 3000 < d.min() and d.max() < 256 * 20 ** 2
    # every batch holds exactly the traffic's shares, at shuffled places
    for b in range(2):
        kb = kinds[b * 4096:(b + 1) * 4096]
        assert np.bincount(kb).tolist() == [2867, 1188, 41]
        assert 0 < np.flatnonzero(kb == 2).mean() / 4096 < 1
    assert not np.array_equal(kinds[:4096], kinds[4096:])
    with pytest.raises(ValueError, match="add up"):
        datagen_dup.draw_queries(spec, n, 256, 1, 64, 1, TRAFFIC["shares"],
                                 64, 128)


def test_the_boundary_queries_lie_at_the_radius_and_a_unit_past_it():
    """What lets a run at size hold the INCLUSIVE boundary: in every
    batch a query with a placed row at exactly the radius and one with
    a placed row a unit past it, taken from the short queries."""
    steps = datagen_dup.boundary_steps(RADIUS_SQ)
    assert steps.tolist() == [128] * 5 + [119, 12, 3, 1, 1, 1]
    assert (steps ** 2).sum() == RADIUS_SQ and steps.max() <= 128
    db, q, kinds = near_dups(3000, n_batches=2, boundary_pairs=1)
    plain = near_dups(3000, n_batches=2)
    assert np.bincount(kinds[:64]).tolist() == [44, 14, 4, 1, 1]
    assert np.bincount(kinds[64:]).tolist() == [44, 14, 4, 1, 1]
    # nothing else of the draw moves
    same = kinds < datagen_dup.AT_RADIUS
    np.testing.assert_array_equal(q[same], plain[1][same])
    assert (plain[2][~same] == 1).all()
    assert q.min() >= 0 and q.max() <= 255 and np.array_equal(q, np.rint(q))
    d = ((q[~same, None].astype(np.float64) - db[None]) ** 2).sum(-1)
    label = kinds[~same]
    assert ((d == RADIUS_SQ).sum(1) == (label == datagen_dup.AT_RADIUS)).all()
    assert ((d == RADIUS_SQ + 1).sum(1)
            == (label == datagen_dup.PAST_RADIUS)).all()
    # so an exclusive boundary loses a row of every AT query's list and
    # of no other's
    want = reference_range.oracle_range(db, q[~same], RADIUS_SQ)
    broken = reference_range.oracle_range(db, q[~same], RADIUS_SQ,
                                          broken="exclusive", cap=K)
    assert (np.diff(want[0]) - np.diff(broken[0])
            == (label == datagen_dup.AT_RADIUS)).all()
    with pytest.raises(ValueError, match="boundary pairs"):
        datagen_dup.draw_queries(CONFIG["rows"], 3000, 8, 1, 64, 1,
                                 TINY_SHARES, 64, 128, RADIUS_SQ, 1)


def test_a_tiny_corpus_still_has_its_long_and_short_queries():
    """``benchmark/tests`` runs the cell on 3,000 rows with the traffic
    whole: families shrink with the corpus, the long queries come from
    the largest there is and still pass the first pass's k = 100."""
    db, q, kinds = near_dups(3000, batch=4096, shares=TRAFFIC["shares"])
    sizes, _ = datagen_dup.layout(CONFIG["rows"], 3000, 2**31 + 34)
    assert sizes.max() <= 3000 // 8 and sizes.sum() == 450
    got = reference_range.oracle_range(db, q[kinds == 2], RADIUS_SQ)
    assert np.diff(got[0]).min() > 100


# --- the cell through the benchmark's harness --------------------------------
BENCH = tinyroot.load_bench()
TINY_TRAFFIC = dict(tinyroot.TINY_TRAFFIC["sweep"], shares=TINY_SHARES,
                    check_heavy_rows=2)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tinyroot``'s copy, with this cell's traffic file cut as it cuts
    ``sweep``'s (it shrinks by file name and does not know this one)."""
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_range")))
    path = os.path.join(root, "benchmark", "traffic", "sweep_range.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(TINY_TRAFFIC)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    # the CPU backend reports no memory; the validator refuses 0
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run_cell(root, traced: bool, seed=2**31 + 34) -> dict:
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 0.5, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


STAGES = {"dispatch_ms", "device_wait_ms", "d2h_ms", "unpack_ms",
          "rank_correct_ms", "repair_ms"}
RANGE = {"range_truncated_pct", "range_host_scan_pct", "range_complete_ms",
         "range_pack_ms", "range_complete_device_ms",
         "range_complete_roofline"}


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_through_the_harness(root, cpu_memory_reading, traced):
    cell = harness.load_cell(root, CELL)
    assert cell.traffic["kind"] == "sweep_range" and cell.chips == 1
    out = run_cell(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        # these are there; a later PR may list more for the cell
        assert want >= STAGES | RANGE | {
            "kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
            "rank_corrected_pct", "idle_pct.sweep"}
        for name in STAGES | RANGE - {"range_host_scan_pct"}:
            assert out["metrics"][name]["value"] > 0, name
        assert out["metrics"]["range_host_scan_pct"]["value"] == 0
        # 4 long queries of 64 a batch
        assert out["metrics"]["range_truncated_pct"]["value"] == 6.25
    else:
        assert want == {"sweep_qps", "setup_s"}


def _break_no_completion(monkeypatch):
    """A range answered by the first pass alone: the truncated queries
    come back with their first k results."""
    real = ShardedKNN._range_complete

    def first_k(self, *args):
        cq, ci, cd, done = real(self, *args)
        rank = np.arange(cq.size) - np.searchsorted(cq, cq)
        keep = rank < self.k
        return cq[keep], ci[keep], cd[keep], done

    monkeypatch.setattr(ShardedKNN, "_range_complete", first_k)


def _break_boundary(monkeypatch):
    """An exclusive boundary: a distance of exactly the radius reads a
    float64 ulp over it wherever membership is decided."""
    real = refine.exact_pair_scores

    def over(db_np, queries_np, rows, cand, metric="l2"):
        d = real(db_np, queries_np, rows, cand, metric)
        return np.where(d == RADIUS_SQ, np.nextafter(d, np.inf), d)

    monkeypatch.setattr(refine, "exact_pair_scores", over)


def _break_pack(monkeypatch):
    """An index dropped in the pack: every non-empty list loses its last
    row."""
    real = ShardedKNN.range_search_certified

    def dropped(self, queries, **kw):
        lims, idx, dist, stats = real(self, queries, **kw)
        keep = np.ones(idx.size, bool)
        keep[lims[1:][np.diff(lims) > 0] - 1] = False
        out = np.zeros_like(lims)
        np.cumsum(np.diff(lims) - (np.diff(lims) > 0), out=out[1:])
        return out, idx[keep], dist[keep], stats

    monkeypatch.setattr(ShardedKNN, "range_search_certified", dropped)


def _planted_draw(monkeypatch):
    """The planted corpus in the generator's place (rows at exactly the
    radius), its queries tiled over the pool; the long ones are the last
    two kinds of list."""
    db, q, _ = planted()

    def draw(spec, n, dim, seed, stream):
        return db

    def draw_queries(spec, n, dim, seed, batch_rows, n_batches, *a):
        reps = batch_rows * n_batches
        kinds = (np.arange(reps) % 6 >= 4).astype(np.int8) * 2
        return np.resize(q, (reps, dim)).copy(), kinds

    monkeypatch.setattr(datagen_dup, "draw", draw)
    monkeypatch.setattr(datagen_dup, "draw_queries", draw_queries)


def test_the_planted_case_through_the_harness_is_correct(
        root, cpu_memory_reading, monkeypatch):
    _planted_draw(monkeypatch)
    assert run_cell(root, False)["correct"] is True


@pytest.mark.parametrize("breaker", [_break_no_completion, _break_boundary,
                                     _break_pack])
def test_a_broken_timed_path_comes_out_not_correct(
        root, cpu_memory_reading, monkeypatch, breaker):
    _planted_draw(monkeypatch)
    breaker(monkeypatch)
    assert run_cell(root, False)["correct"] is False


@pytest.mark.parametrize("breaker", [_break_no_completion, _break_boundary])
def test_a_broken_path_is_not_correct_on_the_generators_own_rows(
        root, cpu_memory_reading, monkeypatch, breaker):
    """The sample always holds long queries and one batch's boundary
    queries, so the generator's own rows show both faults."""
    breaker(monkeypatch)
    assert run_cell(root, False)["correct"] is False


# --- the cell's data files ---------------------------------------------------
def test_the_configuration_is_the_source_cut_in_rows_only():
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "ssnpp2m5"]
    assert entry["file"] == "benchmark/configs/ssnpp2m5.json"
    assert entry["reduced"] == ["rows_n"] == list(
        CONFIG["reduced_from_source"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["metric"],
            CONFIG["radius_sq"], CONFIG["k"], CONFIG["train_tile"],
            CONFIG["reference"]) == (
        2_500_000, 256, "l2", RADIUS_SQ, 100, 131072, "range")
    assert RADIUS_SQ == sum(x * x for x in AT_RADIUS)
    assert CONFIG["rows"]["dist"] == datagen_dup.DIST
    assert CONFIG["limits"] == {"mismatched_rows": 0,
                                "dist_rel_err_max": 2.0 ** -18}
    assert CONFIG["require"] == {"tuning_source": "default",
                                 "interpret": False}
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ssnpp2m5", "sweep_range", 1)
    assert TRAFFIC["shares"] == {"unrelated": 2867, "small_family": 1188,
                                 "heavy_family": 41}
    assert sum(TRAFFIC["shares"].values()) == TRAFFIC["batch_rows"] == 4096
    assert (TRAFFIC["pool_batches"], TRAFFIC["check_rows"],
            TRAFFIC["check_heavy_rows"], TRAFFIC["selector"],
            TRAFFIC["trace_seconds"], TRAFFIC["small_max"],
            TRAFFIC["heavy_min"]) == (8, 64, 8, "pallas", 4, 64, 128)
    # the cell joins the lists the issue names and brings six of its own
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= STAGES | RANGE | {
        "kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
        "rank_corrected_pct", "idle_pct.sweep"}
    for m in bench["per_layer"]:
        if m["name"] in RANGE:
            assert m["workloads"] == [CELL] and m["moves"] == "sweep_qps"
            assert m["layer"] == (
                "range completion (parallel/sharded.py, ops/radius.py)")


def test_the_completions_work_counts_the_long_queries_once():
    work = harness._module("range_complete", "work")
    ops, nbytes = work.ops_bytes(CONFIG, TRAFFIC)
    assert ops == 2.0 * 41 * 2_500_000 * 256
    assert nbytes == 4.0 * 2_500_000 * 256 + 4.0 * 41 * 256
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the rows read once bind it: 3.1 ms
    assert work.least_seconds(CONFIG, TRAFFIC, peaks) == pytest.approx(
        nbytes / 819e9)


# --- two review findings on the bounded radius path ---------------------------
def test_a_cityblock_estimator_predicts_as_an_l1_one():
    """``ops/radius.py`` accepts 'cityblock' in validation and
    ``_dispatch_metric`` names it 'l1' before any dispatch: an estimator
    built with it predicts, and as its 'l1' twin does."""
    from knn_tpu.models.radius import RadiusNeighborsClassifier

    rng = np.random.default_rng(3)
    x = rng.random((200, 6), dtype=np.float32)
    y = (np.arange(200) % 3).astype(np.int32)
    got = [RadiusNeighborsClassifier(
        radius=0.9, metric=m, max_neighbors=128).fit(x, y).predict(x[:20])
        for m in ("cityblock", "l1")]
    np.testing.assert_array_equal(got[0], got[1])


def test_sharded_radius_search_takes_the_l1_fallback_its_docstring_promises():
    from knn_tpu.ops.radius import radius_search

    rng = np.random.default_rng(4)
    db = rng.random((300, 5), dtype=np.float32)
    q = rng.random((9, 5), dtype=np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=3, metric="l1")
    got = prog.radius_search(q, 0.8, max_neighbors=40)
    want = radius_search(q, db, 0.8, max_neighbors=40, metric="l1")
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "range_search_certified" in ShardedKNN.radius_search.__doc__
