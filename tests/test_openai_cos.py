"""Exact cosine search (``openai500k``): ``ShardedKNN(..., metric=
"cosine")`` through ``search_certified``, held to the contract l2 and
inner product have: the indices equal float64 brute force in (1 - q.t /
(|q| |t|), index) order over the float32 rows and queries AS GIVEN.  On
the CPU, the kernel interpreted, at sizes a test can hold:

- the system against the plain reference (``benchmark/reference_cos.py``)
  on seeded ``datagen_mix`` rows of spread norms with a zero row, a zero
  query, exact duplicates and power-of-two scaled copies, under every
  selector, on one device and db-sharded over four;
- a built corpus the float32-unit-row problem gets wrong (what the
  parent answered for): pairs of rows one float32 ulp apart in a few
  coordinates, whose cosines differ by about 1e-8 and whose unit images
  tie or swap;
- the placement (``_unit_rows``), the pair slack and its one method;
- the reference itself, its controls and ``compare``;
- the span, the event and both counters;
- the cell ``openai500k.sweep_cos`` through the whole benchmark harness,
  traced and not, the three held per-layer entries merged in, and broken
  timed paths coming out ``correct: false``.
"""

import hashlib
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops import certified, refine
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.parallel import sharded as sh

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (HERE, BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import control_cos  # noqa: E402  (benchmark/)
import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import program_digest  # noqa: E402  (tests/)
import reference  # noqa: E402
import reference_cos  # noqa: E402
import tiny_cos  # noqa: E402  (benchmark/tests/)
import tinyroot  # noqa: E402
from tiny_cos import CELL, HELD  # noqa: E402

K = 10
TILE = 512
SELECTORS = ["pallas", "approx", "exact"]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "openai500k.json")


def mesh(db_shards: int = 1):
    return make_mesh(1, db_shards, devices=jax.devices()[:db_shards])


def brute(db, q, k=K):
    return tiny_cos.brute(db, q, k)


def unit_problem(db, q, k=K):
    """What the parent's ``search_certified`` was exact for: the float64
    squared-L2 order of the FLOAT32 UNIT rows and queries."""
    def unit(x):
        n = np.linalg.norm(x.astype(np.float64), axis=-1, keepdims=True)
        return (x / np.maximum(n, 1e-300)).astype(np.float32)

    diff = (unit(q).astype(np.float64)[:, None]
            - unit(db).astype(np.float64)[None])
    c = np.einsum("qnd,qnd->qn", diff, diff)
    idx = np.broadcast_to(np.arange(db.shape[0]), c.shape)
    return np.lexsort((idx, c), axis=-1)[:, :k]


def mix(n, n_q, dim=32, seed=2**31 + 43, clusters=64):
    rows = dict(CONFIG["rows"], clusters=clusters, scale_sigma=0.4)
    db = datagen_mix.draw(rows, n, dim, seed, datagen.STREAM_ROWS)
    q = datagen_mix.draw(rows, n_q, dim, seed, datagen.STREAM_QUERIES)
    return db, q


def edged(n, n_q, dim=32):
    """``mix`` with the contract's edges laid in: a zero row, a zero
    query, a query that IS a row, exact duplicates of a row (the lower
    index wins), copies of it scaled by powers of two (the same cosine
    to the bit: ties again) and rows scaled by other factors (the same
    cosine but for float32 rounding: near-ties for the host)."""
    db, q = mix(n, n_q, dim)
    db[7] = 0.0
    db[900:903] = db[40]            # duplicates
    db[1200] = 4.0 * db[40]         # power-of-two copies
    db[1201] = 0.125 * db[40]
    db[1300] = 3.0 * db[41]         # near-ties: rounded in float32
    db[1301] = db[41] / 7.0
    q[3] = 0.0
    q[4] = db[40]
    q[5] = 0.5 * db[41]
    q[6:12] = db[40] + 0.05 * q[6:12]
    return db, q


# --- the system against the plain reference ---------------------------------
@pytest.mark.parametrize("selector", SELECTORS)
@pytest.mark.parametrize("shards,n", [(1, 3000), (4, 4099)])
def test_certified_cosine_equals_the_cosine_oracle(shards, n, selector):
    db, q = edged(n, 48)
    want_i, want_c = reference_cos.oracle_topk(db, q, K)
    np.testing.assert_array_equal(want_i, brute(db, q)[0])
    # the edges are in the answers: the zero query takes the first K
    # rows at distance 1, the copies of row 40 tie and come by index
    np.testing.assert_array_equal(want_i[3], np.arange(K))
    assert (want_c[3] == 1.0).all()
    np.testing.assert_array_equal(want_i[4, :6],
                                  [40, 900, 901, 902, 1200, 1201])
    assert (want_c[4, :6] == want_c[4, 0]).all() and want_c[4, 0] < 1e-15
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="cosine")
    kw = {"tile_n": TILE} if selector == "pallas" else {}
    d, i, stats = prog.search_certified(q, selector=selector, **kw)
    np.testing.assert_array_equal(i, want_i)
    # the stated bound (float64 where the host scored: every entry of a
    # counted selector's answer)
    bound = 2.0 ** -18 * (want_c + 0.125) if selector == "pallas" else 1e-14
    assert (np.abs(np.sort(d, axis=1) - want_c) <= bound).all()
    assert stats["certified"] + stats["fallback_queries"] == q.shape[0]
    assert stats["metric"] == "cosine"
    assert stats["pair_slack"] == sh.COS_UNIT_SLACK == 2.0 ** -20
    assert i.max() < n  # no padding row in any answer
    # what the caller passed is untouched, and norms spread
    norms = np.linalg.norm(db.astype(np.float64), axis=1)
    assert norms.max() > 4 * norms[norms > 0].min()
    # the host keeps the rows as given and one norm a row, not unit rows
    assert prog._host_train() is db or np.array_equal(prog._host_train(), db)
    np.testing.assert_array_equal(prog._cos_norms, refine.row_norms_f64(db))
    np.testing.assert_array_equal(prog._cos_zero_rows, [7])


def test_indices_alone_and_a_zero_row_in_reach_are_exact_too():
    """``return_distances=False`` (no distance block leaves the device)
    and queries whose candidates hold the zero row, which the device
    sees at half its distance: flagged and repaired by the true metric."""
    rng = np.random.default_rng(43)
    db = rng.normal(size=(400, 16)).astype(np.float32)
    db[11] = 0.0
    q = rng.normal(size=(40, 16)).astype(np.float32)
    want_i, want_c = brute(db, q, 250)
    # cosine 0 reaches the top 250 of 400: about half the rows lie
    # behind a query
    assert (want_i == 11).any(1).all()
    prog = ShardedKNN(db, mesh=mesh(), k=250, metric="cosine")
    for selector in SELECTORS:
        kw = {"tile_n": TILE} if selector == "pallas" else {}
        d, i, stats = prog.search_certified(q, selector=selector, **kw)
        np.testing.assert_array_equal(i, want_i)
        assert stats["fallback_queries"] == 40
        none, i2, _ = prog.search_certified(
            q, selector=selector, return_distances=False, **kw)
        assert none is None
        np.testing.assert_array_equal(i2, want_i)


# --- the built case: rows one float32 ulp apart -------------------------------
DIM = 64
PAIRS = 24


def built_corpus(seed=43):
    """Rows and four queries.  ``PAIRS`` pairs of rows (A_j at 2j, B_j at
    2j + 1 + a gap of other rows): B_j is A_j with three coordinates
    moved by one float32 ulp, so their cosines to a query differ by
    about 1e-8, far under the 2^-24 a unit row's entry is rounded by;
    every pair has its own length (2^-3 to 2^3, not powers of two alone)
    and its own angle to the queries, 0.02 and more in cosine from the
    next pair's, so the top ``2 * PAIRS`` of a query is these rows, pair
    by pair, and only the order INSIDE a pair is hard.  The other rows
    lie at cosine 0.5 and under."""
    rng = np.random.default_rng(seed)
    axis = np.zeros(DIM)
    axis[0] = 1.0
    rows, where = [], []
    for j in range(PAIRS):
        side = rng.normal(size=DIM)
        side[0] = 0.0
        side /= np.linalg.norm(side)
        cos = 0.99 - 0.02 * j
        a = (cos * axis + np.sqrt(1 - cos * cos) * side) * 2.0 ** (
            rng.uniform(-3, 3))
        a = a.astype(np.float32)
        b = a.copy()
        for col in rng.choice(np.arange(1, DIM), size=3, replace=False):
            b[col] = np.nextafter(b[col], np.float32(
                np.inf if rng.random() < 0.5 else -np.inf))
        where.append(len(rows))
        rows += [a, b]
        for _ in range(3):  # rows between the pairs, far from the axis
            far = rng.normal(size=DIM)
            far[0] = 0.3 * np.abs(far[0])
            rows.append((far * 2.0 ** rng.uniform(-2, 2)).astype(np.float32))
    far = rng.normal(size=(1500, DIM))
    far[:, 0] = 0.3 * np.abs(far[:, 0])
    db = np.concatenate([np.stack(rows), far.astype(np.float32)])
    q = np.stack([axis * s + 0.01 * rng.normal(size=DIM)
                  for s in (1.0, 3.0, 0.37, 11.0)]).astype(np.float32)
    return db, q, np.asarray(where)


def test_the_built_case_is_what_it_says():
    db, q, where = built_corpus()
    k = 2 * PAIRS
    want_i, want_c = brute(db, q, k)
    pair_rows = np.concatenate([where, where + 1])
    for row in want_i:
        assert set(row) == set(pair_rows)
    # inside a pair the cosines differ by 1e-12 ... 1e-7; between pairs
    # by a thousandth and more
    gaps = np.diff(want_c, axis=1)
    inside, between = gaps[:, 0::2], gaps[:, 1::2]
    assert 0 < inside.min() and inside.max() < 2e-7 < 1e-3 < between.min()
    # the unit rows' problem orders some pairs the other way
    assert (unit_problem(db, q, k) != want_i).any()
    # and float32 rounding of the unit rows is all that does it: the
    # gaps inside the pairs lie under the pair slack, in D' = 2c units
    assert 2 * inside.max() < sh.COS_UNIT_SLACK


@pytest.mark.parametrize("selector", SELECTORS)
@pytest.mark.parametrize("shards", [1, 4])
def test_rows_an_ulp_apart_come_back_in_the_order_of_the_rows_as_given(
        shards, selector):
    """THE test the parent fails: it answered for the unit rows."""
    db, q, _ = built_corpus()
    k = 2 * PAIRS
    want_i, want_c = brute(db, q, k)
    np.testing.assert_array_equal(
        reference_cos.oracle_topk(db, q, k)[0], want_i)
    prog = ShardedKNN(db, mesh=mesh(shards), k=k, metric="cosine")
    kw = {"tile_n": TILE} if selector == "pallas" else {}
    d, i, stats = prog.search_certified(q, selector=selector, **kw)
    np.testing.assert_array_equal(i, want_i)
    assert (np.abs(d - want_c) <= 2.0 ** -18 * (want_c + 0.125)).all()
    if selector == "pallas":
        # every pair is a tie run for the host
        assert stats["rank_corrected_queries"] == q.shape[0]


def test_ranking_by_the_unit_rows_gets_the_built_case_wrong(monkeypatch):
    """The same call with the host ranking as the parent did (squared L2
    of the float32 unit rows): the answer is no longer the oracle's."""
    db, q, _ = built_corpus()
    k = 2 * PAIRS
    want_i, _ = brute(db, q, k)
    prog = ShardedKNN(db, mesh=mesh(), k=k, metric="cosine")
    _break_ranking(monkeypatch)
    _, i, _ = prog.search_certified(q, selector="pallas", tile_n=TILE)
    assert (i != want_i).any()
    np.testing.assert_array_equal(i, unit_problem(db, q, k))


def test_the_merge_drop_carries_the_slack_over_four_shards():
    """(1, 4) mesh, candidates cut to the merge's m + 1: an ulp-pair
    split by the k-th place across shards must not certify on the unit
    rows' word."""
    db, q, where = built_corpus()
    # pairs interleaved over the four shards: A_j and B_j on different
    # ones (the rows are laid out shard by shard)
    n = db.shape[0] - db.shape[0] % 4
    db = db[:n]
    order = np.arange(n).reshape(-1, 4).T.ravel()
    db = db[np.argsort(order)]
    for k in (2 * PAIRS - 1, 2 * PAIRS - 3, 9):  # the k-th place splits a pair
        want_i, _ = brute(db, q, k)
        prog = ShardedKNN(db, mesh=mesh(4), k=k, metric="cosine")
        _, i, stats = prog.search_certified(q, selector="pallas",
                                            tile_n=TILE, margin=2)
        np.testing.assert_array_equal(i, want_i)
        assert stats["db_shards"] == 4


# --- the placement, the slack, its one method ---------------------------------
def test_placement_makes_unit_rows_in_blocks_and_keeps_the_rows_as_given():
    db, _ = mix(20_000, 4, dim=200)
    db[5] = 0.0
    db[6] = [1.0] + [0.0] * 199  # a unit row already: bf16-exact
    unit, norms, placed_max, lo_zero = sh._unit_rows(db)
    assert unit.dtype == np.float32 and unit.shape == db.shape
    want_n = np.sqrt((db.astype(np.float64) ** 2).sum(-1))
    np.testing.assert_allclose(norms, want_n, rtol=1e-15)
    # blocks and threads change nothing: the reference walk's own numbers
    np.testing.assert_array_equal(norms, refine.row_norms_f64(db))
    np.testing.assert_array_equal(
        unit[8], (db[8].astype(np.float64) / norms[8]).astype(np.float32))
    assert not unit[5].any() and norms[5] == 0 and not lo_zero
    np.testing.assert_array_equal(unit[6], db[6])
    # every unit row's rounding is inside what the slack was derived from
    err = np.linalg.norm(
        unit.astype(np.float64) - db.astype(np.float64)
        / np.where(norms > 0, norms, 1)[:, None], axis=1)
    assert err.max() <= 2.0 ** -24 * (1 + 2.0 ** -8)
    assert placed_max == (unit.astype(np.float64) ** 2).sum(-1).max()
    assert abs(placed_max - 1) < 2.0 ** -22
    assert sh._unit_rows(db[6:7])[3] is True
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
    assert prog._db_norm_max() == placed_max and not prog._rows_lo_zero
    assert prog._host_train() is db  # no second copy on the host
    placed = np.asarray(prog._tp)[:20_000]  # 200 columns in 256 lanes
    np.testing.assert_array_equal(placed[:, :200], unit)
    assert placed.shape[1] == 256 and not placed[:, 200:].any()
    np.testing.assert_array_equal(prog._placed_host(), unit)


# --- the batch's map, a sub-batch at a time ---------------------------------
@pytest.mark.parametrize("n,bs,dim", [(4099, 1031, 300), (2500, 700, 1536)])
def test_queries_mapped_a_sub_batch_at_a_time_are_the_whole_batchs(n, bs,
                                                                   dim):
    """Rows and norms filled as the launches are cut equal
    ``_unit_rows`` of the whole batch to the bit: a row count that is
    no multiple of the threads, a zero query in the second sub-batch, a
    ragged last one."""
    _, q = mix(16, n, dim=dim)
    q[bs + 3] = 0.0
    want_u, want_n, _, _ = sh._unit_rows(q)
    map_s = sh._metric_map_seconds()
    unit = sh._UnitQueries(q, map_s)
    assert unit.rows.shape == q.shape and unit.rows.dtype == np.float32
    batches = sh._QueryBatches(unit.rows, bs, unit)
    assert len(batches) == -(-n // bs)
    for at, (lo, chunk, pad) in enumerate(batches):
        # mapped as far as this launch and no farther; the launch's rows
        # are the map's own (a view) but for the padded tail
        assert unit._filled == min(lo + bs, n)
        assert map_s["under_batches"] == at
        take = bs - pad
        np.testing.assert_array_equal(chunk[:take], want_u[lo : lo + take])
        assert chunk.shape == (bs, dim) and not chunk[take:].any()
        assert pad or np.shares_memory(chunk, unit.rows)
    assert (lo, pad) == (n - n % bs, bs - n % bs)
    np.testing.assert_array_equal(unit.rows, want_u)
    np.testing.assert_array_equal(unit.norms, want_n)
    assert not unit.rows[bs + 3].any() and unit.norms[bs + 3] == 0
    # a later pass is handed the same launches and maps nothing
    again = list(batches)
    assert all(a is b for a, b in zip(again, batches))
    assert map_s["under_batches"] == len(batches) - 1
    assert map_s["before_s"] > 0 and map_s["under_s"] > 0


@pytest.mark.parametrize("n,dim,parts,first", [
    (1024, 1536, 4, 256),   # openai500k's sub-batch: one block of 682 and
    (1024, 768, 4, 256),    # one of 342 by _block_rows; imagenet's: one
    (4096, 1536, 8, 512),   # an uncut batch: whole rounds of the threads
    (1001, 1536, 4, 251),   # no multiple of the threads
    (48, 48, 1, 48),        # too few values to share
])
def test_a_sub_batchs_rows_are_shared_evenly_among_the_threads(
        monkeypatch, n, dim, parts, first):
    monkeypatch.setattr(refine, "_POOL_THREADS", 4)
    cut = sh._even_parts(100, 100 + n, dim)
    assert len(cut) == parts and cut[0] == (100, 100 + first)
    assert [a for a, _ in cut[1:]] == [b for _, b in cut[:-1]]
    assert cut[-1][1] == 100 + n
    assert max(b - a for a, b in cut) <= refine._block_rows(dim)
    assert max(b - a for a, b in cut) - min(b - a for a, b in cut) < parts


def test_an_l2_or_dot_calls_launches_are_cut_as_they_were():
    q = np.arange(10 * 3, dtype=np.float32).reshape(10, 3)
    (lo0, c0, p0), (lo1, c1, p1), (lo2, c2, p2) = sh._QueryBatches(q, 4)
    assert (lo0, lo1, lo2, p0, p1, p2) == (0, 4, 8, 0, 0, 2)
    assert np.shares_memory(c0, q) and np.shares_memory(c1, q)
    np.testing.assert_array_equal(c2, np.pad(q[8:], ((0, 2), (0, 0))))
    assert c0.nbytes == c2.nbytes == 4 * 3 * 4
    assert len(sh._QueryBatches(q[:0], 4)) == 0


FLAGS = ["fallback_queries", "certified", "fallback_genuine_misses",
         "fallback_false_alarms", "host_exact_queries"]


@pytest.mark.parametrize("shards,n,selector", [
    (1, 3000, "pallas"), (4, 4099, "pallas"), (1, 3000, "approx"),
    (1, 3000, "exact")])
def test_a_call_cut_in_four_answers_as_the_uncut_call_to_the_bit(
        shards, n, selector):
    db, q = edged(n, 48)
    want_i, want_c = reference_cos.oracle_topk(db, q, K)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="cosine")
    kw = {"tile_n": TILE} if selector == "pallas" else {}
    d1, i1, s1 = prog.search_certified(q, selector=selector, **kw)
    d4, i4, s4 = prog.search_certified(q, selector=selector, batch_size=12,
                                       **kw)
    assert (s1["batches"], s4["batches"]) == (1, 4)
    np.testing.assert_array_equal(i1, want_i)
    np.testing.assert_array_equal(i4, i1)
    np.testing.assert_array_equal(d4, d1)
    flags = FLAGS + (["rank_corrected_queries", "slack_fallback_queries"]
                     if selector == "pallas" else [])
    assert {f: s4.get(f) for f in flags} == {f: s1.get(f) for f in flags}


def map_spans():
    events = obs.get_event_log().recent()
    (whole,) = [e for e in events if e.get("span") == "certified.metric_map"]
    sides = {e["span"].rsplit(".", 1)[1]: e for e in events
             if e.get("span", "").startswith("certified.metric_map.")}
    return whole, sides


@pytest.mark.parametrize("cut", [4, 1])
def test_only_the_first_sub_batchs_map_precedes_the_first_launch(
        fresh_registry, call_order, cut):
    db, q = edged(3000, 48)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
    prog.search_certified(q, selector="pallas", tile_n=TILE)  # the walk
    obs.reset_event_log(None)
    del call_order[:]
    bs = 48 // cut
    _, _, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        batch_size=bs)
    assert stats["batches"] == cut
    # a sub-batch's map, then its launch: the second map begins with the
    # first launch queued
    steps = [s for s in call_order
             if s[0] == "map" or s == ("launched", "certified")]
    assert steps[: 2 * cut] == [
        step for lo in range(0, 48, bs)
        for step in (("map", lo, lo + bs), ("launched", "certified"))]
    whole, sides = map_spans()
    assert set(sides) == {"before", "under", "after"}
    assert {e["parent"] for e in sides.values()} == {"certified.metric_map"}
    assert whole["under_batches"] == cut - 1
    assert whole["before_s"] > 0 and whole["after_s"] == 0
    assert (whole["under_s"] > 0) == (cut > 1)
    assert whole["dur_s"] == pytest.approx(
        whole["before_s"] + whole["under_s"] + whole["after_s"], abs=2e-6)
    for side, e in sides.items():
        assert e["dur_s"] == pytest.approx(whole[f"{side}_s"], abs=1e-6)


def exact_unit(rng, n, dim=32):
    """Rows of 4 or 16 entries of +-2^e, the rest zero: norms 2^(e+1)
    and 2^(e+2), so unit entries +-1/2 and +-1/4, bf16-exact."""
    x = np.zeros((n, dim), np.float32)
    for r in range(n):
        at = rng.choice(dim, size=rng.choice([4, 16]), replace=False)
        x[r, at] = rng.choice([-1.0, 1.0], size=at.size) * 2.0 ** rng.integers(
            -3, 4)
    return x


@pytest.mark.parametrize("voted", [False, True])
def test_rows_with_zero_low_halves_read_every_query_before_the_program(
        fresh_registry, call_order, voted):
    """Where the placement's unit rows are all bf16-exact the kernel's
    products follow the QUERIES' low halves: all of them are mapped
    first, then the program is chosen.  Here only the last sub-batch has
    a query that is not bf16-exact."""
    rng = np.random.default_rng(56)
    db, q = exact_unit(rng, 2000), exact_unit(rng, 48)
    q[40:] = rng.normal(size=(8, 32)).astype(np.float32)
    labels = (np.arange(2000) % 7).astype(np.int32)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine",
                      **({"labels": labels, "num_classes": 7} if voted
                         else {}))
    assert prog._db_norm_max() > 0 and prog._rows_lo_zero
    del call_order[:]  # the placement's own map
    if voted:
        *_, stats = prog.predict_certified(
            q, vote="softmax", temperature=0.07, classes_out=3,
            selector="pallas", tile_n=TILE, batch_size=12)
    else:
        _, i, stats = prog.search_certified(q, selector="pallas",
                                            tile_n=TILE, batch_size=12)
        np.testing.assert_array_equal(
            i, reference_cos.oracle_topk(db, q, K)[0])
    assert stats["batches"] == 4
    assert (stats["terms"], stats["mxu_passes"]) == ("hh+lh", 2)
    steps = [s for s in call_order
             if s[0] == "map" or s == ("launched", "certified")]
    assert steps[:5] == [("map", 0, 48)] + [("launched", "certified")] * 4
    whole, _ = map_spans()
    assert (whole["under_batches"], whole["under_s"]) == (0, 0.0)
    assert whole["before_s"] > 0
    # with bf16-exact queries alone the kernel drops the low product too
    *_, stats = prog.search_certified(q[:40], selector="pallas", tile_n=TILE,
                                      batch_size=10)
    assert stats["terms"] == "hh"


def test_the_pair_slack_is_one_method_for_every_metric():
    db, _ = mix(2000, 4)
    shift = float((db.astype(np.float64) ** 2).sum(-1).max())
    assert ShardedKNN(db, mesh=mesh(), k=K)._pair_slack() == 0.0
    assert ShardedKNN(db, mesh=mesh(), k=K, metric="dot"
                      )._pair_slack() == sh.DOT_AUG_SLACK * shift
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
    assert prog._pair_slack() == sh.COS_UNIT_SLACK
    assert not hasattr(prog, "_dot_slack")
    # the operand the program takes is the slack rounded up to float32
    prog._pallas_setup(28, TILE, "bf16x3")
    tail = prog._pallas_operands("bf16x3")
    assert float(tail[-1]) > sh.COS_UNIT_SLACK == float(
        np.nextafter(tail[-1], np.float32(0)))
    l2 = ShardedKNN(db, mesh=mesh(), k=K)
    l2._pallas_setup(28, TILE, "bf16x3")
    assert len(l2._pallas_operands("bf16x3")) == len(tail) - 1


def test_the_slack_bounds_what_the_unit_rows_move():
    """D' of the placed float32 unit rows against 2c of the rows as
    given, in float64: one row's and a pair's, on random rows and on the
    built ones."""
    rng = np.random.default_rng(7)
    db = (rng.normal(size=(4000, 48)) * np.exp(rng.normal(size=(4000, 1)))
          ).astype(np.float32)
    q = (rng.normal(size=(16, 48)) * 3).astype(np.float32)
    unit_t, unit_q = sh._unit_rows(db)[0], sh._unit_rows(q)[0]
    diff = unit_q.astype(np.float64)[:, None] - unit_t.astype(np.float64)
    placed = np.einsum("qnd,qnd->qn", diff, diff)
    _, c_all = brute(db, q, db.shape[0])
    order, _ = brute(db, q, db.shape[0])
    two_c = np.empty_like(placed)
    np.put_along_axis(two_c, order, 2 * c_all, axis=1)
    p = placed - two_c
    assert np.abs(p).max() < 2.0 ** -21 * (1 + 2.0 ** -7)
    assert (p.max(1) - p.min(1)).max() < 0.76 * sh.COS_UNIT_SLACK


@pytest.mark.parametrize("cell", ["gist1m.sweep", "text2image2m5.sweep_ip"])
def test_the_l2_and_dot_programs_are_the_parents(cell):
    """Both fixtures of ``tests/program_digest.py`` (whole call, cut
    call) at an l2 and the dot shape; ``test_yfcc_filter`` and
    ``test_sub_batch`` hold all five.  A cosine program differs by its
    one compare and bit alone: the same builder with ``slack_outcome``."""
    whole = _json("tests", "fixtures", "unfiltered_program_digests.json")
    cut = _json("tests", "fixtures", "sub_batch_program_digests.json")
    assert program_digest.digest(cell) == whole[cell]
    assert program_digest.digest(cell, 1024) == cut[cell]["1024"]


def test_a_cosine_program_is_the_dot_program_with_one_more_bit():
    from knn_tpu.ops import pallas_knn as pk

    def text(**kw):
        prog = sh._pallas_certified_program(
            mesh(), 38, K, "ring", TILE, "bf16x3", n_train=3000,
            interpret=True, augmented=True, **kw)
        aval = jax.ShapeDtypeStruct
        return str(jax.make_jaxpr(prog)(
            aval((64, 32), np.float32), aval((3000, 32), np.float32),
            aval((), np.float32), aval((), np.float32)))

    dot, cos = text(), text(slack_outcome=True)
    assert dot != cos
    assert hashlib.sha256(dot.encode()).hexdigest() == hashlib.sha256(
        text().encode()).hexdigest()
    assert pk.RANK_SLACK == 2.0 ** -18
    # the flag word's bit 1, read back by the host
    packed = np.zeros((3, 20), np.int32)
    w = 17
    packed[:, w + 1] = [0, 1, 3]
    np.testing.assert_array_equal(sh.failed_by_slack(packed, w),
                                  [False, False, True])
    assert (sh.unpack_certified(packed, K, w, False)[2]
            == [False, True, True]).all()


def test_the_slack_bit_marks_a_certificate_only_the_slack_fails():
    """``_certify_pack_spmd`` with ``slack_outcome`` on built candidates:
    a bound far off certifies, one inside the slack's reach is flagged
    with bit 1 (uncertified BY the slack), one inside the tolerance's is
    flagged without it; the same call without ``slack_outcome`` packs
    the plain flag."""
    import functools

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from knn_tpu.ops import pallas_knn as pk
    from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS

    k, m = 3, 6
    w = sh._analysis_window(k, m)
    slack = np.float32(sh.COS_UNIT_SLACK)
    q = np.zeros((3, 8), np.float32)
    q[:, 0] = 1.0
    d32 = np.tile(np.float32(0.1) * np.arange(1, m + 2, dtype=np.float32),
                  (3, 1))
    li = np.tile(np.arange(m + 1, dtype=np.int32), (3, 1))
    d_k = d32[0, k - 1]
    bare = (d_k - np.float32(1.0)) + np.float32(pk.RANK_SLACK) * d_k + (
        np.float32(2.0 ** -14) * np.float32(2.0))
    lb = np.array([bare + 1.0, bare + 0.5 * slack, bare - 8 * slack],
                  np.float32)

    def flags(**kw):
        body = functools.partial(
            sh._certify_pack_spmd, consts=None, db_norm_max=jnp.float32(1.0),
            precision="bf16x3", quant_offset=0.0, m=m, k=k, w=w,
            merge="ring", n_train=None, hosts=1, chips=1,
            include_distances=False, **kw)
        run = jax.jit(jax.shard_map(
            lambda q, t, d, i, b, e: body(q, t, d, i, b, aug_slack=e),
            mesh=mesh(),
            in_specs=(P(QUERY_AXIS), P(DB_AXIS), P(QUERY_AXIS),
                      P(QUERY_AXIS), P(QUERY_AXIS), P()),
            out_specs=P(QUERY_AXIS), check_vma=False))
        packed = np.asarray(run(q, np.zeros((16, 8), np.float32), d32, li,
                                lb, slack))
        return packed[:, w + -(-(w - 1) // 32)], packed

    word, packed = flags(slack_outcome=True)
    assert word.tolist() == [0, 3, 1]
    np.testing.assert_array_equal(sh.failed_by_slack(packed, w),
                                  [False, True, False])
    np.testing.assert_array_equal(
        sh.unpack_certified(packed, k, w, False)[2], [False, True, True])
    assert flags()[0].tolist() == [0, 1, 1]


def test_the_rounded_low_half_is_the_casts_own_where_the_cast_rounds():
    """On the CPU (the cast is honoured) both forms of the rows' low
    half are one value, and high + low leaves 2^-18 of a value."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    x = sh._unit_rows(mix(2000, 4, dim=200)[0])[0]
    th2, tl2 = pk._split_rows(jnp.asarray(x), True)
    th = jnp.asarray(x).astype(jnp.bfloat16)   # the cast's own round trip
    tl = (jnp.asarray(x) - th.astype(jnp.float32)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(th), np.asarray(th2))
    np.testing.assert_array_equal(np.asarray(tl), np.asarray(tl2))
    assert np.abs(np.asarray(tl, np.float32)).max() > 0
    left = x - np.asarray(th, np.float32) - np.asarray(tl, np.float32)
    assert (np.abs(left) <= 2.0 ** -17 * np.abs(x)).all()


# --- the host's scorers ----------------------------------------------------------
def test_the_hosts_scorers_agree_on_the_cosine_of_the_rows_as_given():
    db, q = edged(3000, 16)
    tn, qn = refine.row_norms_f64(db), refine.row_norms_f64(q)
    want_i, want_c = brute(db, q)
    cand = np.broadcast_to(np.arange(3000), (16, 3000))
    for norms in (None, (qn, tn)):
        d, i = refine.refine_exact(db, q, cand, K, "cosine", norms)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_allclose(d, want_c, rtol=0, atol=1e-15)
        d, i = certified.host_exact_knn(db, q, K, metric="cosine",
                                        norms=norms)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_allclose(d, want_c, rtol=0, atol=1e-15)
    out = np.empty(16 * K)
    refine._score_members(db, q, want_i.ravel(), np.repeat(np.arange(16), K),
                          "cosine", out, (qn, tn))
    np.testing.assert_allclose(out.reshape(16, K), want_c, rtol=0,
                               atol=1e-15)
    # zero norms on either side: cosine 0, exactly
    assert (out.reshape(16, K)[3] == 1.0).all()
    assert refine.cosine_distance(np.zeros(2), np.array([0.0, 4.0])
                                  ).tolist() == [1.0, 1.0]


# --- the plain reference ------------------------------------------------------
def test_the_oracle_is_a_float64_argsort():
    db, q = edged(70_000, 24, dim=24)  # two blocks of rows
    want_i, want_c = brute(db, q)
    got_i, got_c = reference_cos.oracle_topk(db, q, K)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_c, want_c, rtol=0, atol=1e-15)
    cmp = reference_cos.compare(got_i, got_c, want_i, want_c, db, q)
    assert cmp["mismatched_rows"] == 0 and cmp["recall"] == 1.0
    assert cmp["dist_err_max"] <= 1e-14


def test_compare_measures_a_distance_as_the_guarantee_words_it():
    db, q = mix(3000, 8)
    want_i, want_c = brute(db, q)
    off = want_c.copy()
    off[3, 4] += 1e-3 * (want_c[3, 4] + 0.125)
    cmp = reference_cos.compare(want_i, off, want_i, want_c, db, q)
    assert cmp["mismatched_rows"] == 0
    assert cmp["dist_err_max"] == pytest.approx(1e-3, rel=1e-6)
    # a distance of a near-duplicate (c near 0) still has a scale
    assert reference_cos.compare(
        want_i, want_c + 2.0 ** -24, want_i, want_c, db, q)[
        "dist_err_max"] <= 2.0 ** -21
    swapped = want_i.copy()
    swapped[5, [0, 1]] = swapped[5, [1, 0]]
    cmp = reference_cos.compare(swapped, want_c, want_i, want_c, db, q)
    assert (cmp["mismatched_rows"], cmp["dist_err_max"]) == (1, 0.0)
    assert cmp["recall"] == 1.0
    # values of another metric, or not halved, are far outside
    unit = sh._unit_rows(db)[0][want_i].astype(np.float64)
    uq = sh._unit_rows(q)[0].astype(np.float64)
    l2 = ((uq[:, None] - unit) ** 2).sum(-1)
    assert reference_cos.compare(want_i, l2, want_i, want_c, db, q)[
        "dist_err_max"] > 0.1
    bad = want_c.copy()
    bad[0, 0] = np.nan
    assert reference_cos.compare(want_i, bad, want_i, want_c, db, q)[
        "dist_err_max"] == np.inf
    with pytest.raises(ValueError, match="shapes"):
        reference_cos.compare(want_i[:, :5], want_c, want_i, want_c, db, q)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_the_controls_break_what_the_configuration_says(precision):
    """The reference in the program's place, one precision down, under
    the configuration's own limits, at the cell's own width (float32
    loses its distances in the norms' 1,536 terms) and few rows: float32
    breaks the distance limit on every draw and the ranking on most,
    bfloat16 both."""
    db, q = mix(6000, 48, dim=CONFIG["dim"], clusters=4)
    k = int(CONFIG["k"])
    want_i, want_c = reference_cos.oracle_topk(db, q, k)
    got_i, got_c = reference_cos.lowprec_topk(db, q, k, precision)
    cmp = reference_cos.compare(got_i, got_c, want_i, want_c, db, q)
    checks = reference.Checks()
    for name, limit in CONFIG["limits"].items():
        checks.add(name, cmp[name], limit)
    assert checks.correct is False
    broke = {r["check"] for r in checks.rows if not r["ok"]}
    assert set(CONFIG["controls"][precision]) <= broke
    assert set(CONFIG["controls"]) == set(reference_cos.PRECISIONS)
    # the float64 reference itself sits far inside both
    assert reference_cos.compare(want_i, want_c, want_i, want_c, db, q)[
        "dist_err_max"] == 0.0
    with pytest.raises(ValueError, match="precision"):
        reference_cos.lowprec_topk(db, q, k, "int4")


# --- the span, the event and the counters -----------------------------------
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


def test_the_metric_rides_the_call_its_span_and_its_counters(fresh_registry):
    db, q = edged(3000, 24)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
    (placed,) = [e for e in obs.get_event_log().recent()
                 if e.get("name") == "placement.cosine_normalize"]
    assert (placed["rows"], placed["dim"], placed["zero_rows"]) == (
        3000, 32, 1)
    assert placed["slack"] == sh.COS_UNIT_SLACK and placed["seconds"] > 0
    assert series(mn.CERTIFIED_SLACK_QUERIES) == {}
    _, _, first = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        batch_size=8)
    # every outcome is there from the first call, at 0 where none took it
    slack = series(mn.CERTIFIED_SLACK_QUERIES)
    assert set(slack) == {(("outcome", o),) for o in (
        "certified", "uncertified", "uncertified_by_slack")}
    assert sum(slack.values()) == 24
    assert slack[(("outcome", "certified"),)] == first["certified"]
    assert slack[(("outcome", "uncertified_by_slack"),)] == first[
        "slack_fallback_queries"]
    _, _, second = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                         return_distances=False)
    prog.search_certified(q, selector="approx")
    ShardedKNN(db, mesh=mesh(), k=K).search_certified(
        q, selector="pallas", tile_n=TILE)
    assert series(mn.CERTIFIED_METRIC_QUERIES) == {
        (("metric", "cosine"),): 72.0, (("metric", "l2"),): 24.0}
    # the counted selector's certificate has no slack bit: pallas calls only
    assert sum(series(mn.CERTIFIED_SLACK_QUERIES).values()) == 48
    spans = [e for e in obs.get_event_log().recent() if e.get("span")]
    calls = [e for e in spans if e["span"] == "certified.call"]
    assert [c["metric"] for c in calls] == ["cosine"] * 3 + ["l2"]
    assert [c["pair_slack"] for c in calls] == [sh.COS_UNIT_SLACK] * 3 + [0.0]
    assert calls[0]["slack_fallback_queries"] == first[
        "slack_fallback_queries"]
    # one metric_map span a cosine call, child of the call: the unit
    # queries before, nothing after
    maps = [e for e in spans if e["span"] == "certified.metric_map"]
    assert [m["trace_id"] for m in maps] == [c["trace_id"] for c in calls[:3]]
    for m in maps:
        assert (m["parent"], m["metric"]) == ("certified.call", "cosine")
        assert m["before_s"] > 0 and m["after_s"] == 0
        assert m["dur_s"] == pytest.approx(m["before_s"] + m["under_s"],
                                           abs=2e-6)
    # the first call was cut in three: two sub-batches mapped with a
    # launch queued; the others are one launch, all of it before
    assert [m["under_batches"] for m in maps] == [2, 0, 0]
    assert [m["under_s"] > 0 for m in maps] == [True, False, False]
    hist = series(mn.SPAN_SECONDS)[(("span", "certified.metric_map"),)]
    assert hist["count"] == 3
    # the members the host re-scored: the rank_correct events' own sums
    ranked = [e for e in spans if e["span"] == "certified.rank_correct"]
    members = series(mn.RANK_CORRECT_MEMBERS)[()]
    assert members > 0 and members >= first["rank_corrected_queries"]
    assert first["rank_corrected_queries"] >= 6  # the copies of row 40
    assert len(ranked) == 3  # one record a pallas call
    # the device's distance block is fetched (halved on the host) where
    # distances are asked for, and not where they are not
    d2h = [e["d2h_bytes"] for e in spans if e["span"] == "certified.d2h"]
    assert d2h[0] > d2h[1]


# --- the cell through the benchmark's harness --------------------------------
BENCH = tinyroot.load_bench()
FULL = tiny_cos.merged_bench()
TINY = {"dim": 256}  # a test's width: two dim chunks would need 640


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tinyroot``'s copy with the held entries merged in and this
    configuration cut in width too (3,000 x 1,536 interpreted is a
    minute a batch)."""
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_cos")))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(FULL, f)
    path = os.path.join(root, "benchmark", "configs", "openai500k.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    # the CPU backend reports no memory; the validator refuses 0
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run_cell(root, traced: bool, seed=2**31 + 43) -> dict:
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.5, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], FULL, CELL, traced) == parsed
    return parsed


STAGES = {"dispatch_ms", "device_wait_ms", "d2h_ms", "unpack_ms",
          "rank_correct_ms", "repair_ms"}
#: the call's account, and two of the cell's three held entries:
#: listed since PR 53 (every parent has their spans and counters)
ACCOUNT = {"host_exposed_ms", "reselect_inflight_ms", "rank_score_ms",
           "rank_order_ms", "rank_buffers_ms"}
LISTED = STAGES | ACCOUNT | {
    "kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
    "rank_corrected_pct", "idle_pct.sweep", "rank_members_per_query",
    "slack_fallback_pct",
    # listed since PR 54 (its span is PR 53's: every parent records it)
    "repair_refine_ms"}
#: still held: the accepted entry with this cell appended is an edit
NEW = {"metric_map_ms"}


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_through_the_harness(root, cpu_memory_reading, traced):
    cell = harness.load_cell(root, CELL)
    assert cell.traffic["kind"] == "sweep_ip" and cell.chips == 1
    assert cell.config["reference"] == "cos"
    out = run_cell(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["compared"]) == {
        "mismatched_rows", "dist_err_max", "uncounted_batches",
        "changed_answers", "compiles_in_window"}
    want = {m["name"] for m in lastline.required_metrics(FULL, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        assert want == LISTED | NEW
        # BENCHMARK.json as committed lists everything but the held
        # metric_map_ms (an edit to an entry that is there)
        assert {m["name"] for m in lastline.required_metrics(
            BENCH, CELL, True)} == LISTED
        for name in STAGES | {"metric_map_ms", "rank_members_per_query"}:
            assert out["metrics"][name]["value"] > 0, name
        assert 0 <= out["metrics"]["slack_fallback_pct"]["value"] <= 100
    else:
        assert want == {"sweep_qps", "setup_s"}


_break_ranking = tiny_cos.rank_by_the_unit_rows


def _built_draw(monkeypatch):
    """The built corpus in the generator's place (cut or padded to the
    cell's width and rows), its queries tiled over the pool."""
    db, q, _ = built_corpus()

    def draw(spec, n, dim, seed, stream, of=None):
        src = db if stream == datagen.STREAM_ROWS else q
        out = np.zeros((n, dim), np.float32)
        out[:, :DIM] = np.resize(src, (n, DIM))
        return out

    monkeypatch.setattr(datagen_mix, "draw", draw)


@pytest.fixture
def built_root(root, tmp_path):
    """The cell at the built corpus's own rows (no second copy of a
    pair: ``np.resize`` would tile them) and k."""
    import shutil

    mine = str(tmp_path / "root")
    shutil.copytree(root, mine)
    path = os.path.join(mine, "benchmark", "configs", "openai500k.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(rows_n=built_corpus()[0].shape[0], k=2 * PAIRS, dim=128)
    with open(path, "w") as f:
        json.dump(cfg, f)
    return mine


def test_the_built_case_through_the_harness_is_correct(
        built_root, cpu_memory_reading, monkeypatch):
    _built_draw(monkeypatch)
    assert run_cell(built_root, False)["correct"] is True


def test_a_host_that_ranks_by_the_unit_rows_is_not_correct(
        built_root, cpu_memory_reading, monkeypatch):
    """The broken timed path: the parent's.  Every answer's ulp-pairs
    come back in the unit rows' rounding order, and the comparison has
    to say so, by the indices alone."""
    _built_draw(monkeypatch)
    _break_ranking(monkeypatch)
    out = run_cell(built_root, False)
    assert out["correct"] is False
    assert out["compared"]["mismatched_rows"]["value"] > 0
    assert out["compared"]["dist_err_max"]["value"] <= CONFIG["limits"][
        "dist_err_max"]


def test_distances_that_are_not_cosine_distances_are_not_correct(
        root, cpu_memory_reading, monkeypatch):
    """Squared distances of the unit rows handed back as they are (not
    halved): right order, wrong values."""
    real = ShardedKNN.search_certified

    def unhalved(self, queries, **kw):
        d, i, stats = real(self, queries, **kw)
        return 2.0 * d, i, stats

    monkeypatch.setattr(ShardedKNN, "search_certified", unhalved)
    out = run_cell(root, False)
    assert out["correct"] is False
    assert out["compared"]["mismatched_rows"]["value"] == 0


# --- the cell's data files ---------------------------------------------------
def test_the_configuration_is_the_source_whole():
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "openai500k"]
    assert entry["file"] == "benchmark/configs/openai500k.json"
    assert entry["reduced"] == [] == list(CONFIG["reduced_from_source"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["metric"], CONFIG["k"],
            CONFIG["reference"]) == (500_000, 1536, "cosine", 100, "cos")
    assert "queries" not in CONFIG  # fresh draws of the rows' own law
    assert CONFIG["rows"] == {"dist": "zipf_gauss_mix", "clusters": 4096,
                              "zipf_s": 1.0, "noise": 0.5,
                              "scale_sigma": 0.1}
    # the distance limit is set between two readings and under the
    # guarantee's worst-case bound (the configuration's limits_why)
    assert CONFIG["limits"] == {"mismatched_rows": 0,
                                "dist_err_max": 2.0 ** -21}
    assert set(CONFIG["limits_why"]) == set(CONFIG["limits"])
    assert CONFIG["require"] == _json(
        "benchmark", "configs", "bigann5m.json")["require"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openai500k", "sweep_cos", 1)
    # appended by PR 43, the seventh of each (PR 48 appended after them)
    assert bench["workloads"][6] == cell and bench["configs"][6] == entry
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bigann20m-x4.sweep"]
    traffic = _json("benchmark", "traffic", "sweep_cos.json")
    assert traffic["kind"] == "sweep_ip"
    assert {k: traffic[k] for k in (
        "batch_rows", "pool_batches", "selector", "check_rows",
        "trace_seconds")} == {"batch_rows": 4096, "pool_batches": 8,
                              "selector": "pallas", "check_rows": 64,
                              "trace_seconds": 4}


def test_the_held_entries_fit_the_benchmark_and_their_layer_files():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    assert [e["name"] for e in HELD] == [
        "metric_map_ms", "rank_members_per_query", "slack_fallback_pct"]
    for e in HELD:
        layer = _json("benchmark", "layers", f"{e['name']}.json")
        for key in ("layer", "unit", "moves", "source", "better"):
            assert layer[key] == e[key], (e["name"], key)
        assert e["layer"] == "host repair (ops/refine.py, ops/certified.py)"
        assert e["moves"] == "sweep_qps" and CELL in e["workloads"]
    # metric_map_ms is the accepted entry with the cell appended
    assert HELD[0] == dict(listed["metric_map_ms"], workloads=listed[
        "metric_map_ms"]["workloads"] + [CELL])
    for e in HELD[1:]:
        # listed since PR 53, as they stand in the held file; PR 55
        # appended its cell to rank_members_per_query's list, PR 57 its
        # own to both
        later = ["knnlm1m.sweep_k1024"] * (
            e["name"] == "rank_members_per_query") + [
            "openai500k-intfilter.sweep_cos_filter"]
        assert listed[e["name"]] == dict(e, workloads=[CELL] + later)
        assert e["workloads"] == [CELL]
        assert _json("benchmark", "layers", f"{e['name']}.json")[
            "reader"]["type"] == "counter"
    # the cell is in every list the parent's program can fill
    for name in LISTED:
        assert CELL in listed[name]["workloads"], name
    (qps,) = [m for m in BENCH["end_to_end"] if m["name"] == "sweep_qps"]
    assert qps["workloads"][6] == CELL


def test_the_control_script_reads_the_configurations_word(root, capsys):
    """``control_cos.py`` at the tiny root: it exits 0 only where every
    seed broke what the configuration names for that precision."""
    rc = control_cos.main(["--workload", CELL, "--precision", "bf16",
                           "--seeds", "5", "--root", root])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last[
        "every_seed_broke_what_the_configuration_names"] is True
    assert set(last["closest_to_sound"]) == set(CONFIG["limits"])
    with pytest.raises(SystemExit, match="no cosine cell"):
        control_cos.main(["--workload", "text2image2m5.sweep_ip",
                          "--precision", "f32", "--seeds", "5",
                          "--root", root])
