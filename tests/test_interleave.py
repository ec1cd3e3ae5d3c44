"""The interleaved placement (PR 58): a ``ShardedKNN`` built from host
rows with ``row_attr`` lays its rows out on the device in ONE fixed
pseudo-random order, so that the rows any range keeps fall over all the
kernel's bins whatever the attribute's order among the rows.  Positions
on the device, ids on the host:

- PR 57's held case (a contiguous range on ``attr = position``: the
  valid rows in one row tile of three, full bins on 7 queries of 64) now
  certifies, and so does a stride-periodic attribute, which a stride
  layout would put back in one tile;
- every public method such a placement answers returns the caller's ids,
  equal to a placement without the attribute, ties in id order, ``-1``
  padding kept (the float32 launches but for WHICH copies of a row are
  named where a run of them crosses the k-th column); the self-join
  refuses and says so; a placement with tags beside the attribute
  answers ``filter_tags`` by id too;
- the order is a function of the row count alone; a placement without
  ``row_attr``, a pre-placed array and the host-RAM tier hold no map;
- the frames on the certified call's trace stack keep their sizes.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "benchmark"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_cosfilter  # noqa: E402  (benchmark/)
from test_cos_filter import drawn, mesh  # noqa: E402  (tests/)
from test_yfcc_filter import random_bags  # noqa: E402  (tests/)
from test_dim_chunking import frame_slots  # noqa: E402  (tests/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import pallas_knn as pk, tagfilter  # noqa: E402
from knn_tpu.parallel import ShardedKNN  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402
from knn_tpu.join import knn_join  # noqa: E402
from knn_tpu.serving.engine import ServingEngine  # noqa: E402

K, TILE = 10, 1024


def oracle(db, q, valid, k, metric):
    """float64 (indices, distances) of the contract over each query's
    ``valid`` rows [Q, N] bool, in (distance, id) order, padded with -1
    and +inf: direct differences (l2) or the cosine of the values as
    given, a block of rows at a time."""
    db64, q64 = db.astype(np.float64), q.astype(np.float64)
    d = np.empty((len(q), len(db)))
    for lo in range(0, len(db), 4096):
        t = db64[lo:lo + 4096]
        if metric == "cosine":
            d[:, lo:lo + 4096] = 1.0 - (q64 @ t.T) / (
                np.linalg.norm(q64, axis=1)[:, None]
                * np.linalg.norm(t, axis=1)[None])
        else:
            d[:, lo:lo + 4096] = (
                (q64[:, None, :] - t[None]) ** 2).sum(-1)
    d[~valid] = np.inf
    ids = np.broadcast_to(np.arange(len(db)), d.shape)
    return reference_cosfilter._pad(ids, d, k)


# --- PR 57's held case: the range's rows in one tile of three -----------------
HELD_ROWS, HELD_K = 49_152, 100


@pytest.fixture(scope="module")
def held():
    rng = np.random.default_rng(59)
    db = rng.normal(size=(HELD_ROWS, 32)).astype(np.float32)
    db *= rng.lognormal(0.0, 0.3, size=(HELD_ROWS, 1)).astype(np.float32)
    return db, rng.normal(size=(64, 32)).astype(np.float32)


HELD_ATTRS = {
    # the ids are the rows' positions: the last 4,096 are a quarter of
    # the third row tile, 32 rows a lane of it (the cell's 1 % is 39 a
    # lane of one tile of 31)
    "contiguous": (lambda n: np.arange(n), [HELD_ROWS - 4096, HELD_ROWS]),
    # a third of the rows, every third one: a layout by stride 3 would
    # lay them all in one tile
    "periodic": (lambda n: np.arange(n) % 3, [1, 2]),
}


@pytest.mark.parametrize("kind", sorted(HELD_ATTRS))
def test_a_range_on_a_sorted_attribute_fills_no_bin(held, kind):
    db, q = held
    make, span = HELD_ATTRS[kind]
    attr = make(HELD_ROWS)
    ranges = np.tile(np.asarray(span, np.int64), (len(q), 1))
    prog = ShardedKNN(db, mesh=mesh(), k=HELD_K, metric="cosine",
                      row_attr=attr)
    d, i, stats = prog.search_certified(q, selector="pallas",
                                        filter_range=ranges)
    assert stats["pallas_knobs"]["survivor_depth"] == 4  # three tiles
    assert stats["filter"]["interleaved"] is True
    # PR 57's tree: 7 of these 64 on a full bin under "contiguous" (130
    # nearest valid rows in one tile's 128 bins at depth 4)
    # (the depth rule allows a full bin on 5 % of the queries, 3 of 64:
    # every third row of this seed's puts one query on one)
    assert stats["bin_overflow_queries"] == stats["fallback_queries"] == (
        0 if kind == "contiguous" else 1)
    valid = reference_cosfilter.in_range(attr, ranges)
    want_i, want_d = oracle(db, q, valid, HELD_K, "cosine")
    np.testing.assert_array_equal(i, want_i)
    assert np.abs(d - want_d).max() <= 2.0 ** -18
    # the model's assumption, counted: the range's rows lie over every
    # tile and lane about evenly
    places = prog._row_places(np.flatnonzero(valid[0]))
    per_tile = np.bincount(places // pk.TILE_N, minlength=3)
    assert per_tile.min() > 0.7 * per_tile.mean()
    assert np.unique(places % 128).size > 100


def test_the_bins_of_an_answer_are_read_where_the_rows_lie():
    """``_bin_overflows`` takes ids: depth + 1 rows that LIE in one bin
    are a full bin, depth + 1 ids a tile apart are not."""
    db, attr, q = drawn("l2")
    prog = ShardedKNN(db, mesh=mesh(), k=K, train_tile=1024, row_attr=attr)
    prog.search_certified(q[:2], selector="pallas", tile_n=TILE)
    depth = prog._plan["survivor_depth"]
    order = prog._row_order
    one_bin = order[np.arange(depth + 1) * 128 + 5]  # tile 0, lane 5
    assert (np.diff(np.sort(one_bin)) != 128).any()  # no bin by id
    top = np.full((2, K), -1)
    top[0, :depth + 1] = one_bin
    top[1, :depth + 1] = np.arange(depth + 1) * 128 + 5
    obs.reset(enabled=True)
    try:
        assert prog._bin_overflows(top) == 1
        (series,) = obs.snapshot()[mn.CERTIFIED_BIN_OVERFLOW]["series"]
    finally:
        obs.reset()
    assert series["value"] == 1


# --- ids in, ids out ----------------------------------------------------------
ROWS = 3001


def tied(metric: str):
    """``drawn``'s rows with exact copies planted in scattered places:
    twelve of one row (more than k: a run of equal distances that
    crosses the k-th rank), three of eleven others; under cosine every
    copy at its own power of two of length.  The first twelve queries
    lie near a copied row."""
    db, _, q = drawn(metric, seed=58)
    rng = np.random.default_rng(58)
    spots = rng.permutation(ROWS)
    src, at = spots[:12], spots[12:].tolist()
    for j, row in enumerate(src):
        for _ in range(11 if j == 0 else 2):
            scale = 2.0 ** rng.integers(-2, 3) if metric == "cosine" else 1.0
            db[at.pop()] = db[row] * np.float32(scale)
    q = q.copy()
    q[:12] = db[src] + rng.normal(0, 1e-3, size=(12, db.shape[1])).astype(
        np.float32)
    labels = rng.integers(0, 7, size=ROWS).astype(np.int32)
    return db, q, labels


@pytest.fixture(scope="module")
def pairs():
    """(a placement without the attribute, one with it, rows, attribute,
    queries) by metric and shards."""
    made = {}

    def of(metric: str, shards: int):
        if (metric, shards) not in made:
            db, q, labels = tied(metric)
            attr = np.arange(ROWS)  # the ids: sorted, the worst case
            kw = dict(mesh=mesh(shards), k=K, metric=metric,
                      train_tile=1024, labels=labels, num_classes=7)
            made[metric, shards] = (
                ShardedKNN(db, **kw), ShardedKNN(db, row_attr=attr, **kw),
                db, attr, q)
        return made[metric, shards]

    return of


def _half(attr, q):
    return np.tile([[int(attr.size // 2), int(attr.size)]], (len(q), 1))


def _few(attr, q):
    return np.tile([[5, 5 + K - 3]], (len(q), 1))


#: name -> the call: what the certified host ranks, to the letter
METHODS = {
    "certified": lambda p, q: p.search_certified(
        q, selector="pallas", tile_n=TILE)[:2],
    "certified_exact": lambda p, q: p.search_certified(
        q, selector="exact")[:2],
    "certified_approx": lambda p, q: p.search_certified(
        q, selector="approx")[:2],
    "certified_int8": lambda p, q: p.search_certified(
        q, selector="pallas", tile_n=TILE, precision="int8")[:2],
    "range_search": lambda p, q: p.range_search_certified(
        q, radius_sq=25.0)[:3],
    "majority": lambda p, q: p.predict_certified(
        q, selector="pallas", tile_n=TILE)[:1],
    "softmax": lambda p, q: p.predict_certified(
        q, vote="softmax", temperature=0.07, classes_out=2,
        selector="pallas", tile_n=TILE)[:2],
}
#: name -> (the call, the columns of its launch): the float32 launches,
#: whose k columns cannot say which copies lie past the k-th
LAUNCHES = {
    "search": (lambda p, q: p.search(q), K),
    "search_wider": (lambda p, q: p.search(q, k=K + 3, return_sqrt=True),
                     K + 3),
    "radius_search": (lambda p, q: p.radius_search(
        q, 0.4 if p.metric == "cosine" else 4.5, max_neighbors=16)[:2], 16),
    "search_bucketed": (lambda p, q: p.search_bucketed(
        q, buckets=(16, 64)), K),
    "engine": (lambda p, q: ServingEngine(
        p, buckets=(8, 32)).submit(q).result(), K),
    "join_stream": (lambda p, q: knn_join(
        p, q, mode="stream", superblock_rows=24)[:2], K),
}
FILTERED = {"filter_half": _half, "filter_fewer_than_k": _few}


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("method", sorted(METHODS))
def test_an_interleaved_placement_answers_in_ids(pairs, method, metric,
                                                 shards):
    if method == "range_search" and metric == "cosine":
        pytest.skip("range_search_certified is l2's")
    if method == "softmax" and metric == "l2":
        pytest.skip("the weighted vote is cosine's")
    plain, prog, db, attr, q = pairs(metric, shards)
    assert plain._row_order is None and prog._row_order is not None
    want = [np.asarray(a) for a in METHODS[method](plain, q)]
    got = [np.asarray(a) for a in METHODS[method](prog, q)]
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    if method in ("certified", "certified_exact"):
        d, i = got
        # the copies of a row come back tied and in id order, also
        # where the run of twelve crosses the k-th rank
        for pos in range(12):
            run = i[pos][d[pos] == d[pos][0]]
            assert run.size >= 3 and (np.diff(run) > 0).all()
        assert (d[0] == d[0][0]).all()
        twelve = oracle(db, q[:1], np.ones((1, ROWS), bool), 12, metric)[0]
        np.testing.assert_array_equal(i[0], np.sort(twelve[0])[:K])


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("method", sorted(LAUNCHES))
def test_a_float32_launch_answers_in_ids(pairs, method, metric, shards):
    """Ids, the plain placement's distances column for column, equal
    distances in id order; the ids the plain placement's wherever no
    run of equal distances crosses the last column, and rows AT that
    distance where one does (query 0's twelve copies at k = 10)."""
    plain, prog, db, attr, q = pairs(metric, shards)
    call, k = LAUNCHES[method]
    (d0, i0), (d1, i1) = (
        [np.asarray(a) for a in call(p, q)] for p in (plain, prog))
    np.testing.assert_array_equal(d0, d1)
    assert i1.shape == (len(q), k) and (i1 < ROWS).all()
    # one column more, of the plain placement: where a run crosses
    far_d, far_i = (np.asarray(a) for a in plain.search(q, k=k + 12))
    crossed = far_d[:, k - 1] == far_d[:, k]
    assert crossed[0] or k > 12
    np.testing.assert_array_equal(i0[~crossed], i1[~crossed])
    for pos in np.flatnonzero(crossed):
        run = far_i[pos][far_d[pos] == far_d[pos, k - 1]]
        last = i1[pos][far_d[pos, :k] == far_d[pos, k - 1]]
        last = last[last >= 0]  # (past the radius: -1 on both sides)
        assert np.isin(last, run).all() and np.unique(last).size == last.size
        np.testing.assert_array_equal(i0[pos, :k - last.size],
                                      i1[pos, :k - last.size])
    for pos in range(len(q)):
        for value in np.unique(d1[pos][np.isfinite(d1[pos])]):
            assert (np.diff(i1[pos][d1[pos] == value]) > 0).all()
    if method == "radius_search":
        assert (i1 == -1).any() and np.isinf(d1[i1 == -1]).all()


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_the_device_vote_gathers_labels_where_the_rows_lie(pairs, metric,
                                                           shards):
    """``predict`` and the engine's ``op="predict"``: the labels lie on
    the device as the rows do; the plain placement's votes wherever no
    run of copies crosses the k-th rank (which copies vote there is the
    placement's)."""
    plain, prog, db, attr, q = pairs(metric, shards)
    far_d = np.asarray(plain.search(q, k=K + 1)[0])
    whole = far_d[:, K - 1] != far_d[:, K]
    assert 0 < whole.sum() < len(q)
    want = np.asarray(plain.predict(q))
    np.testing.assert_array_equal(np.asarray(prog.predict(q))[whole],
                                  want[whole])
    served = ServingEngine(prog, buckets=(32,)).submit(
        q, op="predict").result()
    np.testing.assert_array_equal(np.asarray(served)[whole], want[whole])
    np.testing.assert_array_equal(prog._labels_host, plain._labels_host)
    np.testing.assert_array_equal(
        np.asarray(prog._labels), plain._labels_host[prog._row_order])


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("kind", sorted(FILTERED))
def test_a_filtered_call_answers_in_ids(pairs, kind, metric, shards):
    _, prog, db, attr, q = pairs(metric, shards)
    ranges = FILTERED[kind](attr, q)
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        filter_range=ranges)
    valid = reference_cosfilter.in_range(attr, ranges)
    want_i, want_d = oracle(db, q, valid, K, metric)
    np.testing.assert_array_equal(i, want_i)
    there = i >= 0
    np.testing.assert_allclose(d[there], want_d[there], rtol=2.0 ** -17,
                               atol=2.0 ** -18)
    assert np.isinf(d[~there]).all()
    assert stats["filter"]["interleaved"] is True
    if kind == "filter_fewer_than_k":
        # -1 padding kept, the ids in range and no other
        assert (there.sum(axis=1) == K - 3).all()
        assert set(i[there].tolist()) == set(range(5, 5 + K - 3))


def test_the_self_join_refuses(pairs):
    l2 = pairs("l2", 1)[1]
    with pytest.raises(ValueError, match="row_attr"):
        l2.self_join_call(0, 64, 64)


def test_more_copies_than_columns():
    """Every row the same: a launch names k rows at the distance, in id
    order, and which k is the placement's; the certified call's host
    ranks by id whatever the device returned."""
    db = np.ones((64, 8), np.float32)
    q = np.ones((3, 8), np.float32)
    for shards in (1, 4):
        prog = ShardedKNN(db, mesh=mesh(shards), k=K,
                          row_attr=np.arange(64))
        d, i = prog.search(q)
        assert (d == 0).all() and (np.diff(i) > 0).all()
        assert ((0 <= i) & (i < 64)).all()
        _, i, _ = prog.search_certified(q, selector="exact")
        np.testing.assert_array_equal(i, np.tile(np.arange(K), (3, 1)))


# --- tags beside the attribute: the index lies as the rows do -----------------
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_filter_tags_on_an_interleaved_placement(metric, shards,
                                                 monkeypatch):
    """A placement with BOTH attributes is interleaved (``row_attr``),
    and its tag index's bitmaps and lists name a row by where it lies:
    ``filter_tags`` answers the float64 oracle's ids, mapped tags and
    listed ones, and equals a placement with the tags alone."""
    # (a bitmap from a sixteenth of a shard's rows: both forms at 3,001)
    monkeypatch.setattr(tagfilter, "BITMAP_ROW_SHARE", 16)
    rng = np.random.default_rng(13)
    db, attr, q = drawn(metric, seed=13)
    indptr, tags = random_bags(rng, ROWS, 40, 3)
    ft = rng.integers(0, 12, size=(len(q), 2)).astype(np.int32)
    ft[::3, 1] = -1
    ft[1] = [39, 38]
    kw = dict(mesh=mesh(shards), k=K, metric=metric, train_tile=1024,
              row_tags=(indptr, tags))
    plain, prog = ShardedKNN(db, **kw), ShardedKNN(db, row_attr=attr, **kw)
    assert plain._row_order is None and prog._row_order is not None
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        filter_tags=ft)
    told = prog._tag_index(TILE)["stats"]
    assert 0 < told["bitmap_tags"] < told["tags"] and told["list_ids"] > 0
    assert stats["filter"]["list_ids"] > 0
    row_of = np.repeat(np.arange(ROWS), np.diff(indptr))
    valid = np.ones((len(q), ROWS), bool)
    for pos, pair in enumerate(ft):
        for tag in pair[pair >= 0]:
            valid[pos] &= np.isin(np.arange(ROWS), row_of[tags == tag])
    want_i, want_d = oracle(db, q, valid, K, metric)
    np.testing.assert_array_equal(i, want_i)
    there = i >= 0
    np.testing.assert_allclose(d[there], want_d[there], rtol=2.0 ** -17,
                               atol=2.0 ** -18)
    assert stats["filter"]["filter"] == "tags"
    assert stats["filter"]["interleaved"] is True
    d0, i0, _ = plain.search_certified(q, selector="pallas", tile_n=TILE,
                                       filter_tags=ft)
    np.testing.assert_array_equal(i, i0)
    np.testing.assert_array_equal(d, d0)
    # the host's bags stay by id, the device's are by position
    host = prog._tag_index(TILE)["host"]
    np.testing.assert_array_equal(host[1], plain._tag_index(TILE)["host"][1])
    at = tagfilter.bags_at(host[0], prog._row_places(host[1]))[1]
    for tag in (0, 39):
        lo, hi = host[0][tag], host[0][tag + 1]
        assert (np.diff(at[lo:hi]) > 0).all()
        np.testing.assert_array_equal(
            np.sort(prog._row_order[at[lo:hi]]), host[1][lo:hi])


# --- the order, and who has none ----------------------------------------------
def _events(log):
    return [json.loads(ln) for ln in log.read_text().splitlines()]


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_the_order_is_a_function_of_the_row_count_alone(metric, tmp_path):
    db, attr, _ = drawn(metric)
    log = tmp_path / "obs.jsonl"
    obs.reset(enabled=True)
    obs.reset_event_log(str(log))
    try:
        one = ShardedKNN(db, mesh=mesh(), k=K, metric=metric, row_attr=attr)
        two = ShardedKNN(db[::-1].copy(), mesh=mesh(4), k=3, metric=metric,
                         row_attr=np.arange(ROWS))
    finally:
        obs.reset()
        obs.reset_event_log(from_env=True)
    order = one._row_order
    np.testing.assert_array_equal(order, two._row_order)
    np.testing.assert_array_equal(order, sh._interleave_order(ROWS))
    assert order.dtype == np.int32
    np.testing.assert_array_equal(np.sort(order), np.arange(ROWS))
    # no stride: neighbours on the device are no fixed step apart by id
    assert np.unique(np.diff(order)).size > ROWS // 2
    told = [e for e in _events(log) if e.get("name") == "placement.interleave"]
    assert [e["rows"] for e in told] == [ROWS, ROWS]
    assert all(0 <= e["seconds"] < 60 for e in told)
    # on the device: row order[p] at position p (cosine: its unit row),
    # the attribute beside it; on the host everything as given
    placed = np.asarray(one._tp)[:ROWS, :db.shape[1]]
    given = db[order]
    if metric == "cosine":
        given = sh._unit_rows(db)[0][order]
        np.testing.assert_array_equal(
            one._cos_norms,
            np.sqrt((db.astype(np.float64) ** 2).sum(-1)))
    np.testing.assert_array_equal(placed, given)
    np.testing.assert_array_equal(one._host_train(), db)
    np.testing.assert_array_equal(one._row_attr, attr)
    np.testing.assert_array_equal(one._row_places(order), np.arange(ROWS))
    held = one._attr_rows(TILE)["device"][0]
    np.testing.assert_array_equal(
        np.asarray(held), tagfilter.place_attr(
            tagfilter.check_row_attr(attr, ROWS)[order], shards=1,
            shard_rows=ROWS, tile_n=TILE)[0])
    # -1, pad rows and the sentinel pass through both maps
    odd = np.asarray([-1, ROWS, np.iinfo(np.int32).max, 0])
    assert one._row_ids(odd).tolist()[:3] == odd.tolist()[:3]
    assert one._row_places(odd).tolist()[:3] == odd.tolist()[:3]


def test_who_is_laid_out_as_given_holds_no_map(tmp_path, monkeypatch):
    db, attr, q = drawn("l2")
    log = tmp_path / "obs.jsonl"
    obs.reset(enabled=True)
    obs.reset_event_log(str(log))
    try:
        bare = ShardedKNN(db, mesh=mesh(), k=K)
        placed = ShardedKNN(jax.device_put(db, jax.sharding.NamedSharding(
            mesh(), jax.sharding.PartitionSpec(sh.db_axes(mesh())))),
            mesh=mesh(), k=K, row_attr=attr)
        tier = ShardedKNN(db, mesh=mesh(), k=K, row_attr=attr,
                          hbm_budget_bytes=100_000)
        dot = ShardedKNN(db, mesh=mesh(), k=K, metric="dot", row_attr=attr)
        # a budget the rows fit under engages no tier and keeps no order
        roomy = ShardedKNN(db, mesh=mesh(), k=K, row_attr=attr,
                           hbm_budget_bytes=10 ** 9)
    finally:
        obs.reset()
        obs.reset_event_log(from_env=True)
    for prog in (bare, placed, tier, dot):
        assert prog._row_order is None and prog._row_place_cache is None
    assert tier.hosttier_stats() is not None
    assert roomy._row_order is not None and roomy.hosttier_stats() is None
    names = [e.get("name") for e in _events(log)]
    assert names.count("placement.interleave") == 1  # roomy's
    # no map: the position IS the id, and nothing is gathered for it
    monkeypatch.setattr(sh, "_rows_at", None)
    pos = np.arange(5)
    assert bare._row_ids(pos) is pos and bare._row_places(pos) is pos
    d0, i0 = bare.search(q)
    d1, i1 = placed.search(q)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    assert isinstance(d0, jax.Array)  # the launch's own arrays, as ever
    # a pre-placed array keeps its order: a filtered call still answers
    ranges = np.tile([[int(attr.min()), int(attr.max()) + 1]], (len(q), 1))
    _, i2, stats = placed.search_certified(q, selector="pallas", tile_n=TILE,
                                           filter_range=ranges)
    assert stats["filter"]["interleaved"] is False
    np.testing.assert_array_equal(i2, np.asarray(i0))


# --- the frames the first batch's draw follows --------------------------------
@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="frame sizes are CPython 3.12's")
@pytest.mark.parametrize("name,slots", [
    ("search_certified", 107), ("_certify_pallas", 57),
    ("_vote_certified", 82), ("_vote_pallas", 47)])
def test_the_certified_calls_keep_their_frames(name, slots):
    """The map is applied in closures and helpers, not as a local of the
    functions on the trace stack (``scripts/frame_sizes.py``; root
    PERF.md section 7 "Since PR 52" (1))."""
    assert frame_slots(getattr(ShardedKNN, name).__code__) == slots
