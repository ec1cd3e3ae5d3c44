"""The bulk certified SELF-join (``knn_tpu.join.knn_self_join``): every
row a query of the placement it is part of, its own row out BY ID, the
certified path's blocks in the join's bounded pipeline.  CPU, small
sizes, one and four devices; ``benchmark/configs/deep5m-knng.json`` is
the deployment, ``benchmark/reference_graph.py`` the plain statement of
the answer.

Held here: the answer is the reference's graph (pairs of exact copies,
a family of copies longer than k, one longer than the analysis window,
which only the repair settles); it is ``search_certified`` at k + 1 over
the same rows with the row dropped by id, which ties the new path to the
old; blocks that straddle row tiles, a ragged last block and a first row
off every grain; the depth of the pipeline moves no bit; a launch moves
no query to the device; what refuses says why; and the SHARE: a chip's
diagonal block merged with its three off-diagonal searches is the uncut
graph, row for row.
"""

import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import reference_graph  # noqa: E402  (benchmark/)

from knn_tpu import obs, tuning  # noqa: E402
from knn_tpu.join import engine, knn_self_join  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import pallas_knn as pk  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402

K, DIM, N = 10, 24, 3000
#: a row tile and a block small enough that a call of a few thousand
#: interpreted rows is many of each (the default tile is 16,384 rows,
#: the engine's block 4,096)
TILE, BLOCK = 256, 192
W = K + 17  # the analysis window of the certified program


def mesh(shards):
    return make_mesh(1, shards, devices=jax.devices()[:shards])


@pytest.fixture
def small_grains(monkeypatch):
    monkeypatch.setitem(tuning.DEFAULT_KNOBS, "tile_n", TILE)
    monkeypatch.setattr(engine, "DEFAULT_SUPERBLOCK_ROWS", BLOCK)


@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def corpus(seed=0, n=N):
    """Clustered rows with exact copies among them: pairs across tiles
    and shards, a family of k + 2 (its tie run crosses the k-th place),
    and one of W + 3 (longer than the window: only the repair can rank
    it), each scattered over the rows."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(20, DIM)).astype(np.float32)
    db = (centres[rng.integers(0, 20, n)]
          + 0.3 * rng.normal(size=(n, DIM))).astype(np.float32)
    ids = rng.permutation(n)
    pairs = ids[:60].reshape(30, 2)
    db[pairs[:, 1]] = db[pairs[:, 0]]
    family, ids = ids[60:60 + K + 2], ids[60 + K + 2:]
    db[family] = db[family[0]]
    crowd = ids[:W + 3]
    db[crowd] = db[crowd[0]]
    return db, pairs, np.sort(family), np.sort(crowd)


def graph(db, lo, hi, k=K):
    return reference_graph.oracle_graph(db, np.arange(lo, hi), k)


def assert_is_the_graph(db, lo, hi, d, i):
    want_i, want_d = graph(db, lo, hi)
    np.testing.assert_array_equal(i, want_i)
    assert not (i == np.arange(lo, hi)[:, None]).any()
    # device float32 direct differences, float64 where the host
    # re-scored; an exact copy reads exactly 0
    np.testing.assert_allclose(d, want_d, rtol=2.0 ** -18, atol=0)
    assert ((d == 0) == (want_d == 0)).all()


# --- the answer ------------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 4])
def test_the_self_join_is_the_reference_graph(small_grains, shards):
    """Rows 37 to 2,950: a first row off every grain, blocks of 192 rows
    over tiles of 256 (every block straddles one or starts inside one),
    a ragged last block (33 rows), shards of 750 rows on four devices
    (blocks straddle those too)."""
    db, pairs, family, crowd = corpus()
    prog = ShardedKNN(db, mesh=mesh(shards), k=K)
    d, i, stats = knn_self_join(prog, rows=(37, 2950))
    assert_is_the_graph(db, 37, 2950, d, i)
    blocks = -(-(2950 - 37) // BLOCK)
    assert (stats["superblocks"], stats["dispatches"]) == (blocks, blocks)
    assert stats["self_excluded"] == stats["rows"] == 2950 - 37
    assert stats["certified"] + stats["fallback_queries"] == stats["rows"]
    assert 0 <= stats["overlap_ratio"] <= 1 and stats["rows_per_s"] > 0
    assert (stats["operands"], stats["sub_batch"]) == ("resident", "small")
    assert stats["terms"] == "hh+hl+lh" and stats["depth"] == 2
    # a pair: each the other's first neighbour, at distance 0
    for a, b in pairs:
        for row, other in ((a, b), (b, a)):
            if 37 <= row < 2950:
                assert (i[row - 37, 0], d[row - 37, 0]) == (other, 0.0)
    # the family of k + 2: a member's list is the k lowest ids of the
    # k + 1 others, all at 0
    for row in family:
        if 37 <= row < 2950:
            others = family[family != row]
            assert list(i[row - 37]) == list(others[:K])
            assert (d[row - 37] == 0).all()
    # the crowd of W + 3 is longer than the window: its rows fell back,
    # and the repair took the row itself out by id too
    inside = crowd[(crowd >= 37) & (crowd < 2950)]
    assert stats["fallback_queries"] >= inside.size
    for row in inside:
        assert list(i[row - 37]) == list(crowd[crowd != row][:K])


@pytest.mark.parametrize("shards", [1, 4])
def test_it_is_search_certified_at_k_plus_one_with_the_row_dropped(
        small_grains, shards):
    """The tie to the old path: the same rows as queries of a k + 1
    placement, ``search_certified(selector="pallas")``, the row itself
    dropped from each list by id (the last entry where k + 1 copies
    come before it)."""
    db, *_ = corpus(3)
    lo, hi = 300, 1500
    d, i, _ = knn_self_join(ShardedKNN(db, mesh=mesh(shards), k=K),
                            rows=(lo, hi))
    wide = ShardedKNN(db, mesh=mesh(shards), k=K + 1)
    wd, wi, _ = wide.search_certified(db[lo:hi], selector="pallas",
                                      tile_n=TILE)
    own = wi == np.arange(lo, hi)[:, None]
    own[~own.any(axis=1), -1] = True  # no such entry: the last goes
    np.testing.assert_array_equal(i, wi[~own].reshape(hi - lo, K))
    np.testing.assert_allclose(d, wd[~own].reshape(hi - lo, K),
                               rtol=2.0 ** -17, atol=0)


def test_a_block_of_the_cells_shape_is_four_launches():
    """The engine's own block (4,096 rows) at the default tile: the
    sub-batch rule cuts it into four launches of 1,024, and the ragged
    second block is one launch that starts early enough to end at the
    call's last row."""
    db, *_ = corpus(5, n=4500)
    prog = ShardedKNN(db, mesh=mesh(1), k=K)
    d, i, stats = knn_self_join(prog)
    assert_is_the_graph(db, 0, 4500, d, i)
    assert (stats["superblocks"], stats["dispatches"]) == (2, 5)
    assert (stats["sub_batch"], stats["sub_batch_rows"]) == (
        "resident", 1024)
    assert stats["pallas_knobs"]["interpret"] is True
    # one tile of 16,384 rows: no bin-merge, so no group of one is short
    assert stats["pallas_knobs"]["select_merge_short"] == 0
    assert stats["tuning"]["source"] == "default"


# --- the pipeline ------------------------------------------------------------------
@pytest.mark.parametrize("shards", [1, 4])
def test_the_depth_moves_no_bit(small_grains, monkeypatch, shards):
    db, *_ = corpus(7)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K)
    out = {}
    for depth in (1, 2, 3):
        monkeypatch.setattr(engine, "SELF_JOIN_DEPTH", depth)
        d, i, stats = knn_self_join(prog, rows=(100, 2100))
        assert stats["depth"] == depth
        out[depth] = (d, i, stats["fallback_queries"],
                      stats["rank_corrected_queries"])
    for depth in (2, 3):
        np.testing.assert_array_equal(out[depth][0], out[1][0])
        np.testing.assert_array_equal(out[depth][1], out[1][1])
        assert out[depth][2:] == out[1][2:]
    assert out[1][2] > 0  # the repair ran, in the pipeline and out of it


def spans(name):
    return [e for e in obs.get_event_log().recent()
            if e.get("span") == name]


def test_a_launch_moves_no_query_and_the_spans_say_what_ran(
        small_grains, fresh_registry):
    db, *_ = corpus(9)
    prog = ShardedKNN(db, mesh=mesh(1), k=K)
    knn_self_join(prog, rows=(0, 600))  # the placement's one-time passes
    obs.reset_event_log(None)
    before = obs.snapshot()
    lo, hi = 64, 64 + 5 * BLOCK + 50
    _, _, stats = knn_self_join(prog, rows=(lo, hi))
    blocks = spans("join.block")
    assert [(b["lo"], b["rows"]) for b in blocks] == [
        (s, min(BLOCK, hi - s)) for s in range(lo, hi, BLOCK)]
    assert sum(b["flagged"] for b in blocks) == stats["fallback_queries"]
    # the stages are a block's: one record a block, its children, and a
    # launch placed nothing
    for stage in ("certified.dispatch", "certified.device_wait",
                  "certified.d2h", "certified.unpack",
                  "certified.rank_correct", "certified.repair"):
        got = spans(stage)
        assert len(got) == len(blocks), stage
        assert {e["parent"] for e in got} == {"join.block"}
    assert {e["h2d_bytes"] for e in spans("certified.dispatch")} == {0}
    # once a call: the bulk span, the exposed seconds under both names
    (bulk,) = spans("join.bulk")
    assert (bulk["rows"], bulk["mode"], bulk["self_excluded"],
            bulk["blocks"]) == (hi - lo, "self", hi - lo, len(blocks))
    (exposed,), (account,) = spans("join.exposed"), spans(
        "certified.exposed")
    assert exposed["dur_s"] == pytest.approx(account["dur_s"], abs=1e-6)
    assert exposed["dur_s"] == pytest.approx(
        exposed["fill_s"] + exposed["between_s"] + exposed["drain_s"],
        abs=1e-5)
    assert 0 < exposed["dur_s"] < bulk["dur_s"]
    assert account["launches"] >= len(blocks)

    def series(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in obs.snapshot()[name]["series"]}

    rows_before = {tuple(sorted(s["labels"].items())): s["value"]
                   for s in before[mn.JOIN_ROWS]["series"]}
    assert (series(mn.JOIN_ROWS)[(("mode", "self"),)]
            - rows_before[(("mode", "self"),)]) == hi - lo
    assert series(mn.JOIN_BLOCKS_INFLIGHT)[()] == 0  # drained


def test_at_most_two_blocks_are_in_flight(small_grains, monkeypatch):
    """The order of the steps, read off the call itself: block b + 1 is
    launched before block b is collected, block b is settled (its
    re-select fetched) after block b + 1 is collected, and never more
    than two blocks are launched and not collected."""
    from knn_tpu.parallel import sharded

    steps = []
    for name in ("launch", "collect", "settle"):
        real = getattr(sharded._SelfJoinCall, name)

        def spy(self, *args, _real=real, _name=name):
            blk = _real(self, *args)
            steps.append((_name, (blk or args[0]).lo))
            return blk

        monkeypatch.setattr(sharded._SelfJoinCall, name, spy)
    db, *_ = corpus(11)
    knn_self_join(ShardedKNN(db, mesh=mesh(1), k=K), rows=(0, 5 * BLOCK))
    los = [b * BLOCK for b in range(5)]
    assert [lo for step, lo in steps if step == "launch"] == los
    assert [lo for step, lo in steps if step == "collect"] == los
    assert [lo for step, lo in steps if step == "settle"] == los
    at = {step: i for i, step in enumerate(steps)}
    inflight = most = 0
    for step, _ in steps:
        inflight += {"launch": 1, "collect": -1, "settle": 0}[step]
        most = max(most, inflight)
    assert most == 2
    for a, b in zip(los, los[1:]):
        assert at[("launch", b)] < at[("collect", a)]
        assert at[("collect", b)] < at[("settle", a)]


# --- what refuses, and why ---------------------------------------------------------
def test_what_it_cannot_answer_refuses_with_its_reason():
    db, *_ = corpus(13, n=600)
    for metric, why in (("dot", "inner product"), ("cosine", "cosine")):
        prog = ShardedKNN(db, mesh=mesh(1), k=K, metric=metric)
        with pytest.raises(ValueError, match=f"squared-L2.*{why}"):
            knn_self_join(prog)
    l2 = ShardedKNN(db, mesh=mesh(1), k=K)
    with pytest.raises(ValueError, match="predicate"):
        knn_self_join(l2, filter_tags=np.zeros((600, 2), np.int32))
    for rows in ((-1, 10), (5, 5), (0, 601)):
        with pytest.raises(ValueError, match="no range"):
            knn_self_join(l2, rows=rows)
    tier = ShardedKNN(db, mesh=mesh(1), k=K, hbm_budget_bytes=20_000)
    assert tier.hosttier_stats() is not None
    with pytest.raises(ValueError, match="host-RAM shard tier"):
        knn_self_join(tier)
    placed = ShardedKNN(db, mesh=mesh(1), k=K)._tp
    with pytest.raises(ValueError, match="pre-placed"):
        knn_self_join(ShardedKNN(placed, mesh=mesh(1), k=K, n_train=600))


# --- the share ---------------------------------------------------------------------
def test_four_diagonal_blocks_and_their_searches_are_the_uncut_graph(
        small_grains):
    """The deployment the cell is one chip of: a set cut into four
    shards, each chip holding one.  A chip's rows against its own shard
    is the DIAGONAL block (the self-join: the one block in which a query
    is in the corpus it searches); against each other shard an ordinary
    certified search.  Merged by (float64 distance, id) over the four
    lists, every row's answer is the uncut reference's, row for row."""
    db, *_ = corpus(17, n=2400)
    s = 600
    shards = [ShardedKNN(db[c * s:(c + 1) * s], mesh=mesh(1), k=K)
              for c in range(4)]
    want_i, want_d = graph(db, 0, 2400)
    for c in range(4):
        mine = db[c * s:(c + 1) * s]
        lists = []
        for other in range(4):
            if other == c:
                _, i, _ = knn_self_join(shards[c])
            else:
                _, i, _ = shards[other].search_certified(
                    mine, selector="pallas", tile_n=TILE)
            lists.append(i + other * s)
        cand = np.concatenate(lists, axis=1)
        diff = mine[:, None, :].astype(np.float64) - db[cand]
        d = np.einsum("qcd,qcd->qc", diff, diff)
        order = np.lexsort((cand, d), axis=1)[:, :K]
        np.testing.assert_array_equal(
            np.take_along_axis(cand, order, axis=1),
            want_i[c * s:(c + 1) * s])
        np.testing.assert_array_equal(
            np.take_along_axis(d, order, axis=1), want_d[c * s:(c + 1) * s])


# --- the kernel's second select ----------------------------------------------------
@pytest.mark.parametrize("n,tile,block_q,first,queries,row_block", [
    (700, 256, 8, 100, 40, 256),    # inside one tile
    (700, 256, 8, 250, 24, 256),    # across two
    (700, 256, 8, 250, 24, 128),    # a tile cut by rows, two steps
    (1000, 128, 16, 640, 300, 128),  # more rows than a tile: four tiles
    (700, 256, 8, -30, 40, 256),    # begins in the shard before
    (700, 256, 8, 690, 40, 256),    # ends in the shard after
])
def test_the_own_tiles_are_the_masked_kernels_bit_for_bit(
        n, tile, block_q, first, queries, row_block):
    """``self_tile_candidates`` against the kernel under validity words
    that mask each query's own row and nothing else: the same
    candidates and bounds, every tile, to the bit."""
    rng = np.random.default_rng(n + first)
    db = np.zeros((n, 128), np.float32)
    db[:, :20] = rng.normal(size=(n, 20))
    ids = np.arange(first, first + queries)
    held = (ids >= 0) & (ids < n)
    q = np.where(held[:, None], db[np.clip(ids, 0, n - 1)], 0.0)
    prep = pk.row_operands(db, tile_n=tile, with_lo=True)
    kw = dict(block_q=block_q, tile_n=tile, survivors=None,
              precision="bf16x3", interpret=True, db_prepared=prep,
              row_block=row_block)
    plain = [np.asarray(x)[:queries] for x in pk._bin_candidates(q, db, **kw)]
    got = pk.self_tile_candidates(
        q, prep, *plain, np.int32(first), block_q=block_q, tile_n=tile,
        survivors=None, terms="hh+hl+lh", row_block=row_block,
        interpret=True)
    # (a row id past the shard's rows names a pad row of its last tile:
    # masked too, and no candidate either way)
    valid = np.ones((queries, prep[-1].shape[0]), bool)
    there = (ids >= 0) & (ids < valid.shape[1])
    valid[np.flatnonzero(there), ids[there]] = False
    want = pk._bin_candidates(
        q, db, valid_words=pk.pack_valid_words(valid, tile), **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(w)[:queries])
    # the row was a candidate of the plain launch and is none now
    assert all((plain[1][r] == i).any() for r, i in enumerate(ids)
               if 0 <= i < n)
    assert not (np.asarray(got[1]) == ids[:, None]).any()
