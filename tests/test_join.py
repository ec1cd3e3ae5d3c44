"""Bulk kNN-join engine (knn_tpu.join): query-side double buffering
over the EXISTING kernels and sharded programs.

The acceptance surface this file pins:

- the bitwise oracle — ``mode="certified"`` joins equal the f64 oracle
  (and the looped certified path) across precisions x kernels and on
  the IVF tier; ``mode="stream"`` joins equal the looped ``search``
  at the same padded block shape across the metric matrix;
- the super-HBM boundary matrices: query budgets that hold A exactly /
  one-row-over / many-x over, and a corpus B over the per-host HBM
  budget, with every executed superblock / db-segment / dispatch count
  pinned against the analysis.hbm byte model (and the sweep-nesting
  order against plan_join);
- the CPU pipelining acceptance: the double-buffered join answers the
  looped serving baseline's answers in one dispatch a superblock with
  a nonzero overlap_ratio;
- the ``join`` bench-artifact validator (the refresher's refusal list).
"""

import numpy as np
import pytest
from oracles import assert_same_neighbors

from knn_tpu.analysis import hbm
from knn_tpu.join import (JOIN_MODES, JOIN_VERSION, default_plan,
                          knn_join, knn_self_join, validate_join_block)
from knn_tpu.parallel import ShardedKNN, make_mesh

DIM = 16
DB_SHARDS = 2
MESH = (4, DB_SHARDS)  # 4 query shards x 2 db shards
QUERY_SHARDS = 4


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None]
          - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


def _db(rng, n, dim=DIM):
    return (rng.random((n, dim)) * 10).astype(np.float32)


@pytest.fixture
def corpus(rng):
    db = _db(rng, 600)
    db[200:220] = db[:20]  # exact duplicates across shard boundaries
    q = _db(rng, 70)
    return db, q


def _looped_search(prog, q, sb_rows, **kw):
    """The looped-serving reference at the SAME padded block shape the
    stream path dispatches (pad rows are ordinary queries whose outputs
    are sliced away) — the bitwise contract's other side."""
    ds, is_ = [], []
    for lo in range(0, q.shape[0], sb_rows):
        blk = q[lo:lo + sb_rows]
        valid = blk.shape[0]
        if valid < sb_rows:
            blk = np.pad(blk, ((0, sb_rows - valid), (0, 0)))
        d, i = prog.search(blk, **kw)
        ds.append(np.asarray(d)[:valid])
        is_.append(np.asarray(i)[:valid])
    return np.concatenate(ds), np.concatenate(is_)


# -- stream mode: bitwise vs looped serving, metric matrix ----------------
@pytest.mark.parametrize("metric", ["l2", "l1", "cosine", "dot"])
def test_stream_join_bitwise_equals_looped_search(corpus, metric):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=7, metric=metric)
    d, i, st = knn_join(prog, q, mode="stream", superblock_rows=32)
    ref_d, ref_i = _looped_search(prog, q, 32)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    assert st["mode"] == "stream" and st["rows"] == q.shape[0]
    assert st["superblocks"] == st["dispatches"] == -(-q.shape[0] // 32)
    assert st["db_segments"] == 1  # resident B streams nothing
    assert st["order"] == "query_major"
    assert st["rows_per_s"] > 0


def test_stream_join_return_sqrt_matches_search(corpus):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    d, i, _ = knn_join(prog, q, mode="stream", superblock_rows=24,
                       return_sqrt=True)
    ref_d, ref_i = _looped_search(prog, q, 24, return_sqrt=True)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)


# -- certified mode: the bitwise oracle across precisions x kernels ------
@pytest.mark.parametrize("precision,own_rows", [
    (None, False), ("bf16x3", False), ("int8", False),
    # the corpus's own rows as the queries, each row no answer of itself:
    # the pipelined self-join (knn_self_join; tests/test_deep_knng.py)
    # against the same oracle with the row masked out by id
    (None, True)])
def test_certified_join_oracle_across_precisions(corpus, precision,
                                                 own_rows):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=7)
    if own_rows:
        lo, hi = 190, 260  # across the duplicates and the shard boundary
        d64 = ((db.astype(np.float64)[None]
                - db[lo:hi].astype(np.float64)[:, None]) ** 2).sum(-1)
        d64[np.arange(hi - lo), np.arange(lo, hi)] = np.inf
        ref_i = np.argsort(d64, axis=-1, kind="stable")[:, :7]
        d, i, st = knn_self_join(prog, rows=(lo, hi))
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(
            d, np.take_along_axis(d64, ref_i, axis=-1), rtol=2.0 ** -18)
        assert (d[10:30, 0] == 0).all()  # rows 200-219 copy rows 0-19
        assert st["mode"] == "self" and st["self_excluded"] == hi - lo
        assert isinstance(st["overlap_ratio"], float)  # a pipeline
        return
    ref_d, ref_i = _oracle(db, q, 7)
    kw = {"selector": "approx"}
    if precision is not None:
        kw["precision"] = precision
    d, i, st = knn_join(prog, q, mode="certified", superblock_rows=24,
                        **kw)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    # bitwise-equal to the looped certified path by construction
    ld, li = [], []
    for lo in range(0, q.shape[0], 24):
        dd, ii, _ = prog.search_certified(q[lo:lo + 24], **kw)
        ld.append(dd)
        li.append(ii)
    np.testing.assert_array_equal(d, np.concatenate(ld))
    np.testing.assert_array_equal(i, np.concatenate(li))
    assert st["overlap_ratio"] is None  # the certified loop: no pipeline


@pytest.mark.parametrize("kernel", ["tiled", "streaming", "fused"])
def test_certified_join_oracle_across_kernels(corpus, kernel):
    db, q = corpus
    ref_d, ref_i = _oracle(db, q, 5)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    d, i, _ = knn_join(prog, q, mode="certified", superblock_rows=32,
                       selector="approx", kernel=kernel)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)


@pytest.mark.parametrize("metric", ["cosine", "dot"])
def test_certified_join_mips_cosine_fast_path(corpus, metric):
    """Satellite: the MIPS/cosine certified path (norm augmentation /
    unit rows at placement) joins bitwise with the looped certified
    call and ranks identically to the XLA search path."""
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=6, metric=metric)
    d, i, _ = knn_join(prog, q, mode="certified", superblock_rows=24,
                       selector="approx")
    ld, li = [], []
    for lo in range(0, q.shape[0], 24):
        dd, ii, _ = prog.search_certified(q[lo:lo + 24],
                                          selector="approx")
        ld.append(dd)
        li.append(ii)
    np.testing.assert_array_equal(d, np.concatenate(ld))
    np.testing.assert_array_equal(i, np.concatenate(li))
    ref_d, ref_i = _looped_search(prog, q, 24)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-5)


def test_certified_join_on_ivf_tier(rng):
    from knn_tpu.ivf.index import IVFIndex

    db = _db(rng, 800)
    q = _db(rng, 40)
    ref_d, ref_i = _oracle(db, q, 6)
    idx = IVFIndex(db, mesh=make_mesh(*MESH), k=6, seed=0)
    d, i, st = knn_join(idx, q, mode="certified", superblock_rows=16)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    assert st["superblocks"] == -(-40 // 16)
    # the probed tier has no resident placement to stream against
    with pytest.raises(ValueError, match="certified"):
        knn_join(idx, q, mode="stream")


# -- super-HBM A: query-budget boundary matrix ----------------------------
def test_query_budget_boundary_matrix(corpus):
    """Budget holds A exactly -> 1 superblock; one row over -> 2;
    many-x over -> the byte model's count.  Results invariant to the
    superblocking (indices exactly; distances to gemm-shape tolerance,
    the CPU caveat the serving engine documents)."""
    db, q = corpus  # 70 query rows
    n_a = q.shape[0]
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    ref_i = None
    ref_d = None
    cases = [
        (hbm.query_block_bytes(72, DIM), 1),    # holds all 70 (72 = 4x)
        (hbm.query_block_bytes(69, DIM), 2),    # one row short of A
        (hbm.query_block_bytes(16, DIM), 5),    # many-x over
    ]
    for budget, expect in cases:
        assert hbm.n_superblocks(n_a, DIM, budget,
                                 query_multiple=QUERY_SHARDS) == expect
        d, i, st = knn_join(prog, q, mode="stream",
                            query_budget_bytes=budget)
        assert st["superblocks"] == st["dispatches"] == expect
        assert st["plan"]["superblocks"] == expect
        if ref_i is None:
            ref_i, ref_d = i, d
        else:
            np.testing.assert_array_equal(i, ref_i)
            np.testing.assert_allclose(d, ref_d, rtol=1e-5)
    # a budget too small for even one query-shard multiple is loud
    with pytest.raises(ValueError, match="cannot hold"):
        knn_join(prog, q, mode="stream", query_budget_bytes=8)


# -- super-HBM B: host-RAM-tier corpus, both nesting orders ---------------
def test_superhbm_b_join_db_major_matches_byte_model_and_resident(rng):
    """B over the per-host HBM budget: the sweep nests db_major (each
    segment placed h2d ONCE), executed counts equal plan_join, and the
    result is bitwise-identical to the resident placement's looped
    search."""
    db = _db(rng, 400)
    q = _db(rng, 48)
    resident = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    budget = hbm.placement_bytes(64, DIM)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=budget)
    segs = hbm.n_sweeps(400, DIM, budget, shard_multiple=DB_SHARDS)
    assert segs >= 6  # genuinely many-x over
    d, i, st = knn_join(prog, q, mode="stream", superblock_rows=16)
    plan = default_plan(prog, 48, superblock_rows=16)
    assert plan["order"] == "db_major"  # B stream bytes dwarf A's
    assert st["order"] == plan["order"]
    assert st["superblocks"] == plan["superblocks"] == 3
    assert st["db_segments"] == plan["db_segments"] == segs
    assert st["dispatches"] == plan["dispatches"] == 3 * segs
    ref_d, ref_i = _looped_search(resident, q, 16)
    # streamed segments vs the resident placement: differently shaped
    # programs — same neighbours, f32 distances within rounding
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)


def test_superhbm_b_join_query_major_single_superblock(rng):
    # one superblock makes query_major the byte-minimal order (s = 1:
    # A + B <= B + g*A for every g >= 1) — the other nesting path
    db = _db(rng, 400)
    q = _db(rng, 48)
    resident = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=hbm.placement_bytes(64, DIM))
    d, i, st = knn_join(prog, q, mode="stream", superblock_rows=48)
    assert st["order"] == "query_major"
    assert st["superblocks"] == 1
    assert st["db_segments"] > 1
    assert st["dispatches"] == st["db_segments"]
    ref_d, ref_i = _looped_search(resident, q, 48)
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)


# -- pipelining acceptance (CPU) -------------------------------------------
def test_join_beats_looped_serving_on_cpu(rng):
    """ACCEPTANCE: the double-buffered join answers what looping the
    serving search over the same blocks answers, in one dispatch a
    superblock, with a nonzero measured dispatch-timeline overlap: the
    next block is in flight before the last is fetched, which the
    blocking loop never has.  (Counts and the overlap's sign, not rows
    a second against the loop's: on a shared CPU box that was a coin.)"""
    n, dim, rows, sb, k = 8192, 32, 1024, 256, 8
    db = rng.normal(size=(n, dim)).astype(np.float32)
    q = rng.normal(size=(rows, dim)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=k)

    overlap = 0.0
    for _ in range(3):
        d, i, st = knn_join(prog, q, mode="stream", superblock_rows=sb)
        assert st["superblocks"] == st["dispatches"] == rows // sb
        assert st["rows"] == rows and st["rows_per_s"] > 0
        overlap = max(overlap, st["overlap_ratio"])
    assert overlap > 0
    ref_d, ref_i = _looped_search(prog, q, sb)
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)


# -- env switches + argument validation -----------------------------------
def test_env_switches_drive_the_plan(corpus, monkeypatch):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    monkeypatch.setenv("KNN_TPU_JOIN_SUPERBLOCK", "32")
    monkeypatch.setenv("KNN_TPU_JOIN_DEPTH", "3")
    _, _, st = knn_join(prog, q, mode="stream")
    assert st["superblock_rows"] == 32
    assert st["depth"] == 3
    monkeypatch.setenv("KNN_TPU_JOIN_SUPERBLOCK", "many")
    with pytest.raises(ValueError, match="KNN_TPU_JOIN_SUPERBLOCK"):
        knn_join(prog, q, mode="stream")


def test_join_argument_validation(corpus):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    assert set(JOIN_MODES) == {"stream", "certified"}
    with pytest.raises(ValueError, match="unknown join mode"):
        knn_join(prog, q, mode="batch")
    with pytest.raises(ValueError, match="incompatible"):
        knn_join(prog, q[:, :8], mode="stream")
    with pytest.raises(ValueError, match="superblock_rows"):
        knn_join(prog, q, mode="stream", superblock_rows=0)
    # certified joins run the program's own certified path: k is pinned
    # at placement, a mismatching override refuses loudly
    with pytest.raises(ValueError, match="program.k"):
        knn_join(prog, q, mode="certified", k=9)


# -- the join bench-artifact validator ------------------------------------
def test_validate_join_block():
    block = {
        "join_version": JOIN_VERSION, "mode": "stream", "rows": 4096,
        "k": 10, "superblock_rows": 512, "depth": 2,
        "order": "query_major", "superblocks": 8, "db_segments": 1,
        "dispatches": 8, "rows_per_s": 12345.6, "overlap_ratio": 0.8,
    }
    assert validate_join_block(block) == []
    broken = {k: v for k, v in block.items() if k != "rows_per_s"}
    assert any("rows_per_s" in v for v in validate_join_block(broken))
    # a block that recorded its own failure is exempt — an honest error
    # field beats a refused line
    assert validate_join_block({"error": "join sweep failed"}) == []


def test_default_plan_is_jax_free_truth(corpus):
    db, q = corpus
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5)
    plan = default_plan(prog, q.shape[0], superblock_rows=32)
    ref = hbm.plan_join(q.shape[0], 600, DIM, superblock_rows=32,
                        db_segment_rows=0)
    for key in ("order", "superblocks", "db_segments", "dispatches",
                "h2d_bytes"):
        assert plan[key] == ref[key]
    _, _, st = knn_join(prog, q, mode="stream", superblock_rows=32)
    for key in ("superblocks", "db_segments", "dispatches"):
        assert st[key] == plan[key]
