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
- the CPU throughput acceptance: the double-buffered join beats the
  looped serving baseline on rows/s with a nonzero overlap_ratio;
- the MODEL_VERSION-7 join roofline: modeled db HBM bytes per query
  fall as 1/superblock_rows until bound_class flips off hbm_bound,
  and attributed join blocks validate against the roofline schema;
- the ``join`` bench-artifact validator (the refresher's refusal list).
"""

import time

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


# -- throughput acceptance (CPU) ------------------------------------------
def test_join_beats_looped_serving_on_cpu(rng):
    """ACCEPTANCE: on the CPU backend the double-buffered join moves
    more rows/s than looping the serving search over the same padded
    blocks, with a nonzero measured dispatch-timeline overlap."""
    n, dim, rows, sb, k = 8192, 32, 1024, 256, 8
    db = rng.normal(size=(n, dim)).astype(np.float32)
    q = rng.normal(size=(rows, dim)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=k)

    def looped_rows_per_s():
        t0 = time.perf_counter()
        for lo in range(0, rows, sb):
            d, i = prog.search(q[lo:lo + sb])
            np.asarray(d)
            np.asarray(i)  # block per dispatch: the serving shape
        return rows / (time.perf_counter() - t0)

    knn_join(prog, q, mode="stream", superblock_rows=sb)  # warm
    looped_rows_per_s()  # warm
    # wall-clock comparison on a shared CPU box: retry the whole
    # best-of-3 duel a few times so one noisy scheduler quantum can't
    # fail the run — the join still has to win an identically-measured
    # round outright
    best_join = best_base = overlap = 0.0
    for _attempt in range(3):
        for _ in range(3):
            _, _, st = knn_join(prog, q, mode="stream", superblock_rows=sb)
            best_join = max(best_join, st["rows_per_s"])
            overlap = max(overlap, st["overlap_ratio"])
        best_base = max(best_base,
                        max(looped_rows_per_s() for _ in range(3)))
        if best_join >= best_base:
            break
    assert overlap > 0
    assert best_join >= best_base, (
        f"join {best_join:.0f} rows/s did not beat looped serving "
        f"{best_base:.0f} rows/s")


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


# -- the MODEL_VERSION-7 join roofline ------------------------------------
def test_join_model_db_bytes_amortize_until_bound_flips():
    """The pinned amortization law: modeled db HBM bytes per query fall
    as 1/superblock_rows while the block stays hbm_bound, until the
    bound flips to a term that stops shrinking (custom peaks make the
    flip land inside the sweep)."""
    from knn_tpu.obs import roofline

    peaks = {"bf16_flops": 400e12, "int8_flops": 800e12,
             "hbm_gbps": 800.0, "vpu_ops": 40e12, "h2d_gbps": 50.0}
    sbs = [128, 512, 2048, 8192, 32768, 131072]
    models = [roofline.join_cost_model(
        n_a=1_000_000, n_b=1_000_000, d=128, k=100, superblock_rows=sb,
        selector="exact", device_kind="TPU v5e", peaks=peaks)
        for sb in sbs]
    per_q = [m["join"]["db_bytes_per_query"] for m in models]
    bounds = [m["bound_class"] for m in models]
    assert bounds[0] == "hbm_bound"
    assert bounds[-1] != "hbm_bound"  # the flip the regime exists for
    for j in range(1, len(sbs)):
        # exact 1/S law: same db bytes spread over more queries
        np.testing.assert_allclose(per_q[j] * sbs[j],
                                   per_q[0] * sbs[0], rtol=1e-12)
    # once flipped, ceiling rows/s stops improving with superblock size
    flip = bounds.index(next(b for b in bounds if b != "hbm_bound"))
    assert models[flip]["ceiling_qps"] is not None


def test_join_model_block_validates_and_h2d_can_bind():
    from knn_tpu.obs import roofline

    model = roofline.join_cost_model(
        n_a=65536, n_b=1_000_000, d=128, k=100, superblock_rows=4096,
        selector="exact", device_kind="TPU v5e")
    block = roofline.attribute(model, 1e5)
    assert roofline.validate_block(block) == []
    assert block["terms"]["h2d"]["overlapped"] is True
    assert block["join"]["superblocks"] == 16
    # a starved host link makes the stream the bound
    slow = roofline.join_cost_model(
        n_a=65536, n_b=1_000_000, d=128, k=100, superblock_rows=4096,
        selector="exact", device_kind="TPU v5e",
        peaks={**roofline.PEAKS_BY_KIND["TPU v5e"], "h2d_gbps": 1e-3})
    assert slow["bound_class"] == "h2d_bound"
    assert roofline.validate_block(
        roofline.attribute(slow, 1e3)) == []


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
