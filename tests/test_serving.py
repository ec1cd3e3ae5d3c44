"""Shape-bucketed serving engine (knn_tpu.serving): exactness across
bucket boundaries, the compile-count bound, warmup, micro-batching, and
trace replay — on the 8-virtual-device CPU mesh.

Exactness contract (serving.engine module docstring): padding is
arithmetic-transparent, so bucketed results are BITWISE identical to a
direct ``search()`` of the same placed batch; against the *unpadded*
direct call, neighbor identity and lexicographic tie-break order are
preserved on every backend, while distances additionally match bitwise
only where the backend's matmul reduction order is batch-shape invariant
(TPU MXU — CPU XLA's own direct calls already differ across batch
shapes in the last float bits, independent of this engine).
"""

import numpy as np
import pytest

from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.serving import (
    QueryQueue,
    ServingEngine,
    bucket_for,
    bucket_ladder,
    parse_buckets,
    split_sizes,
)
from knn_tpu.serving.buckets import normalize_ladder

K = 7
DIM = 12
BUCKETS = (8, 16, 32)


# -- ladder unit tests (pure python) --------------------------------------
def test_bucket_ladder_geometric():
    assert bucket_ladder(8, 64) == (8, 16, 32, 64)
    # non-power-of-two top rung is kept exactly
    assert bucket_ladder(8, 100) == (8, 16, 32, 64, 100)
    assert bucket_ladder(5, 5) == (5,)
    with pytest.raises(ValueError):
        bucket_ladder(0, 8)
    with pytest.raises(ValueError):
        bucket_ladder(16, 8)
    with pytest.raises(ValueError):
        bucket_ladder(8, 64, growth=1.0)


def test_bucket_for_boundaries():
    assert bucket_for(BUCKETS, 1) == 8
    assert bucket_for(BUCKETS, 8) == 8
    assert bucket_for(BUCKETS, 9) == 16
    assert bucket_for(BUCKETS, 32) == 32
    assert bucket_for(BUCKETS, 33) is None  # oversize: caller splits
    with pytest.raises(ValueError):
        bucket_for(BUCKETS, 0)


def test_parse_buckets():
    assert parse_buckets(None) is None
    assert parse_buckets("") is None
    assert parse_buckets("auto") == bucket_ladder()
    assert parse_buckets("64, 8,16") == (8, 16, 64)
    assert parse_buckets([32, 8, 8]) == (8, 32)
    with pytest.raises(ValueError):
        parse_buckets("8,x")
    with pytest.raises(ValueError):
        normalize_ladder([])


def test_split_sizes():
    assert split_sizes(70, 32) == [32, 32, 6]
    assert split_sizes(32, 32) == [32]
    assert split_sizes(3, 32) == [3]
    with pytest.raises(ValueError):
        split_sizes(0, 32)


# -- engine fixtures -------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(3)
    db = (rng.random((400, DIM)) * 10).astype(np.float32)
    q = (rng.random((40, DIM)) * 10).astype(np.float32)
    labels = rng.integers(0, 3, 400).astype(np.int32)
    mesh = make_mesh(4, 2)
    prog = ShardedKNN(db, mesh=mesh, k=K, labels=labels, num_classes=3)
    engine = ServingEngine(prog, buckets=BUCKETS)
    return prog, engine, q


def _padded_direct(prog, q, bucket):
    """The reference result: a DIRECT search() of the bucket-padded batch."""
    qp = np.zeros((bucket, q.shape[1]), np.float32)
    qp[: q.shape[0]] = q
    d, i = prog.search(qp)
    return np.asarray(d)[: q.shape[0]], np.asarray(i)[: q.shape[0]]


# -- exactness across bucket boundaries -----------------------------------
@pytest.mark.parametrize("n", [1, 7, 8, 9, 15, 16, 17, 31, 32])
def test_bucketed_bitwise_matches_direct_across_boundaries(served, n):
    prog, engine, q = served
    d_b, i_b = engine.search(q[:n])
    # bitwise vs the direct call at the same placed batch: pad rows
    # change NOTHING about real rows, the scatter drops nothing
    d_ref, i_ref = _padded_direct(prog, q[:n], bucket_for(BUCKETS, n))
    assert np.array_equal(d_b, d_ref)
    assert np.array_equal(i_b, i_ref)
    # vs the unpadded direct call: identical neighbors in identical
    # order; distances to every matched neighbor agree to f32 roundoff
    # (bitwise on reduction-order-invariant backends — see module doc)
    d_u, i_u = prog.search(q[:n])
    assert np.array_equal(np.asarray(i_u), i_b)
    np.testing.assert_allclose(np.asarray(d_u), d_b, rtol=1e-5, atol=0)


def test_bucketed_tie_break_order_matches_direct(served):
    """Exact duplicate db rows force lexicographic (distance, index)
    ties into the top-k; the bucketed path must resolve them in the
    identical order as the direct call."""
    rng = np.random.default_rng(11)
    base = (rng.random((60, DIM)) * 10).astype(np.float32)
    db = np.concatenate([base, base, base])  # every row triplicated
    mesh = make_mesh(4, 2)
    prog = ShardedKNN(db, mesh=mesh, k=6)
    engine = ServingEngine(prog, buckets=BUCKETS)
    q = base[:20] + np.float32(1e-3)
    for n in (1, 8, 9, 20):
        _, i_b = engine.search(q[:n])
        _, i_u = prog.search(q[:n])
        assert np.array_equal(np.asarray(i_u), i_b), n


def test_oversize_request_splits(served):
    prog, engine, q = served
    assert q.shape[0] > BUCKETS[-1]
    d_b, i_b = engine.search(q)  # 40 rows > top bucket 32
    _, i_u = prog.search(q)
    assert i_b.shape == (q.shape[0], K)
    assert np.array_equal(np.asarray(i_u), i_b)
    disp = engine.stats()["per_bucket_dispatches"]
    assert disp.get(32, 0) >= 1 and disp.get(8, 0) >= 1  # 40 = 32 + 8


# -- compile-count bound ---------------------------------------------------
def test_compile_count_bounded_by_ladder(served):
    """A replayed trace of 20 DISTINCT batch sizes compiles at most
    len(buckets) programs — the serving subsystem's core promise."""
    prog, _, q = served
    engine = ServingEngine(prog, buckets=BUCKETS)
    reqs = [q[:n] for n in range(1, 21)]  # 20 distinct sizes
    results, report = engine.replay(reqs, depth=2)
    assert report["compile_count"] <= len(BUCKETS)
    assert report["executables"] <= len(BUCKETS)
    assert report["requests"] == 20
    assert report["sustained_qps"] > 0
    for n, (_, idx) in zip(range(1, 21), results):
        _, i_u = prog.search(q[:n])
        assert np.array_equal(np.asarray(i_u), idx), n


def test_warmup_precompiles_every_bucket(served):
    prog, _, q = served
    engine = ServingEngine(prog, buckets=BUCKETS)
    counts = engine.warmup()
    assert counts["search"] == len(BUCKETS)
    before = engine.stats()["compile_count"]
    engine.replay([q[:n] for n in (1, 5, 9, 17, 30)], depth=2)
    # warmed ladder: the trace triggers ZERO further compiles
    assert engine.stats()["compile_count"] == before


def test_engine_predict_matches_direct(served):
    prog, engine, q = served
    engine.warmup(ops=("predict",))
    for n in (1, 9, 40):
        assert np.array_equal(
            np.asarray(prog.predict(q[:n])), engine.predict(q[:n])
        ), n


def test_engine_validates(served):
    prog, engine, q = served
    with pytest.raises(ValueError):
        engine.submit(q[:3], op="nope")
    with pytest.raises(ValueError):
        engine.submit(q[:, :4])  # wrong dim
    with pytest.raises(ValueError):
        engine.replay([q[:2]], depth=0)
    with pytest.raises(RuntimeError):
        # no labels on this placement -> predict program must refuse
        ServingEngine(
            ShardedKNN(np.zeros((64, DIM), np.float32) + 1.0,
                       mesh=prog.mesh, k=3),
            buckets=(8,),
        ).warmup(ops=("predict",))


# -- ShardedKNN entry points ----------------------------------------------
def test_search_bucketed_and_compile_cache_stats(served):
    prog, _, q = served
    d1, i1 = prog.search_bucketed(q[:9], buckets=BUCKETS)
    d2, i2 = prog.search_bucketed(q[:9], buckets=BUCKETS)  # engine reused
    assert np.array_equal(d1, d2) and np.array_equal(i1, i2)
    _, i_u = prog.search(q[:9])
    assert np.array_equal(np.asarray(i_u), i1)
    stats = prog.compile_cache_stats()
    assert {"program_cache", "distinct_shapes", "dispatches",
            "shape_counts"} <= set(stats)
    assert stats["dispatches"] >= 1
    assert stats["serving_engines"]  # the bucketed engine is visible


# -- micro-batching queue --------------------------------------------------
def test_queue_coalesces_and_scatters_exactly(served):
    prog, engine, q = served
    with QueryQueue(engine, max_wait_ms=250.0) as qq:
        futs = [qq.submit(q[3 * j : 3 * j + 3]) for j in range(6)]
        results = [f.result(timeout=60) for f in futs]
        stats = qq.stats()
    # all six requests land inside one max-wait window -> ONE dispatch
    assert stats["requests"] == 6
    assert stats["dispatches"] == 1
    assert stats["coalesced_rows"] == 18
    # arrival-to-result latency (includes the queue wait, unlike the
    # engine's dispatch-to-result percentiles)
    assert stats["latency_ms"]["count"] == 6
    assert stats["latency_ms"]["p50"] > 0
    for j, (d, i) in enumerate(results):
        _, i_u = prog.search(q[3 * j : 3 * j + 3])
        assert np.array_equal(np.asarray(i_u), i), j
        assert d.shape == (3, K)


def test_queue_zero_wait_still_exact(served):
    prog, engine, q = served
    with QueryQueue(engine, max_wait_ms=0.0) as qq:
        futs = [qq.submit(q[n : n + 2]) for n in range(0, 12, 2)]
        for n, f in zip(range(0, 12, 2), futs):
            _, i = f.result(timeout=60)
            _, i_u = prog.search(q[n : n + 2])
            assert np.array_equal(np.asarray(i_u), i)
        assert qq.stats()["dispatches"] >= 1


def test_queue_close_flushes_pending(served):
    _, engine, q = served
    qq = QueryQueue(engine, max_wait_ms=10_000.0)  # deadline never fires
    fut = qq.submit(q[:4])
    qq.close()  # close must flush, not drop
    d, i = fut.result(timeout=5)
    assert i.shape == (4, K)
    with pytest.raises(RuntimeError):
        qq.submit(q[:2])


def test_queue_predict_op(served):
    prog, engine, q = served
    with QueryQueue(engine, max_wait_ms=100.0, op="predict") as qq:
        futs = [qq.submit(q[5 * j : 5 * j + 5]) for j in range(3)]
        for j, f in enumerate(futs):
            labels = f.result(timeout=60)
            assert np.array_equal(
                np.asarray(prog.predict(q[5 * j : 5 * j + 5])), labels
            ), j


def test_queue_validates(served):
    _, engine, _ = served
    with pytest.raises(ValueError):
        QueryQueue(engine, max_wait_ms=-1.0)
    with pytest.raises(ValueError):
        QueryQueue(engine, op="nope")


def test_queue_rejects_bad_dim_and_survives(served):
    """A malformed request is rejected at submit (wrong feature dim must
    never reach the coalescing concatenate) and the queue keeps serving
    well-formed requests afterwards."""
    prog, engine, q = served
    with QueryQueue(engine, max_wait_ms=20.0) as qq:
        with pytest.raises(ValueError):
            qq.submit(q[:3, :4])
        f = qq.submit(q[:3])
        _, i = f.result(timeout=60)
        _, i0 = prog.search(q[:3])
        assert np.array_equal(np.asarray(i0), i)


# -- trace replay (a serving sweep, full size) ------------------------------
@pytest.mark.slow
def test_trace_replay_sustained_and_bounded(served):
    """A serving sweep's shape: a log-uniform variable-batch
    trace replayed with dispatch-ahead — sustained q/s, tail latency,
    and the compile bound all present and consistent."""
    prog, _, _ = served
    rng = np.random.default_rng(5)
    pool = (rng.random((256, DIM)) * 10).astype(np.float32)
    ladder = bucket_ladder(8, 64)
    engine = ServingEngine(prog, buckets=ladder)
    engine.warmup()
    sizes = np.exp(rng.uniform(0, np.log(64), size=60)).astype(int).clip(1, 64)
    reqs = [pool[int(rng.integers(0, 256 - s)) :][: int(s)] for s in sizes]
    results, report = engine.replay(reqs, depth=2)
    assert report["compile_count"] <= len(ladder)
    assert report["total_queries"] == int(sizes.sum())
    assert report["sustained_qps"] > 0
    lat = report["latency_ms"]
    assert lat["count"] == 60
    assert lat["p50"] <= lat["p95"] <= lat["p99"] <= lat["max"]
    for s, (_, idx) in zip(sizes, results):
        assert idx.shape == (int(s), K)


# -- metric matrix through the serving surface (join-PR satellite) --------
@pytest.mark.parametrize("metric", ["l1", "cosine", "dot"])
def test_metric_matrix_bucketed_matches_direct_search(rng, metric):
    """l1 / cosine / dot serve through search_bucketed with the same
    neighbors and tie-break order as the direct search — the bucketed
    exactness contract is metric-independent."""
    db = (rng.random((300, DIM)) * 10).astype(np.float32)
    q = (rng.random((11, DIM)) * 10).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=5, metric=metric)
    ref_d, ref_i = prog.search(q)
    d, i = prog.search_bucketed(q, buckets=BUCKETS)
    np.testing.assert_array_equal(i, np.asarray(ref_i))
    np.testing.assert_allclose(d, np.asarray(ref_d), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("metric", ["l1", "cosine", "dot"])
def test_metric_matrix_serving_engine(rng, metric):
    db = (rng.random((300, DIM)) * 10).astype(np.float32)
    q = (rng.random((9, DIM)) * 10).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=5, metric=metric)
    eng = ServingEngine(prog, buckets=BUCKETS)
    ref_d, ref_i = prog.search(q)
    d, i = eng.search(q)
    np.testing.assert_array_equal(i, np.asarray(ref_i))
    np.testing.assert_allclose(d, np.asarray(ref_d), rtol=1e-5,
                               atol=1e-6)
    st = eng.stats(include_slo=False)
    assert sum(st["per_bucket_dispatches"].values()) >= 1
