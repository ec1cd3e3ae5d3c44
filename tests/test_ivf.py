"""IVF tier (knn_tpu.ivf): probe-pruned streaming that stays exact.

The pinned contracts, in ISSUE order: deterministic seeded k-means;
clustered data at nprobe < ncentroids streams <= 1/4 of the brute-force
db bytes (priced with the shared operand row widths) while every
final answer stays bitwise-equal to exact brute force; the certificate
DETECTS forced probe misses and the exact fallback repairs them;
nprobe = ncentroids reproduces the non-IVF exact anchor bitwise across
selectors, precisions, and kernels; the PR-13 mutation oracle extends
to IVF across interleavings and re-cluster compactions; the live
mixed-traffic harness crosses >= 2 background swaps with no request
lost; the ivf artifact block validates."""

import threading

import numpy as np
import pytest

from knn_tpu import loadgen, obs
from knn_tpu.index.artifact import MutationBudgetError
from knn_tpu.ivf import IVFIndex, SELECTORS, train_kmeans
from knn_tpu.ivf.artifact import IVF_VERSION, validate_ivf_block
from knn_tpu.ops.refine import refine_shared_exact
from knn_tpu.parallel.mesh import make_mesh

DIM = 16
K = 5
NCLUSTERS = 8


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset(enabled=True)
    yield
    obs.reset()


def _clustered(rng, per=40, spread=0.05, sep=20.0):
    """Well-separated gaussian blobs: the workload IVF exists for."""
    cents = (rng.normal(size=(NCLUSTERS, DIM)) * sep).astype(np.float32)
    rows = np.concatenate([
        cents[i] + rng.normal(size=(per, DIM)).astype(np.float32) * spread
        for i in range(NCLUSTERS)])
    qs = (cents[rng.integers(0, NCLUSTERS, 24)]
          + rng.normal(size=(24, DIM)).astype(np.float32) * spread)
    return rows, qs


def _exact(db, q, k=K):
    """The brute-force oracle: the SAME f64 refine anchor every
    non-IVF certified final answer resolves through."""
    return refine_shared_exact(
        db, q, np.arange(db.shape[0], dtype=np.int64), k)


@pytest.fixture(scope="module")
def clustered():
    rng = np.random.default_rng(7)
    rows, qs = _clustered(rng)
    return rows, qs


# -- k-means ----------------------------------------------------------------
def test_kmeans_seeded_deterministic(clustered):
    rows, _ = clustered
    mesh = make_mesh()
    a = train_kmeans(rows, NCLUSTERS, mesh=mesh, iters=4, seed=3)
    b = train_kmeans(rows, NCLUSTERS, mesh=mesh, iters=4, seed=3)
    assert np.array_equal(a.centroids, b.centroids)
    assert np.array_equal(a.assign, b.assign)
    assert a.counts.sum() == rows.shape[0]
    assert (a.residuals >= 0).all()
    # the residual really bounds every member's distance to its centroid
    d = np.linalg.norm(rows.astype(np.float64)
                       - a.centroids.astype(np.float64)[a.assign], axis=1)
    assert (d <= a.residuals[a.assign] + 1e-12).all()


# -- the pruning pin --------------------------------------------------------
def test_clustered_probe_streams_quarter_of_brute_force(clustered):
    """The acceptance bar: nprobe < ncentroids on clusterable data
    streams <= 1/4 the db bytes of brute force (operand byte model),
    fully certified, and the final (d, i) are bitwise brute force."""
    rows, qs = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=1, train_iters=4, seed=0)
    d_i, i_i, st = idx.search_certified(qs)
    d_ref, i_ref = _exact(rows, qs)
    assert np.array_equal(i_i, i_ref)
    assert np.array_equal(d_i, d_ref)
    assert st["fallback_rate"] == 0.0
    assert st["certified_queries"] == qs.shape[0]
    assert st["recall_at_k"] == 1.0
    assert st["bytes_streamed_ratio"] <= 0.25, st
    assert st["probe_fraction"] <= 0.25, st


@pytest.mark.parametrize("selector", SELECTORS)
def test_nprobe_all_reproduces_exact_bitwise(clustered, selector):
    rows, qs = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=NCLUSTERS, train_iters=2, seed=0)
    d_i, i_i, st = idx.search_certified(qs, selector=selector,
                                        margin=8, tile_n=256)
    d_ref, i_ref = _exact(rows, qs)
    assert np.array_equal(i_i, i_ref)
    assert np.array_equal(d_i, d_ref)
    assert st["probe_fraction"] == 1.0


@pytest.mark.parametrize("precision,kernel", [
    ("highest", "tiled"), ("bf16x3", "streaming"), ("int8", "streaming"),
    ("bf16x3", "fused"),
])
def test_bitwise_across_pallas_precisions_and_kernels(
        clustered, precision, kernel):
    """End results are selector/precision/kernel-independent: every
    coarse pass only proposes candidates; the f64 refine anchor (and
    the certified fallback) decides."""
    rows, qs = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=2, train_iters=2, seed=0)
    d_i, i_i, st = idx.search_certified(
        qs, selector="pallas", precision=precision, kernel=kernel,
        margin=8, tile_n=256)
    d_ref, i_ref = _exact(rows, qs)
    assert np.array_equal(i_i, i_ref)
    assert np.array_equal(d_i, d_ref)


def test_forced_miss_is_detected_and_repaired(clustered):
    """Adversarial queries BETWEEN clusters at nprobe=1: the residual
    certificate must flag them (detected, never silent), the fallback
    must repair them to bitwise brute force, and the stats must say
    what happened."""
    rows, _ = clustered
    rng = np.random.default_rng(11)
    # midpoints of random cluster pairs: nearest neighbors straddle
    # two lists, so probing one cannot be certified
    cents = train_kmeans(rows, NCLUSTERS, mesh=make_mesh(), iters=4,
                         seed=0).centroids
    pairs = rng.choice(NCLUSTERS, size=(12, 2), replace=True)
    qs = ((cents[pairs[:, 0]] + cents[pairs[:, 1]]) / 2).astype(np.float32)
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=1, train_iters=4, seed=0)
    d_i, i_i, st = idx.search_certified(qs)
    d_ref, i_ref = _exact(rows, qs)
    assert np.array_equal(i_i, i_ref)
    assert np.array_equal(d_i, d_ref)
    assert st["fallback_queries"] > 0, st
    assert st["fallback_rate"] == st["fallback_queries"] / qs.shape[0]
    assert 0.0 <= st["recall_at_k"] <= 1.0


def test_env_switches_consumed(clustered, monkeypatch):
    rows, _ = clustered
    monkeypatch.setenv("KNN_TPU_IVF_NCENTROIDS", "4")
    monkeypatch.setenv("KNN_TPU_IVF_NPROBE", "3")
    monkeypatch.setenv("KNN_TPU_IVF_TRAIN_ITERS", "2")
    monkeypatch.setenv("KNN_TPU_IVF_SEED", "9")
    idx = IVFIndex(rows, mesh=make_mesh(), k=K)
    st = idx.stats()
    assert (st["ncentroids"], st["nprobe"]) == (4, 3)
    assert (st["train_iters"], st["seed"]) == (2, 9)


# -- mutability -------------------------------------------------------------
def test_write_contract_refusals(clustered):
    rows, _ = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   train_iters=2, seed=0)
    extra = rows[:2] + 1.0
    idx.insert(extra, [9000, 9001])
    with pytest.raises(ValueError, match="already live"):
        idx.insert(extra[:1], [9000])
    idx.delete([9000])
    with pytest.raises(ValueError, match="compact"):
        idx.insert(extra[:1], [9000])  # tombstoned id needs compact()
    with pytest.raises(KeyError):
        idx.delete([424242])
    with pytest.raises(MutationBudgetError):
        small = IVFIndex(rows[:8], mesh=make_mesh(), k=K, ncentroids=2,
                         train_iters=1, seed=0)
        small.delete(list(range(4)))  # would leave live < k


def test_mutation_oracle_across_compactions(clustered):
    """The PR-13 oracle, extended: after ANY interleaving of inserts,
    deletes, and re-cluster compactions, certified IVF search is
    bitwise-identical to a fresh exact index of the surviving rows —
    for the counted selector AND the pallas coarse path."""
    rows, qs = clustered
    rng = np.random.default_rng(3)
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=2, train_iters=2, seed=0)
    n0 = rows.shape[0]
    ins1 = rows[:30] + rng.normal(size=(30, DIM)).astype(np.float32)
    idx.insert(ins1, np.arange(n0, n0 + 30))
    idx.delete(np.arange(0, 20))
    rep1 = idx.compact()
    assert rep1["epoch"] == 1
    ins2 = rows[40:55] + rng.normal(size=(15, DIM)).astype(np.float32)
    idx.insert(ins2, np.arange(n0 + 30, n0 + 45))
    idx.delete(np.arange(25, 35))
    rep2 = idx.compact()
    assert rep2["epoch"] == 2
    assert idx.stats()["compactions"] == 2

    # survivors in canonical order: base insertion order then tails
    surv_rows = np.concatenate([rows[20:25], rows[35:], ins1, ins2])
    surv_ids = np.concatenate([
        np.arange(20, 25), np.arange(35, n0), np.arange(n0, n0 + 45)])
    d_ref, p_ref = refine_shared_exact(
        surv_rows, qs, np.arange(surv_rows.shape[0], dtype=np.int64), K)
    i_ref = surv_ids[p_ref]
    for sel in SELECTORS:
        d_i, i_i, _ = idx.search_certified(qs, selector=sel, margin=8,
                                           tile_n=256)
        assert np.array_equal(i_i, i_ref), sel
        assert np.array_equal(d_i, d_ref), sel
    # and a fresh IVF index over the survivors agrees with itself
    fresh = IVFIndex(surv_rows, surv_ids, mesh=make_mesh(), k=K,
                     ncentroids=NCLUSTERS, nprobe=2, train_iters=2,
                     seed=0)
    d_f, i_f, _ = fresh.search_certified(qs)
    assert np.array_equal(i_f, i_ref)
    assert np.array_equal(d_f, d_ref)


def test_concurrent_reads_during_writes(clustered):
    """Snapshot isolation: readers racing writes + a compaction always
    see a consistent corpus (every returned id was live in SOME epoch;
    results equal the oracle of the snapshot they read)."""
    rows, qs = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=2, train_iters=2, seed=0)
    stop = threading.Event()
    errors = []

    def reader():
        while not stop.is_set():
            try:
                d_i, i_i, _ = idx.search_certified(qs[:4])
                assert d_i.shape == (4, K) and (i_i >= 0).all()
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                return

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    n0 = rows.shape[0]
    for b in range(4):
        idx.insert(rows[:5] + np.float32(b + 1),
                   np.arange(n0 + 5 * b, n0 + 5 * (b + 1)))
    idx.compact()
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors


def test_live_mixed_traffic_across_swaps(clustered):
    """The serving bar: loadgen read+write mix on the IVF engine stays
    error-free across >= 2 background re-cluster swaps, and no request
    is lost: every read offered is answered, every write has an
    outcome.  (Counts, not a p99 under a wall-clock bound: a loaded
    test machine decides nothing here.)"""
    from knn_tpu.serving.queue import QueryQueue

    rows, _ = clustered
    rng = np.random.default_rng(13)
    pool = rng.normal(size=(64, DIM)).astype(np.float32)
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=2, train_iters=1, seed=0,
                   compact_tail_rows=6)
    eng = idx.serving_engine(buckets=(8, 16))
    eng.warmup()
    idx.start_compactor()
    spec = loadgen.WorkloadSpec(
        rate_qps=150, duration_s=1.2, seed=13,
        tenants=(
            loadgen.TenantSpec("readers", weight=0.8,
                               batch_sizes=(1, 2, 4)),
            loadgen.TenantSpec("writers", weight=0.2, batch_sizes=(1,),
                               insert_fraction=0.6,
                               delete_fraction=0.3),
        ))
    reqs = loadgen.generate(spec)
    assert any(r.kind == "insert" for r in reqs)
    try:
        with QueryQueue(eng, max_wait_ms=2.0) as qq:
            rep = loadgen.run_workload(qq, reqs, queries=pool)
    finally:
        idx.close()
    swaps = idx.stats()["compactions"]
    assert swaps >= 2, f"only {swaps} compaction swap(s) happened"
    assert rep["writes"]["insert"].get("ok", 0) >= 6
    assert rep["errors"] == 0, rep["outcomes"]
    reads = sum(r.kind == "query" for r in reqs)
    assert rep["offered"] == reads
    assert rep["ok"] == reads, rep["outcomes"]  # no admission control
    assert rep["latency_ms"]["count"] == reads
    assert rep["writes"]["total"] == len(reqs) - reads
    assert rep["records_dropped"] == 0


# -- the ivf artifact block -------------------------------------------------
def _good_block():
    return {
        "ivf_version": IVF_VERSION,
        "ncentroids": 32, "nprobe": 8, "queries": 128, "k": 10,
        "probe_fraction": 0.25, "recall_at_k": 1.0,
        "fallback_rate": 0.0, "bytes_streamed_ratio": 0.25,
        "qps": 1234.5, "selector": "exact",
        "fallback_queries": 0, "certified_queries": 128,
        "genuine_misses": 0, "epoch": 0, "compactions": 0,
    }


def test_ivf_block_validator():
    assert validate_ivf_block(_good_block()) == []
    bad = _good_block()
    del bad["probe_fraction"]
    assert any("probe_fraction" in e for e in validate_ivf_block(bad))
    bad = _good_block()
    bad["ivf_version"] = IVF_VERSION + 1
    assert validate_ivf_block(bad)
    bad = _good_block()
    bad["recall_at_k"] = 1.5
    assert validate_ivf_block(bad)


def test_search_stats_validate_as_block(clustered):
    """The bench emitter builds its block from these stats: the
    live-measured fields must satisfy the cataloged schema ranges."""
    rows, qs = clustered
    idx = IVFIndex(rows, mesh=make_mesh(), k=K, ncentroids=NCLUSTERS,
                   nprobe=2, train_iters=2, seed=0)
    _, _, st = idx.search_certified(qs)
    ist = idx.stats()
    block = {
        "ivf_version": IVF_VERSION,
        "ncentroids": st["ncentroids"], "nprobe": st["nprobe"],
        "queries": st["queries"], "k": st["k"],
        "probe_fraction": st["probe_fraction"],
        "recall_at_k": st["recall_at_k"],
        "fallback_rate": st["fallback_rate"],
        "bytes_streamed_ratio": st["bytes_streamed_ratio"],
        "qps": 100.0, "selector": st["selector"],
        "fallback_queries": st["fallback_queries"],
        "certified_queries": st["certified_queries"],
        "genuine_misses": st["genuine_misses"],
        "epoch": ist["epoch"], "compactions": ist["compactions"],
    }
    assert validate_ivf_block(block) == []
