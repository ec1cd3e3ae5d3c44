"""ops.pq: the product-quantized coarse arm and — the load-bearing part
— its PROVABLE per-subspace error bound ε.  Same proof-obligation
discipline as tests/test_quantize.py: random draws across dims, subspace
widths, codebook sizes, and magnitudes must keep ε >= the observed
|exact score − PQ reconstruction score| for EVERY (query, row) pair, in
exact f64 reconstruction AND under the f32 LUT arithmetic the kernel
actually executes.  The e2e tests pin the certified contract: indices
bitwise-equal to the float64 oracle across tiled/streaming, forced
misses detected and repaired (never silent), and the fused kernel
refusing the pq arm loudly."""

import numpy as np
import pytest

from knn_tpu.ops import pq as pqm


@pytest.fixture(scope="module")
def mesh():
    from knn_tpu.parallel.mesh import make_mesh

    return make_mesh(1, 1)


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None]
          - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


def _observed_errors(q, pq, original, *, f32_arith=False):
    """[Q] max-over-db observed |kernel-space exact score − PQ
    reconstruction score| per query (kernel space: ||t||² − 2 q·t).
    ``f32_arith`` scores through the per-query LUT route in f32 ops —
    the arithmetic the kernel actually runs — to stress the bound's
    f32-slack term too."""
    q64 = np.asarray(q, np.float64)
    t64 = original.astype(np.float64)
    s_true = (t64 ** 2).sum(-1)[None, :] - 2.0 * (q64 @ t64.T)
    if f32_arith:
        lut = pqm.build_luts(q, pq.codebooks, pq.dsub)  # f32
        m, c = pq.nsub, pq.ncodes
        gathered = np.stack(
            [lut[:, s * c + pq.codes[:, s].astype(np.int64)]
             for s in range(m)], axis=0)
        qt = gathered.astype(np.float32).sum(0)  # [Q, N] f32 sum
        s_hat = (np.float32(-2.0) * qt).astype(np.float64)
    else:
        that = pqm.reconstruct(pq.codebooks, pq.codes, pq.dim,
                               pq.dsub).astype(np.float64)
        s_hat = (that ** 2).sum(-1)[None, :] - 2.0 * (q64 @ that.T)
    return np.abs(s_true - s_hat).max(-1)


# --- training & geometry --------------------------------------------------
def test_train_pq_deterministic(mesh):
    rng = np.random.default_rng(7)
    rows = (rng.normal(size=(150, 19)) * 10).astype(np.float32)
    a = pqm.train_pq(rows, mesh=mesh, dsub=4, ncodes=16, seed=3)
    b = pqm.train_pq(rows, mesh=mesh, dsub=4, ncodes=16, seed=3)
    np.testing.assert_array_equal(a.codebooks, b.codebooks)
    np.testing.assert_array_equal(a.codes, b.codes)
    # geometry: one uint8 code per subspace, m = ceil(d / dsub)
    assert a.codes.shape == (150, 5) and a.codes.dtype == np.uint8
    assert a.codebooks.shape == (5, 16, 4)
    assert a.nsub == 5 and a.ncodes == 16 and a.dim == 19


def test_train_pq_validates_args(mesh):
    rows = np.zeros((8, 4), np.float32)
    with pytest.raises(ValueError, match="dsub"):
        pqm.train_pq(rows, mesh=mesh, dsub=0)
    with pytest.raises(ValueError, match="ncodes"):
        pqm.train_pq(rows, mesh=mesh, ncodes=1)
    with pytest.raises(ValueError, match="ncodes"):
        pqm.train_pq(rows, mesh=mesh, ncodes=300)


def test_luts_score_the_reconstruction(mesh):
    # the LUT gather must equal q·t̂ − ||t̂||²/2 against the decoded rows
    rng = np.random.default_rng(11)
    rows = (rng.normal(size=(90, 12)) * 5).astype(np.float32)
    q = (rng.normal(size=(4, 12)) * 5).astype(np.float32)
    pq = pqm.train_pq(rows, mesh=mesh, dsub=3, ncodes=8)
    lut = pqm.build_luts(q, pq.codebooks, pq.dsub)
    m, c = pq.nsub, pq.ncodes
    qt = sum(lut[:, s * c + pq.codes[:, s].astype(np.int64)]
             for s in range(m))
    that = pqm.reconstruct(pq.codebooks, pq.codes, pq.dim, pq.dsub)
    want = (q.astype(np.float64) @ that.astype(np.float64).T
            - 0.5 * (that.astype(np.float64) ** 2).sum(-1)[None])
    np.testing.assert_allclose(qt, want, rtol=1e-4, atol=1e-4)


# --- the bound ------------------------------------------------------------
def test_pq_bound_dominates_observed_error_property(mesh):
    """ε must dominate the observed kernel-space score error for every
    (query, row) pair — across dims, subspace widths, codebook sizes,
    and magnitudes, in f64 reconstruction and f32 LUT arithmetic."""
    rng = np.random.default_rng(20260806)
    scales = (1.0, 100.0, 1e-3)
    for trial in range(9):
        dim = int(rng.choice([6, 17, 40]))
        dsub = int(rng.choice([2, 4, 7]))
        ncodes = int(rng.choice([4, 16, 64]))
        mag = scales[trial % len(scales)]
        rows = (rng.normal(size=(130, dim)) * mag).astype(np.float32)
        q = (rng.normal(size=(5, dim)) * mag).astype(np.float32)
        pq = pqm.train_pq(rows, mesh=mesh, dsub=dsub, ncodes=ncodes,
                          iters=3, seed=trial)
        eps = pqm.score_error_bound_pq(q, pq.stats)
        for f32_arith in (False, True):
            err = _observed_errors(q, pq, rows, f32_arith=f32_arith)
            assert (eps >= err).all(), (
                f"trial {trial} dim={dim} dsub={dsub} ncodes={ncodes} "
                f"mag={mag} f32={f32_arith}: eps {eps} < observed {err}")


def test_bound_consts_pq_round_up(mesh):
    rng = np.random.default_rng(5)
    rows = (rng.normal(size=(64, 10)) * 3).astype(np.float32)
    pq = pqm.train_pq(rows, mesh=mesh, dsub=4, ncodes=8)
    consts = pqm.bound_consts_pq(pq.stats)
    m = pq.nsub
    assert consts.shape == (m + 2,) and consts.dtype == np.float32
    for j in range(m):
        assert float(consts[j]) >= pq.stats["r_sub"][j]
    assert float(consts[m]) >= pq.stats["norm_err_max"]
    assert float(consts[m + 1]) >= pq.stats["db_norm_max"]


def test_device_bound_never_undercuts_host(mesh):
    rng = np.random.default_rng(13)
    rows = (rng.normal(size=(80, 14)) * 20).astype(np.float32)
    q = (rng.normal(size=(6, 14)) * 20).astype(np.float32)
    pq = pqm.train_pq(rows, mesh=mesh, dsub=4, ncodes=16)
    host = pqm.score_error_bound_pq(q, pq.stats)
    import jax.numpy as jnp

    consts = jnp.asarray(pqm.bound_consts_pq(pq.stats))
    q_norm, eps = pqm.score_error_bound_pq_device(
        jnp.asarray(q), consts, dsub=pq.dsub)
    eps = np.asarray(eps, np.float64)
    # consts round UP into f32, so the device ε can only widen (modulo
    # f32 evaluation noise)
    assert (eps >= host * (1 - 1e-5)).all()
    np.testing.assert_allclose(np.asarray(q_norm),
                               (q.astype(np.float64) ** 2).sum(-1),
                               rtol=1e-5)


def test_encode_pq_matches_training_assign(mesh):
    rng = np.random.default_rng(17)
    rows = (rng.normal(size=(110, 9)) * 4).astype(np.float32)
    pq = pqm.train_pq(rows, mesh=mesh, dsub=3, ncodes=8)
    again = pqm.encode_pq(rows, pq.codebooks, mesh=mesh, dsub=pq.dsub)
    np.testing.assert_array_equal(again, pq.codes)


# --- certified end-to-end -------------------------------------------------
def test_pq_certified_matches_oracle_across_kernels(mesh, monkeypatch):
    monkeypatch.setenv("KNN_TPU_PQ_DSUB", "4")
    monkeypatch.setenv("KNN_TPU_PQ_NCODES", "32")
    from knn_tpu.parallel.sharded import ShardedKNN

    rng = np.random.default_rng(0)
    n, d, k = 900, 24, 7
    train = (rng.normal(size=(n, d)) * 10).astype(np.float32)
    queries = (rng.normal(size=(16, d)) * 10).astype(np.float32)
    ref_d, ref_i = _oracle(train, queries, k)
    knn = ShardedKNN(train, k=k, mesh=mesh)
    out = {}
    for kern in ("tiled", "streaming"):
        dd, ii, st = knn.search_certified(
            queries, selector="pallas", precision="pq", kernel=kern)
        out[kern] = (np.asarray(dd), np.asarray(ii))
        # the certified contract: indices exactly the oracle's; distance
        # VALUES are f32-direct unless a query escalated to f64 refine
        np.testing.assert_array_equal(out[kern][1], ref_i)
        np.testing.assert_allclose(out[kern][0], ref_d, rtol=5e-5)
        assert st["certified"] + st["fallback_queries"] == 16
    # the two kernels agree BITWISE, distances and indices both
    np.testing.assert_array_equal(out["tiled"][0], out["streaming"][0])
    np.testing.assert_array_equal(out["tiled"][1], out["streaming"][1])


def test_pq_forced_miss_is_detected_and_repaired(monkeypatch):
    """Cram the entire true top-k into ONE kernel bin (lane 7 of ten
    column groups of one tile): the kernel keeps only the bin's top 2,
    so the certificate MUST flag the loss and the fallback must still return
    the float64 oracle's answer — a pq miss is repaired, never
    silent."""
    monkeypatch.setenv("KNN_TPU_PQ_NCODES", "32")
    from knn_tpu.ops.pallas_knn import BIN_W, knn_search_pallas

    rng = np.random.default_rng(2)
    dim, k = 12, 10
    tile_n = 12 * BIN_W
    db = (rng.normal(size=(tile_n, dim)) * 50).astype(np.float32)
    query = rng.normal(size=(1, dim)).astype(np.float32)
    hot = [7 + BIN_W * g for g in range(k)]
    for j, r in enumerate(hot):
        db[r] = query[0] + (j + 1) * 1e-3
    ref_d, ref_i = _oracle(db, query, k)
    d, i, stats = knn_search_pallas(query, db, k, tile_n=tile_n,
                                    margin=4, precision="pq")
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)
    assert stats["fallback_queries"] >= 1
    assert stats["fallback_genuine_misses"] >= 1


def test_pq_fused_refuses_loudly(mesh, monkeypatch):
    monkeypatch.setenv("KNN_TPU_PQ_NCODES", "8")
    from knn_tpu.parallel.sharded import ShardedKNN

    rng = np.random.default_rng(3)
    train = (rng.normal(size=(300, 16)) * 5).astype(np.float32)
    queries = rng.normal(size=(4, 16)).astype(np.float32)
    knn = ShardedKNN(train, k=3, mesh=mesh)
    with pytest.raises(ValueError, match="pq"):
        knn.search_certified(queries, selector="pallas",
                             precision="pq", kernel="fused")


# --- the pq artifact block ------------------------------------------------
def test_pq_artifact_block_schema_and_shim():
    from knn_tpu.ops.pq_artifact import (PQ_REQUIRED, PQ_VERSION,
                                         validate_pq_block)

    assert PQ_REQUIRED == ("pq_version", "dsub", "ncodes", "nsub",
                           "lut_bytes", "bound_max", "queries")
    good = {"pq_version": PQ_VERSION, "dsub": 4, "ncodes": 256,
            "nsub": 32, "lut_bytes": 32 * 256 * 4 * 16,
            "bound_max": 1.5, "queries": 16}
    assert validate_pq_block(good) == []
    # null bound_max is an honest degraded value, still valid
    assert validate_pq_block(dict(good, bound_max=None)) == []
    bad = dict(good)
    del bad["nsub"]
    assert any("nsub" in e for e in validate_pq_block(bad))
    assert any("pq_version" in e for e in validate_pq_block(
        dict(good, pq_version=PQ_VERSION + 1)))
    # a block that recorded its own failure is exempt
    assert validate_pq_block({"error": "boom"}) == []
