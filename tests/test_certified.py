"""Certified-exact KNN tests: the pipeline must equal the float64 oracle
regardless of how bad the coarse pass is — certification + fallback carry
the correctness burden, the coarse pass only carries speed."""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu.ops.certified import (
    certification_tolerance,
    count_below,
    knn_search_certified,
)


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


@pytest.fixture
def data(rng):
    db = rng.normal(size=(600, 24)).astype(np.float32) * 30
    db[300:350] = db[:50]  # exact duplicates: distance ties
    queries = rng.normal(size=(40, 24)).astype(np.float32) * 30
    return db, queries


def test_count_below_matches_numpy(data):
    db, queries = data
    d64 = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    thr = np.quantile(d64, 0.1, axis=-1).astype(np.float32)
    got = np.asarray(count_below(jnp.asarray(db), jnp.asarray(queries), jnp.asarray(thr), tile=100))
    # the documented contract is FLOAT32 expanded-square arithmetic: a
    # row whose true distance sits within the f32 error of the threshold
    # (certification_tolerance, the slack the certificate itself budgets
    # for this pass) may be counted on either side — by this backend's
    # program or any other — and no other row may.  The count is held
    # to the float64 truth within the rows that rounding can flip.
    want64 = (d64 < thr[:, None]).sum(-1)
    tol = certification_tolerance(queries, db)
    flippable = (np.abs(d64 - thr[:, None]) <= tol[:, None]).sum(-1)
    assert (np.abs(got - want64) <= flippable).all(), (
        got - want64, flippable)
    assert flippable.max() <= 3  # the band stays a boundary effect


def test_certified_matches_oracle(data):
    db, queries = data
    ref_d, ref_i = _oracle(db, queries, 10)
    d, i, stats = knn_search_certified(queries, db, 10, tile=128)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    assert stats["fallback_queries"] + stats["certified"] == queries.shape[0]


def test_certified_survives_garbage_candidates(data):
    # worst coarse pass possible: constant junk candidates for every query —
    # certification must flag every query and the fallback must restore
    # the exact result
    db, queries = data

    def garbage(q, d, m):
        return jnp.tile(jnp.arange(m, dtype=jnp.int32), (q.shape[0], 1))

    ref_d, ref_i = _oracle(db, queries, 7)
    d, i, stats = knn_search_certified(queries, db, 7, tile=128, candidate_fn=garbage)
    np.testing.assert_array_equal(i, ref_i)
    assert stats["fallback_queries"] > 0  # the junk was detected


def test_certified_partial_garbage(data):
    # half the queries get their true candidates, half get junk: only the
    # junk half may fall back, and results stay exact for all
    db, queries = data
    _, true_cand = _oracle(db, queries, 12)

    def half_garbage(q, d, m):
        cand = jnp.asarray(true_cand[:, :m])
        junk = jnp.tile(jnp.arange(m, dtype=jnp.int32), (q.shape[0], 1))
        half = q.shape[0] // 2
        mask = (jnp.arange(q.shape[0]) < half)[:, None]
        return jnp.where(mask, junk, cand)

    ref_d, ref_i = _oracle(db, queries, 9)
    d, i, stats = knn_search_certified(queries, db, 9, margin=3, tile=128,
                                       candidate_fn=half_garbage)
    np.testing.assert_array_equal(i, ref_i)
    assert stats["fallback_queries"] >= queries.shape[0] // 2 - 1


def test_certified_ties_at_boundary(rng):
    # duplicates straddling the k boundary: lexicographic rule must hold
    db = np.repeat(rng.normal(size=(20, 6)).astype(np.float32), 3, axis=0)  # 60 rows
    queries = db[::7][:5] + 1e-4
    ref_d, ref_i = _oracle(db, queries, 4)
    d, i, _ = knn_search_certified(queries, db, 4, tile=16)
    np.testing.assert_array_equal(i, ref_i)


def test_certified_k_too_large(data):
    db, queries = data
    with pytest.raises(ValueError, match="k="):
        knn_search_certified(queries, db, db.shape[0] + 1)


def test_host_exact_knn_matches_oracle(data):
    from knn_tpu.ops.certified import host_exact_knn

    db, queries = data
    ref_d, ref_i = _oracle(db, queries, 9)
    d, i = host_exact_knn(db, queries, 9, tile=128, q_chunk=7)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=0, atol=0)


def test_persistent_certificate_failure_goes_host_exact(rng):
    # more identical nearest rows than the repair's widened selection can
    # span: the widen-th selected score ties the k-th distance, so the
    # exclusion-value re-certification keeps failing and the pipeline
    # must drop to the unconditional float64 host scan — still exact,
    # with ties resolved to the lowest indices
    db = rng.normal(size=(400, 8)).astype(np.float32) * 20
    q = rng.normal(size=(6, 8)).astype(np.float32)
    db[50:150] = q[0] + 0.001  # 100 identical rows > widen=69 beside q0
    ref_d, ref_i = _oracle(db, q, 3)
    d, i, stats = knn_search_certified(q, db, 3, tile=128, margin=2)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-12)
    assert stats["fallback_queries"] >= 1
    assert stats.get("host_exact_queries", 0) >= 1


def test_certified_int8_pallas_candidates_stay_exact(data):
    # the int8 Pallas coarse pass plugged into the COUNTED certificate:
    # the count-below pass is coarse-precision-independent (it counts
    # every db row against the f64-refined threshold), so quantization
    # error can only raise the fallback rate — results equal the oracle
    from knn_tpu.ops.certified import pallas_candidate_fn

    db, queries = data
    ref_d, ref_i = _oracle(db, queries, 8)
    d, i, stats = knn_search_certified(
        queries, db, 8, tile=128,
        candidate_fn=pallas_candidate_fn(precision="int8", tile_n=256),
    )
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    assert stats["fallback_queries"] + stats["certified"] == queries.shape[0]
