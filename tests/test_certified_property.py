"""Property-based exactness: for ANY database/query/k/margin drawn by
hypothesis, every certified selector must reproduce the float64 oracle's
lexicographic top-k bit-for-bit.  This is the suite's randomized sweep of
the shapes the hand-written fixtures don't enumerate — tie pileups,
degenerate margins, k=1, n barely above k, non-multiple-of-bin sizes.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import HealthCheck, given, settings, strategies as st  # noqa: E402

import oracles  # noqa: E402 — tests/oracles.py: THE oracle semantics

from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402


def _oracle(db, queries, k):
    # tests/oracles.py is THE oracle-semantics home; topk_lowindex
    # already returns the (values, indices) pair
    return oracles.topk_lowindex(oracles.sq_l2(queries, db), k)


@settings(max_examples=12, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(8, 700),
    dim=st.integers(2, 24),
    k=st.integers(1, 12),
    margin=st.integers(0, 24),
    dup_frac=st.floats(0.0, 0.4),
    selector=st.sampled_from(["exact", "approx"]),
)
def test_counted_certified_matches_oracle(seed, n, dim, k, margin, dup_frac,
                                          selector):
    rng = np.random.default_rng(seed)
    k = min(k, n)
    db = rng.normal(size=(n, dim)).astype(np.float32) * 10
    n_dup = int(n * dup_frac)
    if n_dup:
        # duplicate rows force exact ties -> the lexicographic tie-break
        # and the strict-count certificate must both hold
        db[rng.choice(n, n_dup, replace=False)] = db[
            rng.choice(n, n_dup, replace=True)]
    queries = rng.normal(size=(7, dim)).astype(np.float32) * 10
    ref_d, ref_i = _oracle(db, queries, k)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=k)
    d, i, stats = prog.search_certified(queries, selector=selector,
                                        margin=margin)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9, atol=1e-9)
    assert stats["certified"] + stats["fallback_queries"] == 7


@settings(max_examples=8, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(
    seed=st.integers(0, 2**31 - 1),
    n_tiles=st.integers(2, 5),
    extra=st.integers(0, 127),
    dim=st.integers(2, 16),
    k=st.integers(1, 9),
    final_select=st.sampled_from(["exact", "approx"]),
    survivors=st.sampled_from([None, 1, 3]),
    grid_order=st.sampled_from(["query_major", "db_major"]),
)
def test_pallas_certified_matches_oracle_property(seed, n_tiles, extra, dim,
                                                  k, final_select, survivors,
                                                  grid_order):
    rng = np.random.default_rng(seed)
    n = n_tiles * 128 + extra
    db = rng.normal(size=(n, dim)).astype(np.float32) * 10
    db[n // 2: n // 2 + 10] = db[:10]  # cross-bin exact ties
    queries = rng.normal(size=(5, dim)).astype(np.float32) * 10
    ref_d, ref_i = _oracle(db, queries, k)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=k)
    d, i, stats = prog.search_certified(
        queries, selector="pallas", margin=8, tile_n=256,
        final_select=final_select, survivors=survivors,
        grid_order=grid_order,
    )
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)
