"""NearestNeighbors estimator + CSR graph exports vs NumPy oracles."""

import numpy as np
import pytest

from knn_tpu.models.neighbors import NearestNeighbors
from knn_tpu.parallel import make_mesh
from tests.test_radius import _oracle_d, _safe_radius, _sets


def _csr_rows(data, indices, indptr):
    return [
        (data[indptr[r]:indptr[r + 1]], indices[indptr[r]:indptr[r + 1]])
        for r in range(len(indptr) - 1)
    ]


@pytest.fixture
def data(rng):
    X = (rng.random((300, 10)) * 10).astype(np.float32)
    Q = (rng.random((20, 10)) * 10).astype(np.float32)
    return X, Q


def test_kneighbors_matches_oracle(data):
    X, Q = data
    nn = NearestNeighbors(k=7).fit(X)
    d, i = nn.kneighbors(Q)
    d64 = _oracle_d(X, Q, "l2")
    want = np.lexsort(
        (np.broadcast_to(np.arange(300), d64.shape), d64), axis=-1)[:, :7]
    np.testing.assert_array_equal(np.asarray(i), want)
    # per-call k override + sqrt values
    ds, _ = nn.kneighbors(Q, 3, return_sqrt=True)
    np.testing.assert_allclose(
        np.asarray(ds), np.sort(d64, axis=-1)[:, :3], rtol=1e-5)


def test_kneighbors_graph_shapes_and_modes(data):
    X, Q = data
    nn = NearestNeighbors(k=4).fit(X)
    data_c, idx_c, ptr_c = nn.kneighbors_graph(Q)
    assert (data_c == 1.0).all() and len(idx_c) == 20 * 4
    assert list(ptr_c[:3]) == [0, 4, 8]
    data_d, idx_d, ptr_d = nn.kneighbors_graph(Q, mode="distance")
    np.testing.assert_array_equal(idx_d, idx_c)
    d, i = nn.kneighbors(Q)
    np.testing.assert_array_equal(data_d, np.asarray(d).ravel())
    # self-graph: each fit row's nearest neighbor is itself, at ~0 —
    # the expanded-square fast path leaves f32 cancellation residue
    # (~2^-14 absolute at this data scale), not exact zeros
    sd, si, sp = nn.kneighbors_graph(mode="distance")
    assert (si.reshape(300, 4)[:, 0] == np.arange(300)).all()
    assert (sd.reshape(300, 4)[:, 0] < 1e-3).all()


@pytest.mark.parametrize("shards", [1, 4])
def test_kneighbors_graph_without_self_is_exact_by_id(data, shards):
    """``include_self=False`` on a placed estimator: the bulk certified
    self-join's graph: no row names itself, an exact copy stays at 0,
    every list is the float64 oracle's over the other rows."""
    import jax

    X, _ = data
    X = X.copy()
    X[200:210] = X[:10]  # exact copies
    nn = NearestNeighbors(k=4, mesh=make_mesh(
        1, shards, devices=jax.devices()[:shards])).fit(X)
    data_d, idx, ptr = nn.kneighbors_graph(mode="distance",
                                           include_self=False)
    idx, data_d = idx.reshape(300, 4), data_d.reshape(300, 4)
    d64 = _oracle_d(X, X, "l2")
    d64[np.arange(300), np.arange(300)] = np.inf
    want = np.lexsort(
        (np.broadcast_to(np.arange(300), d64.shape), d64), axis=-1)[:, :4]
    np.testing.assert_array_equal(idx, want)
    assert not (idx == np.arange(300)[:, None]).any()
    assert (idx[:10, 0] == np.arange(200, 210)).all()
    assert (data_d[:10, 0] == 0).all() and list(ptr[:3]) == [0, 4, 8]
    # the default is the graph it always was: the row itself first
    _, with_self, _ = nn.kneighbors_graph()
    assert (with_self.reshape(300, 4)[:10, 0] == np.arange(10)).all()
    for bad in (dict(Q=X[:5]), dict(k=3)):
        with pytest.raises(ValueError, match="include_self=False"):
            nn.kneighbors_graph(include_self=False, **bad)
    with pytest.raises(ValueError, match="fitted on a mesh"):
        NearestNeighbors(k=4).fit(X).kneighbors_graph(include_self=False)


def test_radius_neighbors_graph_matches_oracle(data):
    X, Q = data
    d64 = _oracle_d(X, Q, "l2")
    radius = _safe_radius(d64, 0.03)
    sets = _sets(d64, radius)
    nn = NearestNeighbors(k=3, radius=radius,
                          max_neighbors=max(len(s) for s in sets) + 2).fit(X)
    data_, indices, indptr = nn.radius_neighbors_graph(Q)
    rows = _csr_rows(data_, indices, indptr)
    assert len(rows) == 20
    for r, (vals, idxs) in enumerate(rows):
        assert set(idxs.tolist()) == sets[r]
        assert (vals == 1.0).all()
    # distance mode carries ascending ranking-space values per row
    dd, di, dp = nn.radius_neighbors_graph(Q, mode="distance")
    np.testing.assert_array_equal(di, indices)
    for vals, _ in _csr_rows(dd, di, dp):
        assert (np.diff(vals) >= 0).all()


def test_radius_graph_strict_truncation(data):
    X, Q = data
    d64 = _oracle_d(X, Q, "l2")
    radius = _safe_radius(d64, 0.25)  # dense
    nn = NearestNeighbors(k=3, radius=radius, max_neighbors=4).fit(X)
    with pytest.raises(ValueError, match="more than max_neighbors"):
        nn.radius_neighbors_graph(Q)
    data_, indices, indptr = nn.radius_neighbors_graph(Q, strict=False)
    assert (np.diff(indptr) <= 4).all()


def test_meshed_matches_single_device(data):
    X, Q = data
    nn1 = NearestNeighbors(k=6).fit(X)
    nn2 = NearestNeighbors(k=6, mesh=make_mesh(4, 2)).fit(X)
    _, i1 = nn1.kneighbors(Q)
    _, i2 = nn2.kneighbors(Q)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    d64 = _oracle_d(X, Q, "l2")
    radius = _safe_radius(d64, 0.03)
    M = max(len(s) for s in _sets(d64, radius)) + 2
    nn1.max_neighbors = nn2.max_neighbors = M
    _, ri1, c1 = nn1.radius_neighbors(Q, radius)
    _, ri2, c2 = nn2.radius_neighbors(Q, radius)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    ri1 = np.asarray(ri1)
    for r in range(20):
        assert (set(ri1[r][ri1[r] >= 0].tolist())
                == set(ri2[r][ri2[r] >= 0].tolist()))


def test_errors(data):
    X, Q = data
    nn = NearestNeighbors(k=5)
    with pytest.raises(RuntimeError, match="fit"):
        nn.kneighbors(Q)
    nn.fit(X)
    with pytest.raises(ValueError, match="no radius"):
        nn.radius_neighbors(Q)
    with pytest.raises(ValueError, match="unknown mode"):
        nn.kneighbors_graph(Q, mode="nope")
    with pytest.raises(ValueError, match="queries"):
        nn.kneighbors(Q[:, :4])
