"""Radius-neighbors search + classifier vs float64 NumPy oracles.

Radii are chosen at the midpoint of the widest inter-distance gap near a
target quantile of the fixture's true distance distribution — nonempty
neighbor sets AND boundary-safe by construction (float32-vs-float64
arithmetic cannot flip membership, the documented ops.radius contract).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from knn_tpu.models.radius import RadiusNeighborsClassifier
from knn_tpu.ops.radius import (
    SENTINEL_IDX,
    count_within,
    radius_search,
    radius_threshold,
)
from knn_tpu.parallel import ShardedKNN, make_mesh


def _oracle_d(db, q, metric):
    db64, q64 = db.astype(np.float64), q.astype(np.float64)
    if metric == "l2":
        return np.sqrt(((db64[None] - q64[:, None]) ** 2).sum(-1))
    if metric == "l1":
        return np.abs(db64[None] - q64[:, None]).sum(-1)
    dn = db64 / np.linalg.norm(db64, axis=-1, keepdims=True)
    qn = q64 / np.linalg.norm(q64, axis=-1, keepdims=True)
    return 1.0 - qn @ dn.T  # cosine


def _safe_radius(d, quantile):
    """A radius at the midpoint of the widest gap between consecutive
    distance values near the target quantile — every point sits at least
    half that gap from the boundary."""
    flat = np.sort(d.ravel())
    target = np.quantile(flat, quantile)
    lo = np.searchsorted(flat, target * 0.9)
    hi = np.searchsorted(flat, target * 1.1)
    seg = flat[max(lo, 1) - 1 : min(hi + 1, flat.size)]
    gaps = np.diff(seg)
    j = int(np.argmax(gaps))
    radius = float((seg[j] + seg[j + 1]) / 2)
    assert gaps[j] > 4e-4 * max(radius, 1.0), "no safe gap in fixture"
    return radius


def _sets(d, radius):
    return [set(np.flatnonzero(row <= radius).tolist()) for row in d]


@pytest.fixture
def data(rng):
    db = (rng.random((400, 12)) * 10).astype(np.float32)
    q = (rng.random((25, 12)) * 10).astype(np.float32)
    return db, q


@pytest.mark.parametrize("metric", ["l2", "l1", "cosine"])
def test_radius_search_matches_oracle(data, metric):
    db, q = data
    d64 = _oracle_d(db, q, metric)
    radius = _safe_radius(d64, 0.02)
    sets = _sets(d64, radius)
    assert sum(len(s) for s in sets) > 25  # fixture is non-vacuous
    M = max(len(s) for s in sets) + 3
    d, i, counts = radius_search(q, db, radius, max_neighbors=M,
                                 metric=metric, train_tile=128)
    d, i, counts = np.asarray(d), np.asarray(i), np.asarray(counts)
    for qi, want in enumerate(sets):
        got = set(i[qi][i[qi] != SENTINEL_IDX].tolist())
        assert got == want, (metric, qi)
        assert counts[qi] == len(want)
        # in-radius entries form an ascending-distance prefix
        row = d[qi]
        finite = row[np.isfinite(row)]
        assert (np.diff(finite) >= 0).all()
        assert np.isinf(row[len(finite):]).all()


def test_radius_truncation_is_reported(data):
    db, q = data
    d64 = _oracle_d(db, q, "l2")
    radius = _safe_radius(d64, 0.10)  # dense sets
    sets = _sets(d64, radius)
    sizes = sorted(len(s) for s in sets)
    M = max(2, sizes[len(sizes) // 2])  # truncates the densest ~half
    assert sizes[-1] > M  # the fixture genuinely truncates somewhere
    d, i, counts = radius_search(q, db, radius, max_neighbors=M, metric="l2")
    counts = np.asarray(counts)
    # counts stay EXACT even when the result is truncated
    assert [int(c) for c in counts] == [len(s) for s in sets]
    assert (counts > M).any()
    # truncated rows are full: all M slots in-radius
    for qi in np.flatnonzero(counts > M):
        assert (np.asarray(i[qi]) != SENTINEL_IDX).all()


def test_count_within_per_query_thresholds(data, rng):
    db, q = data
    d64sq = _oracle_d(db, q, "l2") ** 2
    # per-query thresholds: each query gets its own radius, each chosen
    # boundary-safely from ITS OWN distance row
    thr = np.asarray(
        [radius_threshold(_safe_radius(row[None], 0.05), "l2")
         for row in np.sqrt(d64sq)], np.float32)
    counts = np.asarray(count_within(jnp.asarray(db), jnp.asarray(q), thr,
                                     "l2", tile=96))
    want = (d64sq <= thr[:, None].astype(np.float64)).sum(-1)
    np.testing.assert_array_equal(counts, want)


def test_radius_rejects_dot_metric(data):
    db, q = data
    with pytest.raises(ValueError, match="radius semantics"):
        radius_search(q, db, 1.0, max_neighbors=8, metric="dot")
    with pytest.raises(ValueError, match="radius must be"):
        radius_search(q, db, -1.0, max_neighbors=8, metric="l2")


class TestClassifier:
    def _clustered(self, rng):
        centers = rng.normal(size=(3, 8)).astype(np.float32) * 12
        y = (np.arange(240) % 3).astype(np.int32)
        X = centers[y] + rng.normal(size=(240, 8)).astype(np.float32)
        return X, y, centers

    def test_predict_matches_knn_within_radius(self, rng):
        X, y, centers = self._clustered(rng)
        q = centers[np.arange(30) % 3] + rng.normal(
            size=(30, 8)).astype(np.float32) * 0.5
        clf = RadiusNeighborsClassifier(
            8.0, max_neighbors=240, metric="l2").fit(X, y)
        pred = np.asarray(clf.predict(q))
        assert (pred == (np.arange(30) % 3)).all()
        assert clf.score(q, np.arange(30) % 3) == 1.0

    def test_outlier_raises_then_labels(self, rng):
        X, y, centers = self._clustered(rng)
        far = np.full((2, 8), 1e4, np.float32)
        clf = RadiusNeighborsClassifier(
            8.0, max_neighbors=240, metric="l2").fit(X, y)
        with pytest.raises(ValueError, match="no neighbors within"):
            clf.predict(far)
        clf2 = RadiusNeighborsClassifier(
            8.0, max_neighbors=240, metric="l2", outlier_label=7).fit(X, y)
        assert (np.asarray(clf2.predict(far)) == 7).all()

    def test_strict_truncation_raises_then_votes_nearest(self, rng):
        X, y, _ = self._clustered(rng)
        q = X[:4]
        clf = RadiusNeighborsClassifier(
            50.0, max_neighbors=16, metric="l2").fit(X, y)  # radius >> data
        with pytest.raises(ValueError, match="more than max_neighbors"):
            clf.predict(q)
        loose = RadiusNeighborsClassifier(
            50.0, max_neighbors=16, metric="l2", strict=False).fit(X, y)
        # nearest-16 vote == plain 16-NN vote here (all within radius)
        from knn_tpu.models.classifier import KNNClassifier

        knn = KNNClassifier(k=16, metric="l2").fit(X, y)
        np.testing.assert_array_equal(
            np.asarray(loose.predict(q)), np.asarray(knn.predict(q)))

    def test_vote_tie_break_matches_reference_semantics(self):
        # all-equidistant duplicates: label 1 reaches the tied max first
        # in (distance, index) order — the knn_mpi.cpp:324-336 rule
        X = np.zeros((6, 4), np.float32)
        y = np.array([2, 1, 1, 2, 0, 0], np.int32)
        clf = RadiusNeighborsClassifier(
            1.0, max_neighbors=6, metric="l2").fit(X, y)
        assert int(np.asarray(clf.predict(np.zeros((1, 4), np.float32)))[0]) == 1


class TestRegressor:
    def test_uniform_matches_oracle(self, rng):
        db = (rng.random((200, 10)) * 10).astype(np.float32)
        yv = rng.normal(size=200).astype(np.float32)
        q = (rng.random((15, 10)) * 10).astype(np.float32)
        d64 = _oracle_d(db, q, "l2")
        radius = _safe_radius(d64, 0.08)
        sets = _sets(d64, radius)
        assert all(sets), "fixture: every query needs >= 1 neighbor"
        from knn_tpu.models.radius import RadiusNeighborsRegressor

        reg = RadiusNeighborsRegressor(
            radius, max_neighbors=max(len(s) for s in sets) + 2).fit(db, yv)
        pred = np.asarray(reg.predict(q))
        want = np.array([yv[sorted(s)].astype(np.float64).mean()
                         for s in sets])
        np.testing.assert_allclose(pred, want, rtol=1e-5)
        assert reg.score(q, want) > 0.999999

    def test_distance_weights_and_outliers(self, rng):
        db = (rng.random((200, 10)) * 10).astype(np.float32)
        yv = rng.normal(size=200).astype(np.float32)
        q = (rng.random((10, 10)) * 10).astype(np.float32)
        d64 = _oracle_d(db, q, "l2")
        radius = _safe_radius(d64, 0.08)
        sets = _sets(d64, radius)
        from knn_tpu.models.radius import RadiusNeighborsRegressor

        reg = RadiusNeighborsRegressor(
            radius, max_neighbors=max(len(s) for s in sets) + 2,
            weights="distance").fit(db, yv)
        pred = np.asarray(reg.predict(q))
        for qi, s in enumerate(sets):
            idxs = sorted(s)
            dd = d64[qi, idxs]
            w = 1.0 / np.maximum(dd, 1e-12)
            want = (w * yv[idxs].astype(np.float64)).sum() / w.sum()
            np.testing.assert_allclose(pred[qi], want, rtol=1e-4)
        # outliers: raise by default, fill when outlier_value given
        far = np.full((2, 10), 1e4, np.float32)
        with pytest.raises(ValueError, match="no neighbors"):
            reg.predict(far)
        reg2 = RadiusNeighborsRegressor(
            radius, max_neighbors=64, outlier_value=-3.5).fit(db, yv)
        assert (np.asarray(reg2.predict(far)) == np.float32(-3.5)).all()


def test_failed_fit_leaves_no_inferred_state(rng):
    # a shape-mismatched fit must NOT poison num_classes: the next
    # (correct) fit would silently one-hot with too few bins
    X = (rng.random((20, 4)) * 10).astype(np.float32)
    clf = RadiusNeighborsClassifier(5.0, max_neighbors=8)
    with pytest.raises(ValueError, match="bad shapes"):
        clf.fit(X, np.array([0, 1, 2], np.int32))
    assert clf.num_classes is None
    clf.fit(X, (np.arange(20) % 10).astype(np.int32))
    assert clf.num_classes == 10


def test_regressor_score_sklearn_conventions(rng):
    from knn_tpu.models.radius import RadiusNeighborsRegressor

    X = (rng.random((30, 4)) * 10).astype(np.float32)
    # constant targets predicted exactly -> R^2 = 1.0 (sklearn), not 0.0
    reg = RadiusNeighborsRegressor(1e3, max_neighbors=30).fit(
        X, np.ones(30, np.float32))
    assert reg.score(X[:5], np.ones(5)) == 1.0
    # multi-output: per-output R^2 averaged uniformly — an output with
    # huge variance must not drown a poorly-predicted small one
    y2 = np.stack([np.ones(30), np.arange(30, dtype=np.float64) * 100],
                  axis=1).astype(np.float32)
    reg2 = RadiusNeighborsRegressor(1e3, max_neighbors=30).fit(X, y2)
    s = reg2.score(X[:6], np.stack(
        [np.zeros(6), np.asarray(reg2.predict(X[:6]))[:, 1]], axis=1))
    # output 0: constant truth (0) never predicted (pred=1) -> 0.0;
    # output 1: exact -> 1.0; uniform average = 0.5
    assert s == 0.5, s


def test_sharded_radius_matches_single_device(data):
    db, q = data
    d64 = _oracle_d(db, q, "l2")
    radius = _safe_radius(d64, 0.02)
    M = max(len(s) for s in _sets(d64, radius)) + 3
    ref_d, ref_i, ref_c = radius_search(q, db, radius, max_neighbors=M,
                                        metric="l2")
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=5)
    d, i, c = prog.radius_search(q, radius, max_neighbors=M)
    # counts and per-row MEMBERSHIP are exact; positional order can swap
    # for near-tied rows whose f32 values differ by an ulp between the
    # two program structures (each program is internally lexicographic
    # over ITS OWN values), and values agree to f32 ulps only
    np.testing.assert_array_equal(c, np.asarray(ref_c))
    ref_i = np.asarray(ref_i)
    for qi in range(q.shape[0]):
        assert (set(i[qi][i[qi] >= 0].tolist())
                == set(ref_i[qi][ref_i[qi] >= 0].tolist())), qi
    ref_d = np.asarray(ref_d)
    np.testing.assert_array_equal(np.isinf(d), np.isinf(ref_d))
    np.testing.assert_allclose(d[np.isfinite(d)], ref_d[np.isfinite(ref_d)],
                               rtol=1e-5)


def test_sharded_radius_guards(data):
    db, q = data
    # bf16 placements are refused: the bf16-ranked mask vs f32 count
    # would widen the boundary band ~2000x
    prog16 = ShardedKNN(db, mesh=make_mesh(8, 1), k=5,
                        compute_dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="float32 placement"):
        prog16.radius_search(q, 5.0, max_neighbors=8)
    # a max_neighbors wider than the db shard must RAISE, never silently
    # narrow (counts > M truncation detection would misread a clamped
    # result as complete)
    prog = ShardedKNN(db, mesh=make_mesh(1, 8), k=5)  # 50-row shards
    with pytest.raises(ValueError, match="exceeds db shard size"):
        prog.radius_search(q, 5.0, max_neighbors=128)


def test_sharded_radius_cosine(data):
    db, q = data
    d64 = _oracle_d(db, q, "cosine")
    radius = _safe_radius(d64, 0.02)
    sets = _sets(d64, radius)
    assert sum(len(s) for s in sets) > 25
    M = max(len(s) for s in sets) + 3
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=5, metric="cosine")
    d, i, c = prog.radius_search(q, radius, max_neighbors=M)
    for qi, want in enumerate(sets):
        got = set(i[qi][i[qi] != SENTINEL_IDX].tolist())
        assert got == want, qi
        assert c[qi] == len(want)


def test_cityblock_alias_matches_l1(data):
    """'cityblock' passes radius_threshold's eager validation
    but used to die inside the search dispatch — the alias must now run,
    and run IDENTICALLY to 'l1' (same threshold, same dispatch)."""
    db, q = data
    d_l1, i_l1, c_l1 = radius_search(q, db, 9.0, max_neighbors=16,
                                     metric="l1")
    d_cb, i_cb, c_cb = radius_search(q, db, 9.0, max_neighbors=16,
                                     metric="cityblock")
    np.testing.assert_array_equal(np.asarray(d_l1), np.asarray(d_cb))
    np.testing.assert_array_equal(np.asarray(i_l1), np.asarray(i_cb))
    np.testing.assert_array_equal(np.asarray(c_l1), np.asarray(c_cb))
    # count_within dispatches the alias too
    np.testing.assert_array_equal(
        np.asarray(count_within(db, q, 9.0, "cityblock")),
        np.asarray(count_within(db, q, 9.0, "l1")),
    )


def test_sharded_radius_l1_falls_back_to_single_device(data):
    """The docstring's promised L1 fallback exists: a host-array-built
    ShardedKNN routes L1 radius queries through the single-device
    ops.radius path (one pairwise computation for mask AND count), with
    results identical to calling it directly."""
    db, q = data
    d64 = _oracle_d(db, q, "l1")
    radius = _safe_radius(d64, 0.02)
    M = max(len(s) for s in _sets(d64, radius)) + 3
    ref = radius_search(q, db, radius, max_neighbors=M, metric="l1")
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=5, metric="l1")
    got = prog.radius_search(q, radius, max_neighbors=M)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        prog.radius_search(q, radius, max_neighbors=0)
