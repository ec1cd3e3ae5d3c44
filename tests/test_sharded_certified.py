"""Sharded certified-exact path: coarse selector (approx/pallas/exact) on
each db shard, lexicographic merge, float64 refine, distributed count-below
certificate (psum over the db axis), exact fallback — must equal the
float64 oracle on every mesh shape."""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu.models.classifier import knn_predict
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.pipeline import run_job
from knn_tpu.utils.config import JobConfig


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


@pytest.fixture
def data(rng):
    db = rng.normal(size=(1100, 16)).astype(np.float32) * 10
    db[500:550] = db[:50]  # ties across shard boundaries
    queries = rng.normal(size=(37, 16)).astype(np.float32) * 10
    return db, queries


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4), (1, 8)])
@pytest.mark.parametrize("selector", ["approx", "exact"])
def test_sharded_certified_matches_oracle(data, mesh_shape, selector):
    db, queries = data
    ref_d, ref_i = _oracle(db, queries, 7)
    prog = ShardedKNN(db, mesh=make_mesh(*mesh_shape), k=7)
    d, i, stats = prog.search_certified(queries, selector=selector)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    assert stats["certified"] + stats["fallback_queries"] == queries.shape[0]


def test_sharded_certified_pallas_selector(rng):
    # pallas bins need >= k*BIN_W rows per shard: use a bigger db, 2 shards
    db = rng.normal(size=(4 * 128 * 5, 8)).astype(np.float32)
    queries = rng.normal(size=(16, 8)).astype(np.float32)
    ref_d, ref_i = _oracle(db, queries, 4)
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=4)
    d, i, stats = prog.search_certified(queries, selector="pallas")
    np.testing.assert_array_equal(i, ref_i)


def test_predict_certified_matches_exact_predict(data):
    db, queries = data
    labels = (np.arange(db.shape[0]) % 5).astype(np.int32)
    mesh = make_mesh(2, 4)
    prog = ShardedKNN(db, mesh=mesh, k=9, labels=labels, num_classes=5)
    ref = np.asarray(
        knn_predict(jnp.asarray(db), jnp.asarray(labels), jnp.asarray(queries),
                    k=9, num_classes=5)
    )
    got, stats = prog.predict_certified(queries)
    np.testing.assert_array_equal(got, ref)


def test_certified_rejects_non_l2(data):
    db, queries = data
    prog = ShardedKNN(db, mesh=make_mesh(8, 1), k=3, metric="l1")
    with pytest.raises(ValueError, match="l2, cosine and dot"):
        prog.search_certified(queries)


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_pipeline_certified_mode(tmp_path, rng, metric):
    # --mode certified end to end through run_job, both supported
    # metrics (cosine's config gate opened in round 4): labels must
    # match the exact pipeline and the stats invariants must hold
    from knn_tpu.data.datasets import make_blobs, save_labeled_csv, save_unlabeled_csv

    feats, labels = make_blobs(300, 6, 3, cluster_std=0.3, seed=9)
    paths = {
        "train": str(tmp_path / "train.csv"),
        "val": str(tmp_path / "val.csv"),
        "test": str(tmp_path / "test.csv"),
    }
    save_labeled_csv(paths["train"], feats[:200], labels[:200])
    save_labeled_csv(paths["val"], feats[200:250], labels[200:250])
    save_unlabeled_csv(paths["test"], feats[250:])

    def cfg(mode):
        return JobConfig(
            train_file=paths["train"], test_file=paths["test"], val_file=paths["val"],
            output_file=str(tmp_path / f"out_{mode}.csv"), k=5,
            metric=metric, query_shards=4, db_shards=2, mode=mode,
        )

    exact = run_job(cfg("exact"))
    cert = run_job(cfg("certified"))
    np.testing.assert_array_equal(exact.test_labels, cert.test_labels)
    np.testing.assert_array_equal(exact.val_labels, cert.val_labels)

    # --mode certified observability: stats land on the result and in metrics()
    assert exact.certified_stats is None
    assert "certified_stats" not in exact.metrics()
    stats = cert.certified_stats
    assert stats is not None
    n_queries = cert.n_test + cert.n_val
    assert stats["certified"] + stats["fallback_queries"] == n_queries
    assert cert.metrics()["certified_stats"] == stats


def test_config_certified_metric_gate():
    with pytest.raises(ValueError, match="requires the l2 or cosine"):
        JobConfig(mode="certified", metric="l1")
    JobConfig(mode="certified", metric="cosine")  # supported since round 4
    # case is normalized at the config boundary so downstream dispatch
    # (ShardedKNN's cosine placement normalization) can't be bypassed
    assert JobConfig(mode="certified", metric="Cosine").metric == "cosine"
    with pytest.raises(ValueError, match="mode"):
        JobConfig(mode="fast")
    with pytest.raises(ValueError, match="selector"):
        JobConfig(selector="magic")


@pytest.mark.parametrize("batch_size", [16, 37, 64])
def test_sharded_certified_batched_matches_unbatched(data, batch_size):
    # pipelined batching is an execution strategy, not a semantic knob:
    # results must be identical for any batch size, including non-dividing
    # and larger-than-Q sizes
    db, queries = data
    prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=7)
    ref_d, ref_i, _ = prog.search_certified(queries)
    d, i, stats = prog.search_certified(queries, batch_size=batch_size)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    assert stats["certified"] + stats["fallback_queries"] == queries.shape[0]


@pytest.mark.parametrize("ties", [False, True], ids=["clean", "fallbacks"])
@pytest.mark.parametrize("batch_size", [16, 37, 64])
def test_pallas_batched_matches_unbatched(rng, batch_size, ties):
    # the one-pass selector's schedule (dispatch every sub-batch, then
    # fetch and repair each in order) is an execution strategy too:
    # three sub-batches with a ragged tail, two, and one padded batch
    # answer bitwise like one call — also when rows fall back, which
    # the repair then re-selects over the whole call's flagged queries
    db = rng.normal(size=(1500, 12)).astype(np.float32) * 10
    queries = rng.normal(size=(40, 12)).astype(np.float32) * 10
    if ties:
        # an exact-tie run WIDER than the rank-analysis window: no
        # provable top-k boundary, so the device flags the query
        # unresolved and the widened re-select must repair it
        db[100:125] = db[99]
        queries[1] = db[99] + 1e-4
    _, ref_i = _oracle(db, queries, 5)
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=5)
    kw = dict(selector="pallas", margin=8, tile_n=256)
    d0, i0, s0 = prog.search_certified(queries, **kw)
    d1, i1, s1 = prog.search_certified(queries, batch_size=batch_size, **kw)
    if ties:
        assert s0["fallback_queries"] > 0  # the repair path really ran
    np.testing.assert_array_equal(i0, ref_i)
    np.testing.assert_array_equal(i1, i0)
    np.testing.assert_array_equal(d1, d0)
    for key in ("fallback_queries", "certified", "rank_corrected_queries",
                "select_width"):
        assert s1[key] == s0[key], key
    # the knobs too, but for how the call was cut, which is the point
    cut = ("sub_batch", "batches")
    assert ({k: v for k, v in s1["pallas_knobs"].items() if k not in cut}
            == {k: v for k, v in s0["pallas_knobs"].items() if k not in cut})
    assert (s0["sub_batch"], s0["batches"]) == ("small", 1)
    assert (s1["sub_batch"], s1["batches"]) == (
        "explicit", -(-queries.shape[0] // batch_size))
    assert s1["certified"] + s1["fallback_queries"] == queries.shape[0]


@pytest.mark.parametrize("kernel", ["streaming", "fused"])
def test_pallas_batched_full_width_kernels_match_tiled(rng, kernel):
    # the one-launch kernels under sub-batching on a db-sharded mesh:
    # bitwise the tiled kernel's one-batch answer
    db = rng.normal(size=(900, 10)).astype(np.float32) * 20
    queries = rng.normal(size=(24, 10)).astype(np.float32) * 20
    prog = ShardedKNN(db, mesh=make_mesh(1, 2), k=4)
    kw = dict(selector="pallas", margin=6, tile_n=256)
    d0, i0, _ = prog.search_certified(queries, **kw)
    d1, i1, s1 = prog.search_certified(queries, batch_size=8, kernel=kernel,
                                       **kw)
    np.testing.assert_array_equal(d1, d0)
    np.testing.assert_array_equal(i1, i0)
    assert s1["pallas_knobs"]["kernel"] == kernel


def test_pallas_certified_beats_f32_cancellation(rng):
    # at tiny distances vs large norms the expanded-square f32 "exact"
    # path loses ~all its bits (catastrophic cancellation); the pallas
    # path's direct-difference rank + tie runs + repair must still match
    # the FLOAT64 oracle (which the f32 exact path here cannot)
    from knn_tpu.ops.certified import host_exact_knn

    db = rng.normal(size=(3000, 10)).astype(np.float32) * 10
    db[200:260] = db[:60]          # duplicate ties
    db[500:540] = db[0] + 0.0001   # 40-way pileup nearer than db[0] itself
    queries = np.vstack([
        db[0][None] + 0.01,
        rng.normal(size=(15, 10)).astype(np.float32) * 10,
    ]).astype(np.float32)
    od, oi = host_exact_knn(db, queries, 12)
    for mesh_shape in [(8, 1), (2, 4)]:
        prog = ShardedKNN(db, mesh=make_mesh(*mesh_shape), k=12)
        for wd in (True, False):
            d, i, stats = prog.search_certified(
                queries, selector="pallas", tile_n=256, return_distances=wd
            )
            np.testing.assert_array_equal(i, oi)
            assert (d is None) == (not wd)


@pytest.mark.parametrize("selector", ["approx", "exact", "pallas"])
def test_return_distances_false_uniform_contract(data, selector):
    # (None, idx, stats) for EVERY selector — not a pallas-only behavior
    db, queries = data
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=7)
    ref_d, ref_i = _oracle(db, queries, 7)
    kwargs = {"tile_n": 256} if selector == "pallas" else {}
    d, i, stats = prog.search_certified(
        queries, selector=selector, return_distances=False, **kwargs
    )
    assert d is None
    np.testing.assert_array_equal(i, ref_i)


def test_adaptive_gap_threshold_kills_false_alarms(rng):
    # a db row sits WITHIN the count pass's f32 tolerance of d_k: the old
    # fixed threshold (d_k + tol) counted it and false-alarmed into the
    # exact fallback; the adaptive form finds the first >2*tol gap at
    # rank j >= k inside the margin window and counts against its
    # midpoint instead — certified, zero fallbacks, result still exact
    from knn_tpu.ops.certified import certification_tolerance

    dim, k = 4, 3
    base = 3000.0
    db = rng.normal(size=(512, dim)).astype(np.float32)
    db = db / np.linalg.norm(db, axis=-1, keepdims=True)
    radii = np.linspace(base, base * 1.4, 512).astype(np.float32)
    db = db * radii[:, None]
    queries = np.zeros((5, dim), dtype=np.float32)
    tol = certification_tolerance(queries, db)[0]
    assert tol > 1.0  # the scale makes the f32 slack material
    # plant ranks 0..k: the (k+1)-th neighbor within tol/4 of the k-th,
    # then a clean > 2*tol gap before everything else
    r_k = base
    tight_rows = np.eye(dim, dtype=np.float32)[:1] * np.sqrt(
        np.array([r_k**2 - 3, r_k**2 - 2, r_k**2 - 1, r_k**2,
                  r_k**2 + tol / 4], dtype=np.float64)
    ).astype(np.float32)[:, None]
    db[:5] = tight_rows
    db[5:] = db[5:] * 1.2  # push the rest past a comfortable gap
    ref_d, ref_i = _oracle(db, queries, k)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=k)
    d, i, stats = prog.search_certified(queries, selector="exact", margin=8)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)
    assert stats["fallback_queries"] == 0


def test_certified_counted_margin_zero(rng):
    # m == k: the adaptive gap search has no window — must degrade to the
    # fixed threshold without indexing past the candidate array
    db = rng.normal(size=(64, 8)).astype(np.float32)
    queries = rng.normal(size=(5, 8)).astype(np.float32)
    ref_d, ref_i = _oracle(db, queries, 4)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=4)
    d, i, stats = prog.search_certified(queries, selector="exact", margin=0)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)


def _cosine_oracle(db, queries, k):
    """float64 cosine-distance lexicographic top-k over the float32 rows
    and queries AS GIVEN, 1 - q.t / (|q| |t|) with nothing rounded
    before the product: the contract search_certified holds since PR 43
    (before it: the order of the float32 unit rows)."""
    d64, q64 = db.astype(np.float64), queries.astype(np.float64)
    den = (np.linalg.norm(q64, axis=-1)[:, None]
           * np.linalg.norm(d64, axis=-1)[None, :])
    d = 1.0 - np.einsum("qd,nd->qn", q64, d64) / den
    idx = np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d.shape), d),
                     axis=-1)[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


@pytest.mark.parametrize("selector", ["exact", "approx", "pallas"])
def test_certified_cosine_matches_oracle(rng, selector):
    # VERDICT r4 item: cosine certified search through the LIBRARY path
    # (db normalized at placement, queries at entry, l2 certificate on
    # unit vectors, the host ranking by the rows as given) must match
    # the float64 cosine oracle of the rows as given, with distances
    # returned in 1-similarity units
    db = (rng.normal(size=(900, 24)) * np.linspace(
        0.5, 3.0, 900)[:, None]).astype(np.float32)  # varied row norms
    queries = (rng.normal(size=(17, 24)) * 2).astype(np.float32)
    k = 7
    ref_d, ref_i = _cosine_oracle(db, queries, k)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=k, metric="cosine")
    d, i, stats = prog.search_certified(queries, selector=selector, margin=8)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-5, atol=1e-6)
    assert stats["certified"] + stats["fallback_queries"] == 17


def test_certified_cosine_plain_search_agrees(rng):
    # placement-time normalization must not change plain cosine search
    # (pairwise_cosine re-normalizes idempotently)
    db = (rng.normal(size=(300, 12)) * 5).astype(np.float32)
    queries = rng.normal(size=(9, 12)).astype(np.float32)
    a = ShardedKNN(db, mesh=make_mesh(1, 2), k=5, metric="cosine")
    _, ref_i = _cosine_oracle(db, queries, 5)
    _, ia = a.search(queries)
    np.testing.assert_array_equal(np.asarray(ia), ref_i)


def test_certified_l1_still_rejected(rng):
    db = rng.normal(size=(64, 8)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=3, metric="l1")
    with pytest.raises(ValueError, match="l2, cosine and dot"):
        prog.search_certified(rng.normal(size=(2, 8)).astype(np.float32))




def test_certified_pallas_multitile_multichunk_sharded(rng):
    # the gist-shaped corner: dim > DIM_CHUNK (multi-chunk scratch
    # accumulation) x multiple db tiles per shard x 2 db shards, grouped
    # binning — every structural axis of the kernel at once, vs the
    # float64 oracle
    db = rng.normal(size=(6 * 256 + 40, 200)).astype(np.float32) * 5
    queries = rng.normal(size=(9, 200)).astype(np.float32) * 5
    ref_d, ref_i = _oracle(db, queries, 6)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=6)
    d, i, stats = prog.search_certified(queries, selector="pallas",
                                        tile_n=256, margin=8)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)


#: the exclusion values ``v_w`` (the widen-th float32 score of each flagged
#: query's re-select) that the program BEFORE PR 50 returned on this data,
#: one and four devices alike: it padded every shard's rows to whole
#: ``train_tile``s and scanned equal tiles
_PARENTS_V_W = [393.0, 432.0, 412.0, 259.0]


@pytest.mark.parametrize("mesh_shape", [(1, 1), (1, 4)],
                         ids=["one_device", "four_devices"])
def test_a_ragged_reselect_repairs_a_fallback_batch(monkeypatch, mesh_shape):
    """The repair's exact re-select over rows that are no whole number of
    ``train_tile``s (1,500 = 11 x 128 + 92 on one device, 375 = 2 x 128 +
    119 a shard on four): the last tile is read whole, ending at the last
    row, and the rows it shares with the tile before are masked.  Tie runs
    wider than the rank window, planted at the head, across the overlap's
    two edges and through the last row, flag their queries; small-integer
    values make every float32 score exact, so the widened selection is the
    float64 oracle's to the last pair and its ``v_w`` the parent's."""
    from knn_tpu.ops import certified

    rng = np.random.default_rng(50)
    db = rng.integers(-9, 10, size=(1500, 12)).astype(np.float32)
    queries = rng.integers(-9, 10, size=(40, 12)).astype(np.float32)
    # one device: tiles end at 1,408 (the clamped one starts at 1,372);
    # four: shard 0's at 256 (247), shard 3's at rows 1,381 (1,372)
    for at, (lo, hi) in enumerate([(100, 131), (240, 271), (1390, 1421),
                                   (1469, 1500)], start=1):
        db[lo:hi] = db[lo - 1]
        queries[at] = db[lo - 1]
    seen = []
    real = certified.repair_uncertified

    def spy(*args, select_fn, **kw):
        def recorded(qb, widen):
            fs, fi = select_fn(qb, widen)
            seen.append((np.asarray(fs), np.asarray(fi)))
            return fs, fi
        return real(*args, select_fn=recorded, **kw)

    monkeypatch.setattr(certified, "repair_uncertified", spy)
    prog = ShardedKNN(db, mesh=make_mesh(*mesh_shape), k=5, train_tile=128)
    shard_rows = prog._tp.sharding.shard_shape(prog._tp.shape)[0]
    assert shard_rows % 128 and shard_rows > 128
    d, i, stats = prog.search_certified(queries, selector="pallas", margin=8,
                                        tile_n=256)
    ref_d, ref_i = _oracle(db, queries, 5)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    assert stats["fallback_queries"] == 4
    assert stats.get("host_exact_queries", 0) == 0  # v_w proved them all
    ((fs, fi),) = seen
    widen = fs.shape[1]
    assert widen == 77  # max(2 m, m + 64) at m = 13
    want_d, want_i = _oracle(db, queries[1:5], widen)
    np.testing.assert_array_equal(fi, want_i)
    np.testing.assert_array_equal(fs, want_d.astype(np.float32))
    assert fs[:, -1].tolist() == _PARENTS_V_W
