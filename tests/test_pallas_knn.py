"""Pallas fused kernel tests (interpret mode on CPU).

The kernel emits top-s-per-bin candidates plus per-bin exclusion bounds;
exactness always comes from refine + the bound certificate + fallback.
These tests pin the candidate mechanics (bin geometry, survivors, padding,
dim chunking), the *soundness of the exclusion bound* — the property the
whole one-pass certified path rests on — and the end-to-end certified
result against a float64 oracle.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu.ops.pallas_knn import (
    BIN_W,
    knn_search_pallas,
    local_certified_candidates,
    pallas_knn_candidates,
)


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    idx = np.argsort(d, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


def test_kernel_recovers_two_planted_neighbors_per_bin(rng):
    # plant TWO of the j-th nearest neighbors in bin j: the top-2-per-bin
    # reduction must recover ALL of them (the round-2 kernel kept one per
    # bin and lost the second — the dominant fallback cause at k=100)
    n_bins, dim = 4, 16
    tile_n = n_bins * BIN_W
    db = rng.normal(size=(tile_n, dim)).astype(np.float32) * 100
    query = rng.normal(size=(1, dim)).astype(np.float32)
    planted = []
    for b in range(n_bins):
        lo, hi = rng.choice(BIN_W, size=2, replace=False)
        for j, off in enumerate((lo, hi)):
            idx = b * BIN_W + int(off)
            db[idx] = query[0] + (2 * b + j + 1) * 1e-3
            planted.append(idx)
    cand = np.asarray(
        pallas_knn_candidates(
            jnp.asarray(query), jnp.asarray(db), 2 * n_bins, tile_n=tile_n
        )
    )
    np.testing.assert_array_equal(np.sort(cand[0]), np.sort(planted))


def test_kernel_masks_padding_rows(rng):
    # db not a multiple of tile_n: PAD_VAL rows score astronomically far
    # from an origin-query and must never surface as candidates
    db = (rng.normal(size=(3 * BIN_W + 17, 8)).astype(np.float32) + 5.0) * 10
    query = np.zeros((1, 8), dtype=np.float32)
    cand = np.asarray(
        pallas_knn_candidates(jnp.asarray(query), jnp.asarray(db), 8, tile_n=BIN_W)
    )
    assert (cand < db.shape[0]).all()


def test_dim_chunking_matches_unchunked_scores(rng):
    # dim=300 spans 3 chunks (pad to 384); candidate sets must match the
    # oracle's top-k exactly on well-separated data
    db = rng.normal(size=(2 * BIN_W, 300)).astype(np.float32)
    queries = rng.normal(size=(9, 300)).astype(np.float32)
    _, true_idx = _oracle(db, queries, 3)
    cand = np.asarray(
        pallas_knn_candidates(jnp.asarray(queries), jnp.asarray(db), 16,
                              tile_n=2 * BIN_W)
    )
    for c, t in zip(cand, true_idx):
        assert set(t.tolist()) <= set(c.tolist())


def test_db_major_grid_bitwise_equal_query_major(rng):
    # the grid-order change touches ONLY iteration order: every output
    # (candidates, indices, bounds) must be bitwise-identical, across
    # single- and multi-chunk dims and uneven tile counts
    from knn_tpu.ops.pallas_knn import _bin_candidates

    for dim in (24, 300):
        db = rng.normal(size=(3 * BIN_W + 40, dim)).astype(np.float32) * 10
        queries = rng.normal(size=(11, dim)).astype(np.float32) * 10
        outs = {}
        for go in ("query_major", "db_major"):
            outs[go] = _bin_candidates(
                jnp.asarray(queries), jnp.asarray(db), block_q=8,
                tile_n=2 * BIN_W, survivors=2,
                precision="bf16x3", interpret=True, grid_order=go)
        for a, b in zip(outs["query_major"], outs["db_major"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("precision", ["highest", "bf16x3", "bf16x3f",
                                       "int8"])
@pytest.mark.parametrize("kernel,grid_order", [
    ("tiled", "query_major"), ("streaming", "query_major"),
    ("tiled", "db_major"),
])
def test_exclusion_bound_is_sound(rng, precision, kernel, grid_order):
    # THE property the one-pass certificate rests on: every db point
    # outside the candidate set must have kernel-space score >= lb
    # (within the precision mode's tolerance), and the returned d32 must
    # be the candidates' true distances to f32 accuracy
    db = rng.normal(size=(5 * BIN_W + 60, 24)).astype(np.float32) * 10
    queries = rng.normal(size=(7, 24)).astype(np.float32) * 10
    m = 13
    d32, idx, lb = local_certified_candidates(
        jnp.asarray(queries), jnp.asarray(db), m=m, block_q=8,
        tile_n=2 * BIN_W, precision=precision, interpret=True,
        kernel=kernel, grid_order=grid_order,
    )
    d32 = np.asarray(d32)[:7]
    idx, lb = np.asarray(idx)[:7], np.asarray(lb)[:7]
    q64, db64 = queries.astype(np.float64), db.astype(np.float64)
    s_true = (db64**2).sum(-1)[None, :] - 2.0 * (q64 @ db64.T)
    d_true = ((db64[None] - q64[:, None]) ** 2).sum(-1)
    from knn_tpu.ops.pallas_knn import kernel_tolerance

    tol = kernel_tolerance(queries, db, precision=precision)
    for qi in range(queries.shape[0]):
        outside = np.setdiff1d(np.arange(db.shape[0]), idx[qi])
        assert s_true[qi, outside].min() >= lb[qi] - tol[qi]
        np.testing.assert_allclose(
            d32[qi], d_true[qi, idx[qi]], rtol=1e-5, atol=1e-3
        )


def test_survivor_cap_pads_output(rng):
    # tile_n=BIN_W -> 1 bin -> survivors capped at MAX_SURVIVORS=8; the
    # remaining 120 slots are sentinel-padded, selection still works
    db = rng.normal(size=(BIN_W, 8)).astype(np.float32)
    queries = rng.normal(size=(3, 8)).astype(np.float32)
    _, true_idx = _oracle(db, queries, 2)
    cand = np.asarray(
        pallas_knn_candidates(jnp.asarray(queries), jnp.asarray(db), 8,
                              tile_n=BIN_W)
    )
    for c, t in zip(cand, true_idx):
        assert set(t.tolist()) <= set(c[c < db.shape[0]].tolist())


def test_pallas_certified_matches_oracle(rng):
    db = rng.normal(size=(15 * BIN_W + 31, 24)).astype(np.float32) * 20
    db[200:250] = db[:50]  # ties
    queries = rng.normal(size=(23, 24)).astype(np.float32) * 20
    ref_d, ref_i = _oracle(db, queries, 9)
    d, i, stats = knn_search_pallas(queries, db, 9, tile_n=4 * BIN_W, margin=8)
    np.testing.assert_array_equal(i, ref_i)
    # indices are exact; distances are f32-direct unless a query escalated
    # to the float64 refine (ops.pallas_knn.RANK_SLACK contract)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)
    assert stats["certified"] + stats["fallback_queries"] == 23
    assert (stats["fallback_genuine_misses"]
            + stats["fallback_false_alarms"]) == stats["fallback_queries"]


def test_pallas_certified_survives_adversarial_bins(rng):
    # cram the ENTIRE true top-k into ONE kernel bin with k >
    # MAX_SURVIVORS: the kernel keeps only the bin's top 8, the bound
    # certificate must flag the loss and the fallback must still return
    # the exact answer.  A bin is one LANE across a tile's column groups
    dim, k = 12, 10
    tile_n = 12 * BIN_W  # 12 groups of 128 lanes per tile
    db = rng.normal(size=(tile_n, dim)).astype(np.float32) * 50
    hot = [7 + BIN_W * g for g in range(k)]  # lane 7 of groups 0..9
    query = rng.normal(size=(1, dim)).astype(np.float32)
    for j, r in enumerate(hot):
        db[r] = query[0] + (j + 1) * 1e-3
    ref_d, ref_i = _oracle(db, query, k)
    d, i, stats = knn_search_pallas(query, db, k, tile_n=tile_n, margin=4)
    np.testing.assert_array_equal(i, ref_i)
    assert stats["fallback_queries"] >= 1
    assert stats["fallback_genuine_misses"] >= 1


def test_pad_candidates_never_get_finite_distances(rng):
    # regression (round-3 review): kernel-padding candidate indices in
    # [rows, padded) used to be clip-gathered onto the LAST REAL row and
    # emerge with its finite distance, breaking certified exactness when
    # real survivors were scarce
    db = rng.normal(size=(132, 8)).astype(np.float32) * 10
    queries = rng.normal(size=(5, 8)).astype(np.float32) * 10
    d32, idx, lb = local_certified_candidates(
        jnp.asarray(queries), jnp.asarray(db), m=20, tile_n=2 * BIN_W,
        interpret=True,
    )
    d32, idx = np.asarray(d32)[:5], np.asarray(idx)[:5]
    pad = idx >= db.shape[0]
    assert np.isinf(d32[pad]).all()
    assert (idx[pad] == 2**31 - 1).all()


def test_preplaced_zero_padded_db_masks_pad_rows(rng):
    # pre-placed arrays follow the multihost contract: caller zero-pads
    # and passes n_train; a zero pad row sits at the origin and must not
    # surface from the pallas certified path (round-3 review finding)
    import jax

    from knn_tpu.parallel import ShardedKNN, make_mesh
    from knn_tpu.parallel.mesh import pad_to_multiple

    db = (rng.normal(size=(1001, 8)).astype(np.float32) + 4.0) * 10
    queries = np.zeros((9, 8), dtype=np.float32)  # at the origin, like pads
    ref_d, ref_i = _oracle(db, queries, 5)
    mesh = make_mesh(2, 4)
    padded, n_train = pad_to_multiple(db, 8)  # zero fill
    placed = jax.device_put(
        padded,
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("db")),
    )
    prog = ShardedKNN(placed, mesh=mesh, k=5, n_train=n_train)
    prog._train_host = db  # host copy for the certified refine
    d, i, stats = prog.search_certified(queries, selector="pallas",
                                        tile_n=2 * BIN_W)
    assert (i < n_train).all()
    np.testing.assert_array_equal(i, ref_i)


def test_kernel_rejects_bad_geometry(rng):
    db = rng.normal(size=(256, 8)).astype(np.float32)
    q = rng.normal(size=(4, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="multiple"):
        pallas_knn_candidates(jnp.asarray(q), jnp.asarray(db), 4, tile_n=100)


def test_candidate_fn_composition_on_tiny_db(rng):
    # regression (round-3 review): knn_search_certified computes
    # m = min(k+margin, n); on dbs with n <= k+margin the kernel keeps
    # n-1 rows + sentinel padding and the count certificate repairs the
    # one unexaminable row — composition must stay exact
    from knn_tpu.ops.certified import knn_search_certified

    db = rng.normal(size=(20, 6)).astype(np.float32) * 10
    queries = rng.normal(size=(7, 6)).astype(np.float32) * 10
    ref_d, ref_i = _oracle(db, queries, 5)
    d, i, stats = knn_search_certified(
        queries, db, 5, candidate_fn=pallas_knn_candidates
    )
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=1e-9)


@pytest.mark.parametrize("survivors", [1, 3, 4, 8])
def test_grouped_survivors_match_oracle(rng, survivors):
    # the tunable geometry (more survivors per bin widens the candidate
    # array the final select scans and cuts the fallbacks): certified
    # exactness must hold at every survivor count, not only the default
    # 2 — one survivor loses the second of two neighbours that share a
    # lane and has to repair it, eight is the unrolled network's cap
    db = rng.normal(size=(9 * BIN_W + 45, 16)).astype(np.float32) * 20
    queries = rng.normal(size=(11, 16)).astype(np.float32) * 20
    ref_d, ref_i = _oracle(db, queries, 7)
    d, i, stats = knn_search_pallas(
        queries, db, 7, tile_n=4 * BIN_W, margin=8, survivors=survivors,
    )
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)
    assert stats["pallas_knobs"]["survivors"] == survivors
    assert stats["certified"] + stats["fallback_queries"] == 11


def test_multi_block_output_lanes_match_oracle(rng):
    # survivors > 1 forces a multiple-of-128-lane output block (a
    # narrower one does not lower): both the _geometry arithmetic AND a
    # real kernel run at out_w = 1024
    from knn_tpu.ops.pallas_knn import _geometry

    # always 128 lane-bins; out_w = survivors * 128, capped at 8
    assert _geometry(4 * BIN_W) == (128, 2, 256, 128)
    assert _geometry(32 * BIN_W, 64) == (128, 8, 1024, 128)
    assert _geometry(160 * BIN_W, 1) == (128, 1, 128, 128)

    db = rng.normal(size=(2 * 32 * BIN_W + 77, 8)).astype(np.float32) * 5
    queries = rng.normal(size=(5, 8)).astype(np.float32) * 5
    k = 5
    ref_d, ref_i = _oracle(db, queries, k)
    # 8 survivors -> out_w = 1024 (8 blocks)
    d, i, _ = knn_search_pallas(queries, db, k, tile_n=32 * BIN_W, margin=6,
                                survivors=8)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)


def test_final_select_approx_stays_exact(rng):
    # approx_max_k as the final candidate select: the exclusion value is
    # restored exactly (masked min over the de-selected), so the result
    # must STILL match the float64 oracle — misses surface as fallbacks,
    # never as wrong neighbors
    db = rng.normal(size=(12 * BIN_W + 9, 24)).astype(np.float32) * 20
    db[300:340] = db[:40]  # cross-bin ties
    queries = rng.normal(size=(17, 24)).astype(np.float32) * 20
    ref_d, ref_i = _oracle(db, queries, 8)
    d, i, stats = knn_search_pallas(queries, db, 8, tile_n=4 * BIN_W,
                                    margin=8, final_select="approx")
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_allclose(d, ref_d, rtol=5e-5)


def test_bit_mask_roundtrip(rng):
    import jax

    from knn_tpu.parallel.sharded import _pack_bits_u32, unpack_bits_u32

    for b in (1, 31, 32, 33, 116, 128):
        mask = rng.random((9, b)) < 0.3
        packed = jax.jit(_pack_bits_u32)(jnp.asarray(mask))
        assert packed.shape == (9, -(-b // 32))
        out = unpack_bits_u32(np.asarray(packed), b)
        np.testing.assert_array_equal(out, mask)


def test_effective_tile_halves_for_midsize_dbs():
    # the round-4 default tile (16384) must not starve mid-size dbs of
    # candidate width: the shared halving helper shrinks the tile until
    # n_tiles * out_w covers min_width (= m+2 for certified callers)
    from knn_tpu.ops.pallas_knn import _geometry, effective_tile

    # 10k rows, need 302 lanes: one 10112-tile gives 256 -> halve
    t = effective_tile(10_000, 16384, None, 302)
    assert t % BIN_W == 0
    n_tiles = -(-10_000 // t)
    assert n_tiles * _geometry(t)[2] >= 302

    # huge db: no halving needed, the request is honored
    assert effective_tile(1_000_000, 16384, None, 130) == 16384
    # tiny db: tile caps at the padded rows
    assert effective_tile(200, 16384, None, 4) == 256
    # bottoms out at BIN_W even when the width can never be met
    assert effective_tile(100, 16384, None, 10**6) == BIN_W
    # an explicitly invalid request still raises, never silently repaired
    with pytest.raises(ValueError, match="multiple"):
        effective_tile(10_000, 100, None, 10)
    # more survivors widen a tile's block, so fewer halvings are needed
    assert effective_tile(10_000, 16384, 4, 302) > t


def test_default_tile_wide_margin_midsize_end_to_end(rng):
    # regression: at the 16384 default tile a 10k-row db previously
    # raised "m+2 exceeds ... survivors" for wide margins; the adaptive
    # tile must keep the certified path exact end-to-end instead
    db = rng.normal(size=(10_000, 12)).astype(np.float32) * 30
    queries = rng.normal(size=(4, 12)).astype(np.float32) * 30
    ref_d, ref_i = _oracle(db, queries, 60)
    d, i, stats = knn_search_pallas(queries, db, 60, margin=240)
    np.testing.assert_array_equal(i, ref_i)
