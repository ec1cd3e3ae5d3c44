"""The double-buffered streaming kernel (ops.pallas_knn kernel="streaming")
in interpret mode: bitwise equality against the tiled grouped kernel at
every output level — raw bin candidates, the certified candidate stage,
and the end-to-end certified search — across tile-boundary cases (n not
divisible by tile_n, true neighbors straddling a tile edge, duplicate
distances exercising the lexicographic tie-break), plus the float64
direct-difference oracle (the pairwise_sq_l2_direct semantics in fp64).
Bitwise equality is the whole contract: the streaming pipeline changes
HOW the db reaches VMEM (explicit double-buffered DMA, one launch per
batch/shard), never WHAT is computed.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu.ops.pallas_knn import (
    BIN_W,
    DIM_CHUNK,
    _bin_candidates,
    kernel_launches_per_batch,
    knn_search_pallas,
    local_certified_candidates,
)
from tests.oracles import sq_l2, topk_lowindex


def _oracle(db, queries, k):
    return topk_lowindex(sq_l2(queries, db), k)


@pytest.mark.parametrize("dim", [24, 300])
@pytest.mark.parametrize("precision,survivors", [
    ("bf16x3", 2), ("bf16x3f", 2), ("highest", 2), ("int8", 2),
    ("bf16x3", 1), ("bf16x3", 4), ("int8", 1), ("int8", 4),
])
def test_streaming_bitwise_equals_tiled_bin_candidates(rng, dim, precision,
                                                       survivors):
    # raw kernel outputs (candidates, indices, per-tile bounds) across
    # uneven tile counts (n % tile_n != 0 -> PAD_VAL padding), both
    # single- and multi-chunk dims (300 spans 3 DIM_CHUNKs), and output
    # blocks of one, two and four lane-rows a tile (the streaming kernel
    # writes each tile's block at a dynamic column offset of that width).
    # Equal where the chunking is equal: the streaming kernel keeps
    # 128-column chunks at every width, the tiled one would run a tile
    # this small as ONE chunk (ops.pallas_knn.dim_chunking), so it is
    # held to the streaming kernel's width
    db = rng.normal(size=(3 * BIN_W + 41, dim)).astype(np.float32) * 10
    queries = rng.normal(size=(11, dim)).astype(np.float32) * 10
    outs = {}
    for kern in ("tiled", "streaming"):
        outs[kern] = _bin_candidates(
            jnp.asarray(queries), jnp.asarray(db), block_q=8,
            tile_n=2 * BIN_W, survivors=survivors,
            precision=precision, interpret=True, kernel=kern,
            dim_chunk=DIM_CHUNK)
    assert outs["tiled"][0].shape[1] == 2 * survivors * BIN_W
    for a, b in zip(outs["tiled"], outs["streaming"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("precision", ["bf16x3", "int8"])
@pytest.mark.parametrize("n_rows", [
    2 * BIN_W,          # exactly one tile
    2 * BIN_W + 1,      # one row past a tile edge
    5 * BIN_W + 60,     # several tiles, ragged tail
])
def test_streaming_bitwise_equals_tiled_certified_stage(rng, n_rows,
                                                        precision):
    # the full certified candidate stage (kernel + final select + f32
    # rescore): d32, idx, AND the exclusion bound must agree bitwise
    db = rng.normal(size=(n_rows, 24)).astype(np.float32) * 10
    queries = rng.normal(size=(7, 24)).astype(np.float32) * 10
    outs = {}
    for kern in ("tiled", "streaming"):
        outs[kern] = local_certified_candidates(
            jnp.asarray(queries), jnp.asarray(db), m=13, block_q=8,
            tile_n=2 * BIN_W, interpret=True, kernel=kern,
            precision=precision)
    for a, b in zip(outs["tiled"], outs["streaming"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_k_spanning_tile_edge_matches_oracle(rng):
    # plant the true top-k STRADDLING a tile boundary (last rows of tile
    # 0, first rows of tile 1): the carried candidate list must merge
    # across the in-kernel tile loop exactly like the tiled path's XLA
    # merge
    dim, k, tile_n = 16, 8, 2 * BIN_W
    db = rng.normal(size=(4 * BIN_W, dim)).astype(np.float32) * 50
    query = rng.normal(size=(1, dim)).astype(np.float32)
    hot = [tile_n - 4 + j for j in range(4)] + [tile_n + j for j in range(4)]
    for j, r in enumerate(hot):
        db[r] = query[0] + (j + 1) * 1e-3
    ref_d, ref_i = _oracle(db, query, k)
    for kern in ("tiled", "streaming"):
        d, i, _ = knn_search_pallas(query, db, k, tile_n=tile_n, margin=6,
                                    kernel=kern)
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(d, ref_d, rtol=5e-5)


def test_streaming_duplicate_distances_lexicographic_ties(rng):
    # duplicate rows ACROSS tiles force exact distance ties whose
    # resolution is the documented lexicographic (distance, index)
    # order; a query placed on a duplicated pair plus a near-tie pileup
    # exercises the rank-correction path under both kernels
    db = rng.normal(size=(6 * BIN_W + 31, 12)).astype(np.float32) * 20
    db[3 * BIN_W : 3 * BIN_W + 40] = db[:40]        # tile-2 copies of tile-0 rows
    db[5 * BIN_W : 5 * BIN_W + 10] = db[100] + 1e-3  # near-tie pileup
    queries = rng.normal(size=(9, 12)).astype(np.float32) * 20
    queries[0] = db[0] + 5e-4    # lands ON a cross-tile duplicate pair
    queries[1] = db[100] + 5e-4  # lands in the pileup
    ref_d, ref_i = _oracle(db, queries, 7)
    results = {}
    for kern in ("tiled", "streaming"):
        d, i, stats = knn_search_pallas(queries, db, 7, tile_n=2 * BIN_W,
                                        margin=8, kernel=kern)
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(d, ref_d, rtol=5e-5)
        results[kern] = (d, i, stats)
    # and the two kernels agree bitwise END TO END — certification
    # stats included (the knob/provenance blocks legitimately differ:
    # they record which kernel ran)
    np.testing.assert_array_equal(results["tiled"][0], results["streaming"][0])
    np.testing.assert_array_equal(results["tiled"][1], results["streaming"][1])
    strip = lambda s: {k: v for k, v in s.items()  # noqa: E731
                       if k not in ("pallas_knobs", "tuning")}
    assert strip(results["tiled"][2]) == strip(results["streaming"][2])


def test_streaming_sharded_search_certified_bitwise(rng):
    # the sharded certified pipeline with db shards: one streaming
    # launch PER SHARD, merged across the db axis — results bitwise
    # equal to the tiled path's
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db = rng.normal(size=(1500, 12)).astype(np.float32) * 20
    queries = rng.normal(size=(9, 12)).astype(np.float32) * 20
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=5)
    out = {}
    for kern in ("tiled", "streaming"):
        d, i, stats = prog.search_certified(
            queries, selector="pallas", margin=8, tile_n=2 * BIN_W,
            kernel=kern)
        out[kern] = (d, i, stats)
        assert stats["pallas_knobs"]["kernel"] == kern
    np.testing.assert_array_equal(out["tiled"][0], out["streaming"][0])
    np.testing.assert_array_equal(out["tiled"][1], out["streaming"][1])
    ref_d, ref_i = _oracle(db, queries, 5)
    np.testing.assert_array_equal(out["streaming"][1], ref_i)


def test_streaming_rejects_db_major():
    # the streaming launch has no db grid axis to reorder — refusing the
    # knob beats silently ignoring it
    with pytest.raises(ValueError, match="db_major"):
        _bin_candidates(
            jnp.zeros((4, 8), jnp.float32), jnp.zeros((256, 8), jnp.float32),
            block_q=8, tile_n=2 * BIN_W, survivors=2,
            precision="bf16x3", interpret=True, grid_order="db_major",
            kernel="streaming")


def test_kernel_launch_accounting():
    # the bench's launch-count contract: tiled = one pipelined body
    # launch per train tile, streaming = ONE per (batch, shard)
    assert kernel_launches_per_batch("tiled", 1_000_000, 16384) == 62
    assert kernel_launches_per_batch("streaming", 1_000_000, 16384) == 1
    assert kernel_launches_per_batch("tiled", 16384, 16384) == 1
    with pytest.raises(ValueError, match="kernel"):
        kernel_launches_per_batch("warp", 1000, 128)


# --- int8 coarse arm (the quantized MXU path, ops.quantize) -------------

def _int8_exact_data(rng, n_rows, dim):
    """Integer-valued data whose per-row max is pinned at 127: the int8
    quantization is then EXACT (unit scales, zero residuals) and every
    kernel score is a small integer computed exactly by BOTH the bf16x3
    reference and the int8 arm — which is what makes FINAL results
    bitwise comparable across precisions (fallback-pattern divergence
    cannot leak into the values: all distances are < 2^24 integers,
    exact in f32 and f64 alike)."""
    db = rng.integers(-100, 101, size=(n_rows, dim)).astype(np.float32)
    db[:, 0] = 127.0  # pins max|row| -> scale exactly 1.0
    return db


@pytest.mark.parametrize("kern", ["tiled", "streaming"])
@pytest.mark.parametrize("n_rows", [
    2 * BIN_W,          # exactly one tile
    2 * BIN_W + 1,      # ragged: one row past a tile edge
    5 * BIN_W + 60,     # several tiles, ragged tail
])
def test_int8_final_results_bitwise_vs_reference(rng, n_rows, kern):
    """THE acceptance gate: precision='int8' reproduces the reference
    grouped config's FINAL certified (distances, indices) bitwise, across
    both db-streaming kernels and ragged tile counts — including
    cross-tile duplicate ties (exact distance ties resolved by the
    lexicographic rule + f64 rank correction)."""
    dim, k = 12, 7
    db = _int8_exact_data(rng, n_rows, dim)
    # cross-tile duplicates + a query ON a duplicated pair: exact ties
    dup = min(40, n_rows - 2 * BIN_W) if n_rows > 2 * BIN_W else 20
    db[n_rows - dup:] = db[:dup]
    queries = _int8_exact_data(rng, 9, dim)
    queries[0] = db[0]  # exact-tie pileup on a duplicated row
    ref_d, ref_i, _ = knn_search_pallas(queries, db, k, tile_n=2 * BIN_W,
                                        margin=8)
    d, i, stats = knn_search_pallas(queries, db, k, tile_n=2 * BIN_W,
                                    margin=8, precision="int8",
                                    kernel=kern)
    np.testing.assert_array_equal(i, ref_i)
    np.testing.assert_array_equal(d, ref_d)
    # and both equal the float64 oracle (exactness, not just agreement)
    oracle_d, oracle_i = _oracle(db, queries, k)
    np.testing.assert_array_equal(i, oracle_i)
    np.testing.assert_allclose(d, oracle_d, rtol=0, atol=0)


def test_int8_noisy_data_indices_exact_with_fallback(rng):
    """Real (non-representable) f32 data: quantization error is genuine,
    the certificate widens by the bound, and whatever falls back repairs
    — final INDICES equal the oracle unconditionally."""
    db = rng.normal(size=(5 * BIN_W + 31, 16)).astype(np.float32) * 10
    # near-tie pileup: distances inside the quantization band, forcing
    # the widened certificate to flag + repair rather than trust the rank
    queries = rng.normal(size=(8, 16)).astype(np.float32) * 10
    db[100:110] = queries[1][None, :] + rng.normal(
        size=(10, 16)).astype(np.float32) * 1e-2
    ref_d, ref_i = _oracle(db, queries, 6)
    for kern in ("tiled", "streaming"):
        d, i, stats = knn_search_pallas(queries, db, 6, tile_n=2 * BIN_W,
                                        margin=8, precision="int8",
                                        kernel=kern)
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(d, ref_d, rtol=5e-5)
        assert stats["fallback_queries"] + stats["certified"] == 8


def test_int8_sharded_search_certified_bitwise(rng):
    # sharded db: the quantized placement shards along the db axis, one
    # launch per shard, lb pmin'd — tiled and streaming int8 agree
    # bitwise end to end and match the oracle indices
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db = rng.normal(size=(1500, 12)).astype(np.float32) * 20
    queries = rng.normal(size=(9, 12)).astype(np.float32) * 20
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=5)
    out = {}
    for kern in ("tiled", "streaming"):
        d, i, stats = prog.search_certified(
            queries, selector="pallas", margin=8, tile_n=2 * BIN_W,
            precision="int8", kernel=kern)
        out[kern] = (d, i)
        assert stats["pallas_knobs"]["precision"] == "int8"
    np.testing.assert_array_equal(out["tiled"][0], out["streaming"][0])
    np.testing.assert_array_equal(out["tiled"][1], out["streaming"][1])
    ref_d, ref_i = _oracle(db, queries, 5)
    np.testing.assert_array_equal(out["streaming"][1], ref_i)
    # the quantized placement was built once and cached
    assert prog._int8_cache is not None


def test_int8_uncertifiable_default_precision_still_refused(rng):
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db = rng.normal(size=(600, 8)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=3)
    with pytest.raises(ValueError, match="tolerance model"):
        prog.search_certified(db[:4], selector="pallas",
                              precision="default")
