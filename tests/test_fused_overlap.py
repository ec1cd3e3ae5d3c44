"""The fused-select kernel arm (ops.pallas_knn kernel="fused" —
in-loop carry + sound exclusion-bound early-out, bitwise-identical
final results) and the block_q default's result invariance."""

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import tuning
from knn_tpu.ops.pallas_knn import (
    BIN_W,
    _bin_candidates,
    kernel_launches_per_batch,
    knn_search_pallas,
    local_certified_candidates,
)
from tests.oracles import sq_l2, topk_lowindex


def _oracle(db, queries, k):
    return topk_lowindex(sq_l2(queries, db), k)


# --- fused kernel: bitwise parity ---------------------------------------


@pytest.mark.parametrize("precision", ["highest", "bf16x3", "int8"])
@pytest.mark.parametrize("n_rows", [
    2 * BIN_W,          # exactly one tile
    2 * BIN_W + 1,      # ragged: one row past a tile edge
    5 * BIN_W + 60,     # several tiles, ragged tail
])
def test_fused_bitwise_equals_tiled_certified_stage(rng, n_rows, precision):
    """THE acceptance gate: the fused arm reproduces the reference
    grouped config's certified candidate stage (d32, idx, exclusion
    bound) BITWISE across precisions and ragged tile counts — the
    early-out carry is armed (keep = m+2 plumbed from the certified
    caller) on every one of these runs."""
    db = rng.normal(size=(n_rows, 24)).astype(np.float32) * 10
    queries = rng.normal(size=(7, 24)).astype(np.float32) * 10
    outs = {}
    for kern in ("tiled", "fused"):
        outs[kern] = local_certified_candidates(
            jnp.asarray(queries), jnp.asarray(db), m=13, block_q=8,
            tile_n=2 * BIN_W, interpret=True, kernel=kern,
            precision=precision)
    for a, b in zip(outs["tiled"], outs["fused"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dim", [24, 300])  # 300 spans 3 DIM_CHUNKs
def test_fused_disarmed_bin_candidates_match_streaming(rng, dim):
    """Without ``keep`` the early-out disarms (thr stays +inf, nothing
    skips) and the fused kernel's raw outputs equal the streaming
    kernel's exactly — the fused arm IS the streaming launch plus the
    carry machinery."""
    db = rng.normal(size=(3 * BIN_W + 41, dim)).astype(np.float32) * 10
    queries = rng.normal(size=(11, dim)).astype(np.float32) * 10
    outs = {}
    for kern in ("streaming", "fused"):
        outs[kern] = _bin_candidates(
            jnp.asarray(queries), jnp.asarray(db), block_q=8,
            tile_n=2 * BIN_W, survivors=2,
            precision="bf16x3", interpret=True, kernel=kern)
    for a, b in zip(outs["streaming"], outs["fused"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_early_out_fires_and_stays_bitwise(rng):
    """The early-out must actually SKIP on skippable data (observable:
    a skipped tile's whole candidate block pads +inf/sentinel where the
    streaming kernel emitted real values), while the certified stage
    stays bitwise-identical — the skip predicate provably changed
    nothing downstream."""
    db = rng.normal(size=(6 * BIN_W, 16)).astype(np.float32)
    db[2 * BIN_W:] += 500.0  # tiles 1..2 uniformly far from every query
    queries = db[:9] + rng.normal(size=(9, 16)).astype(np.float32) * 1e-2
    cd_f, _, b_f = _bin_candidates(
        jnp.asarray(queries), jnp.asarray(db), block_q=16,
        tile_n=2 * BIN_W, survivors=2, precision="bf16x3",
        interpret=True, kernel="fused", keep=15)
    cd_s, _, b_s = _bin_candidates(
        jnp.asarray(queries), jnp.asarray(db), block_q=16,
        tile_n=2 * BIN_W, survivors=2, precision="bf16x3",
        interpret=True, kernel="streaming")
    cd_f, cd_s = np.asarray(cd_f), np.asarray(cd_s)
    out_w = 2 * BIN_W  # survivors=2
    skipped = [t for t in range(3)
               if np.isinf(cd_f[:, t * out_w:(t + 1) * out_w]).all()
               and not np.isinf(cd_s[:, t * out_w:(t + 1) * out_w]).all()]
    assert skipped, "the exclusion-bound early-out never fired"
    assert 0 not in skipped  # the tile holding every true neighbor ran
    # and the FINAL certified stage cannot tell the difference
    outs = {}
    for kern in ("tiled", "fused"):
        outs[kern] = local_certified_candidates(
            jnp.asarray(queries), jnp.asarray(db), m=13, block_q=16,
            tile_n=2 * BIN_W, interpret=True, kernel=kern)
    for a, b in zip(outs["tiled"], outs["fused"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_cross_tile_duplicate_ties_end_to_end(rng):
    """Exact cross-tile distance ties + a near-tie pileup: the
    lexicographic tie-break and the f64 rank correction see identical
    inputs under the fused arm — end-to-end results and certification
    stats agree with the tiled reference bit for bit."""
    db = rng.normal(size=(6 * BIN_W + 31, 12)).astype(np.float32) * 20
    db[3 * BIN_W: 3 * BIN_W + 40] = db[:40]         # cross-tile copies
    db[5 * BIN_W: 5 * BIN_W + 10] = db[100] + 1e-3  # near-tie pileup
    queries = rng.normal(size=(9, 12)).astype(np.float32) * 20
    queries[0] = db[0] + 5e-4
    queries[1] = db[100] + 5e-4
    ref_d, ref_i = _oracle(db, queries, 7)
    results = {}
    for kern in ("tiled", "fused"):
        d, i, stats = knn_search_pallas(queries, db, 7, tile_n=2 * BIN_W,
                                        margin=8, kernel=kern)
        np.testing.assert_array_equal(i, ref_i)
        np.testing.assert_allclose(d, ref_d, rtol=5e-5)
        results[kern] = (d, i, stats)
    np.testing.assert_array_equal(results["tiled"][0], results["fused"][0])
    np.testing.assert_array_equal(results["tiled"][1], results["fused"][1])
    strip = lambda s: {k: v for k, v in s.items()  # noqa: E731
                       if k not in ("pallas_knobs", "tuning")}
    assert strip(results["tiled"][2]) == strip(results["fused"][2])


def test_fused_sharded_search_certified_bitwise(rng):
    """Sharded db: one fused launch PER SHARD, merged across the db
    axis — bitwise equal to the tiled path and the oracle."""
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db = rng.normal(size=(1500, 12)).astype(np.float32) * 20
    queries = rng.normal(size=(9, 12)).astype(np.float32) * 20
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=5)
    out = {}
    for kern in ("tiled", "fused"):
        d, i, stats = prog.search_certified(
            queries, selector="pallas", margin=8, tile_n=2 * BIN_W,
            kernel=kern)
        out[kern] = (d, i)
        assert stats["pallas_knobs"]["kernel"] == kern
    np.testing.assert_array_equal(out["tiled"][0], out["fused"][0])
    np.testing.assert_array_equal(out["tiled"][1], out["fused"][1])
    _, ref_i = _oracle(db, queries, 5)
    np.testing.assert_array_equal(out["fused"][1], ref_i)


def test_fused_refuses_incompatible_knobs(rng):
    db = rng.normal(size=(4 * BIN_W, 8)).astype(np.float32)
    q = db[:4]
    with pytest.raises(ValueError, match="final_select='exact'"):
        local_certified_candidates(jnp.asarray(q), jnp.asarray(db), m=5,
                                   interpret=True, kernel="fused",
                                   final_select="approx")
    with pytest.raises(ValueError, match="db_major"):
        local_certified_candidates(jnp.asarray(q), jnp.asarray(db), m=5,
                                   interpret=True, kernel="fused",
                                   grid_order="db_major")
    # launch accounting: fused is ONE launch like streaming
    assert kernel_launches_per_batch("fused", 1_000_000, 16384) == 1


# --- defaults promotion (satellite) -------------------------------------


def test_block_q_256_is_the_default_and_result_invariant(rng):
    """block_q 256 is the default at the tuning layer, and block_q is
    result-invariant — the whole basis of promoting it without touching
    the bitwise contract."""
    assert tuning.DEFAULT_KNOBS["block_q"] == 256
    # block_q re-blocks the query grid only: results are bitwise
    # invariant to it (per-row arithmetic untouched)
    db = rng.normal(size=(3 * BIN_W + 17, 12)).astype(np.float32) * 10
    q = rng.normal(size=(16, 12)).astype(np.float32) * 10
    outs = [local_certified_candidates(
        jnp.asarray(q), jnp.asarray(db), m=9, block_q=bq,
        tile_n=2 * BIN_W, interpret=True) for bq in (8, 16)]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
