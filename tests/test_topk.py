import jax
import jax.numpy as jnp
import numpy as np
import pytest

import oracles
from knn_tpu.ops import topk
from knn_tpu.ops.metrics import METRICS


def test_topk_smallest_sorted_and_lowindex_ties(rng):
    d = rng.integers(0, 5, size=(6, 40)).astype(np.float32)  # many ties
    vals, idx = topk.topk_smallest(jnp.asarray(d), 7)
    vals, idx = np.asarray(vals), np.asarray(idx)
    ref_vals, ref_idx = oracles.topk_lowindex(d, 7)
    np.testing.assert_array_equal(vals, ref_vals)
    np.testing.assert_array_equal(idx, ref_idx)


def test_knn_search_matches_oracle(rng):
    q = rng.normal(size=(9, 12)).astype(np.float32)
    t = rng.normal(size=(50, 12)).astype(np.float32)
    d_ref, i_ref = oracles.topk_lowindex(oracles.sq_l2(q, t), 5)
    d, i = topk.knn_search(jnp.asarray(q), jnp.asarray(t), 5)
    np.testing.assert_array_equal(np.asarray(i), i_ref)
    np.testing.assert_allclose(np.asarray(d), d_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tile", [7, 16, 50, 64])
@pytest.mark.parametrize("metric", ["l2", "l1"])
def test_tiled_equals_untiled(rng, tile, metric):
    q = rng.normal(size=(9, 12)).astype(np.float32)
    t = rng.normal(size=(50, 12)).astype(np.float32)
    d0, i0 = topk.knn_search(jnp.asarray(q), jnp.asarray(t), 6, metric)
    d1, i1 = topk.knn_search_tiled(jnp.asarray(q), jnp.asarray(t), 6, metric, train_tile=tile)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-4, atol=1e-4)


def test_tiled_tie_break_lowindex(rng):
    # duplicate train rows across tile boundaries: ties must resolve to the
    # lower train index even when the duplicate lives in a later tile
    base = rng.normal(size=(10, 8)).astype(np.float32)
    t = np.concatenate([base, base, base], axis=0)  # indices i, i+10, i+20 equal
    q = base[:4] + 0.0
    _, idx = topk.knn_search_tiled(jnp.asarray(q), jnp.asarray(t), 3, train_tile=7)
    idx = np.asarray(idx)
    d_ref, i_ref = oracles.topk_lowindex(oracles.sq_l2(q, t), 3)
    np.testing.assert_array_equal(idx, i_ref)


_SENTINEL = np.iinfo(np.int32).max

#: (rows, train_tile, k, n_valid) of a scan whose last tile is ragged: the
#: tile is read whole, ending at the last row, and the rows it shares with
#: the tile before are masked by their index
RAGGED = {
    "tail": (50, 16, 6, None),
    "tail_of_one_row": (49, 16, 6, None),
    # the clamped tile starts at row 20 and holds rows 30-49 a second time
    "tile_over_half_the_rows": (50, 30, 6, None),
    "tile_one_row_short": (50, 49, 6, None),
    # a tile no wider than k is merged whole (the branch without a top-k)
    "tile_at_most_k": (50, 7, 9, None),
    "n_valid_in_the_last_tile": (50, 16, 6, 49),
    "n_valid_in_the_overlap": (50, 30, 6, 25),
    # fewer valid rows than k: +inf and the sentinel, never a real index
    "n_valid_below_k": (50, 16, 6, 4),
    "n_valid_below_k_one_tile_live": (50, 30, 9, 7),
}


def _ragged_rows(rng, rows, tile):
    """Small-integer rows (every distance exact in float32, and tied many
    times over), with copies on both sides of the clamped tile's overlap:
    a row of the tail equals a row of the overlap, and both equal a row
    of the first tile."""
    t = rng.integers(0, 3, size=(rows, 5)).astype(np.float32) + 1.0
    first_of_tail = rows // tile * tile
    overlap = max(rows - tile, 0)
    for a, b, c in [(1, overlap, first_of_tail),
                    (3, first_of_tail - 1, rows - 1)]:
        t[b] = t[a]
        t[c] = t[a]
    q = np.concatenate([t[[1, 3, first_of_tail, rows - 1]],
                        rng.integers(0, 3, size=(5, 5)).astype(np.float32) + 1.0])
    return q, t


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("case", RAGGED)
def test_a_ragged_scan_is_the_untiled_search(rng, case, metric):
    """Every ``(distance, index)`` pair of the scan that reads the rows
    where they lie equals the whole distance matrix's, lower index first
    among equals, with ``n_valid`` traced (the host tier's ragged segment)
    and masked rows under no real index."""
    rows, tile, k, n_valid = RAGGED[case]
    q, t = _ragged_rows(rng, rows, tile)

    whole = -(-rows // tile) * tile

    def all_three(q, t, n_valid):
        # the parent's program: the operand padded to whole tiles, the pad
        # rows masked by n_valid (a scan of equal tiles, bit for bit the
        # same arithmetic; the untiled matrix rounds cosine otherwise)
        padded = topk.knn_search_tiled(
            q, jnp.pad(t, ((0, whole - rows), (0, 0))), k, metric,
            train_tile=tile, n_valid=rows if n_valid is None else n_valid)
        return (topk.knn_search(q, t, k, metric, n_valid=n_valid), padded,
                topk.knn_search_tiled(q, t, k, metric, train_tile=tile,
                                      n_valid=n_valid))

    args = (jnp.asarray(q), jnp.asarray(t))
    if n_valid is None:  # static: the re-select's form
        out = jax.jit(lambda q, t: all_three(q, t, None))(*args)
    else:  # traced: the host tier's
        out = jax.jit(all_three)(*args, jnp.int32(n_valid))
    (d0, i0), (dp, ip), (d1, i1) = [
        (np.asarray(d), np.asarray(i)) for d, i in out]
    live = min(rows if n_valid is None else n_valid, k)
    assert np.isfinite(d1[:, :live]).all()
    assert (i1[:, live:] == _SENTINEL).all() and np.isinf(d1[:, live:]).all()
    np.testing.assert_array_equal(i1, ip)
    np.testing.assert_array_equal(d1, dp)
    if metric == "cosine":
        np.testing.assert_allclose(d1, d0, rtol=1e-5, atol=1e-6)
    else:
        # the untiled search names a padding row by its own index
        np.testing.assert_array_equal(
            i1, np.where(np.isinf(d0), _SENTINEL, i0))
        np.testing.assert_array_equal(d1, d0)
    if metric == "l2":
        ref_d, ref_i = oracles.topk_lowindex(oracles.sq_l2(q, t[:n_valid]), live)
        np.testing.assert_array_equal(i1[:, :live], ref_i)
        np.testing.assert_array_equal(d1[:, :live], ref_d)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("rows,width", [
    (1_000_000, 1024),   # gist1m.sweep as placed: 7 tiles and 82,496 rows
    (5_000_000, 128)])   # bigann5m.sweep: 38 tiles and 19,264 rows
def test_the_exact_program_pads_no_row(rows, width):
    """The certified repair's re-select at a cell's shape, traced from
    abstract arguments: nothing in it pads the train operand or writes an
    array of its size (the pad to whole tiles was a copy of all rows in
    every call, 12.8 and 3.5 ms a batch on the chip at these two shapes),
    and the one reader of the rows is the scan's window."""
    from knn_tpu.parallel import make_mesh
    from knn_tpu.parallel import sharded as sh

    prog = sh._knn_program(make_mesh(1, 1), 256, "l2", "ring", rows, 131072,
                           None, "exact", dcn_merge=None)
    jaxpr = jax.make_jaxpr(prog)(
        jax.ShapeDtypeStruct((16, width), jnp.float32),
        jax.ShapeDtypeStruct((rows, width), jnp.float32))
    eqns = list(_equations(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert "pad" not in names and "scan" in names
    whole = [e.primitive.name for e in eqns for v in e.outvars
             if np.prod(v.aval.shape, dtype=np.int64) >= rows * width]
    assert whole == []
    windows = [e for e in eqns if e.primitive.name == "dynamic_slice"
               and e.invars[0].aval.shape == (rows, width)]
    assert [e.outvars[0].aval.shape for e in windows] == [(131072, width)]


def test_k_larger_than_train_raises(rng):
    q = jnp.asarray(rng.normal(size=(2, 4)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(3, 4)).astype(np.float32))
    with pytest.raises(ValueError):
        topk.knn_search_tiled(q, t, 5, train_tile=2)


def test_approx_recall(rng):
    q = rng.normal(size=(16, 32)).astype(np.float32)
    t = rng.normal(size=(2048, 32)).astype(np.float32)
    k = 10
    _, exact = topk.knn_search(jnp.asarray(q), jnp.asarray(t), k)
    _, approx = topk.knn_search_approx(jnp.asarray(q), jnp.asarray(t), k, recall_target=0.95)
    exact, approx = np.asarray(exact), np.asarray(approx)
    recall = np.mean(
        [len(set(exact[i]) & set(approx[i])) / k for i in range(q.shape[0])]
    )
    assert recall >= 0.8
