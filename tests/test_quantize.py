"""ops.quantize: the int8 coarse arm's quantization scheme and — the
load-bearing part — the PROVABLE per-query error bound ε the certificate
widens its threshold by.  The property test draws random (db, query)
pairs across dims, magnitudes, and dtypes and asserts ε >= the observed
|f32 score − reconstructed int8 score| for EVERY pair: the bound is a
proof obligation, not a heuristic, because a single violated pair could
certify a wrong answer."""

import numpy as np
import pytest

from knn_tpu.ops import quantize as qz


def _observed_errors(q, qr, t_sh, *, f32_arith=False):
    """[Q] max-over-db observed |shifted-space f32 score − int8
    reconstructed score| per query, computed in float64 against the
    shifted f64 db rows ``t_sh`` (``f32_arith`` re-evaluates the
    reconstruction in f32 ops to stress the bound's f32-slack term
    too)."""
    q_sh = np.asarray(q, np.float64) - qr.offset
    s_true = (t_sh ** 2).sum(-1)[None, :] - 2.0 * (q_sh @ t_sh.T)
    qi, sq, _ = qz.quantize_rows_np(q, offset=qr.offset)
    dots = qi.astype(np.int64) @ qr.values.astype(np.int64).T  # exact
    tn = (t_sh ** 2).sum(-1).astype(np.float32)
    if f32_arith:
        scale = (sq[:, None].astype(np.float32)
                 * qr.scales[None, :].astype(np.float32))
        s_hat = (tn[None, :]
                 - np.float32(2.0) * (dots.astype(np.float32) * scale))
        s_hat = s_hat.astype(np.float64)
    else:
        s_hat = (tn.astype(np.float64)[None, :]
                 - 2.0 * (sq[:, None].astype(np.float64)
                          * qr.scales[None, :].astype(np.float64)) * dots)
    return np.abs(s_true - s_hat).max(-1)


def _draw(rng, kind, n, dim):
    if kind == "normal":
        db = rng.normal(size=(n, dim)).astype(np.float32) * 10
        q = rng.normal(size=(5, dim)).astype(np.float32) * 10
    elif kind == "big":
        db = rng.normal(size=(n, dim)).astype(np.float32) * 1000
        q = rng.normal(size=(5, dim)).astype(np.float32) * 1000
    elif kind == "tiny":
        db = rng.normal(size=(n, dim)).astype(np.float32) * 1e-3
        q = rng.normal(size=(5, dim)).astype(np.float32) * 1e-3
    elif kind == "integer":
        db = rng.integers(-127, 128, size=(n, dim)).astype(np.float32)
        q = rng.integers(-127, 128, size=(5, dim)).astype(np.float32)
    elif kind == "uint8":
        db = rng.integers(0, 256, size=(n, dim), dtype=np.uint8)
        q = rng.integers(0, 256, size=(5, dim)).astype(np.float32)
    else:  # skewed: a few huge components dominate the row max
        db = rng.normal(size=(n, dim)).astype(np.float32)
        db[:, 0] *= 500
        q = rng.normal(size=(5, dim)).astype(np.float32)
        q[:, -1] *= 500
    return db, q


def test_bound_dominates_observed_error_property():
    """Hypothesis-style loop: random draws across dims/dtypes/magnitudes;
    ε must dominate the observed distance error for every (query, db row)
    pair, in exact f64 reconstruction AND under f32 rescale arithmetic."""
    rng = np.random.default_rng(20260803)
    kinds = ("normal", "big", "tiny", "integer", "uint8", "skewed")
    for trial in range(60):
        kind = kinds[trial % len(kinds)]
        dim = int(rng.choice([3, 8, 17, 64, 130]))
        n = int(rng.choice([20, 97, 256]))
        db, q = _draw(rng, kind, n, dim)
        if kind == "uint8":
            qr = qz.from_uint8(db)
            original = db
        else:
            qr = qz.quantize_rows_np(db)
            original = db
        stats = qz.db_bound_stats(qr, original, chunk=50)
        eps = qz.score_error_bound(q, stats, offset=qr.offset)
        t_sh = original.astype(np.float64) - qr.offset
        for f32_arith in (False, True):
            err = _observed_errors(q, qr, t_sh, f32_arith=f32_arith)
            assert (eps >= err).all(), (
                f"trial {trial} kind={kind} dim={dim} f32={f32_arith}: "
                f"eps {eps} < observed {err}")


def test_quantize_rows_roundtrip_and_ranges():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 16)).astype(np.float32) * 25
    qr = qz.quantize_rows_np(x)
    assert qr.values.dtype == np.int8
    assert np.abs(qr.values.astype(np.int16)).max() <= 127
    # per-component residual <= scale/2 (round-to-nearest, no clipping
    # at this magnitude)
    err = np.abs(x - qr.scales[:, None] * qr.values.astype(np.float32))
    assert (err <= qr.scales[:, None] * 0.5 + 1e-7).all()
    np.testing.assert_allclose(qz.dequantize(qr), x, atol=qr.scales.max())


def test_quantize_zero_rows_unit_scale():
    x = np.zeros((3, 8), np.float32)
    qr = qz.quantize_rows_np(x)
    np.testing.assert_array_equal(qr.scales, np.ones(3, np.float32))
    np.testing.assert_array_equal(qr.values, np.zeros((3, 8), np.int8))


def test_device_and_host_quantization_agree():
    # the device certificate recomputes the query quantization with the
    # traceable twin; both must produce the same payload (the bound's
    # residuals are the kernel's ACTUAL residuals only then)
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    x = rng.normal(size=(9, 33)).astype(np.float32) * 7
    host = qz.quantize_rows_np(x)
    dv, ds = qz.quantize_rows(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(dv), host.values)
    np.testing.assert_array_equal(np.asarray(ds), host.scales)


def test_from_uint8_is_exact_unit_scale():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 256, size=(30, 12), dtype=np.uint8)
    qr = qz.from_uint8(x)
    assert qr.offset == 128.0
    np.testing.assert_array_equal(qr.scales, np.ones(30, np.float32))
    # byte payload reused exactly: dequantized + offset == the original
    np.testing.assert_array_equal(qz.dequantize(qr), x.astype(np.float32))
    # residuals are identically zero -> the bound collapses to f32 slack
    stats = qz.db_bound_stats(qr, x)
    assert stats["et2_max"] == 0.0
    with pytest.raises(ValueError, match="uint8"):
        qz.from_uint8(x.astype(np.int16))


def test_bound_consts_round_up():
    stats = {"db_norm_max": 1.0 + 2.0 ** -30, "t2hat_max": 3.0,
             "et2_max": 1e-9}
    c = qz.bound_consts(stats)
    assert c.dtype == np.float32
    assert float(c[0]) >= stats["db_norm_max"]
    assert float(c[2]) >= stats["et2_max"]


def test_uint8_sharded_int8_search_is_exact(rng):
    """End to end: a uint8 (bvecs-style) database through
    ShardedKNN(precision='int8') — byte-exact placement, certified
    results equal to the float64 oracle."""
    from knn_tpu.parallel import ShardedKNN, make_mesh

    db = rng.integers(0, 256, size=(900, 16), dtype=np.uint8)
    q = rng.integers(0, 256, size=(7, 16)).astype(np.float32)
    d64 = ((db.astype(np.float64)[None]
            - q.astype(np.float64)[:, None]) ** 2).sum(-1)
    ref_i = np.argsort(d64, axis=-1, kind="stable")[:, :4]
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=4)
    d, i, stats = prog.search_certified(
        q, selector="pallas", margin=8, tile_n=256, precision="int8")
    np.testing.assert_array_equal(i, ref_i)
    pl8 = prog._int8_cache
    assert pl8["offset"] == 128.0
    assert pl8["stats"]["et2_max"] == 0.0  # byte-exact, no residuals
    assert stats["fallback_queries"] + stats["certified"] == q.shape[0]
