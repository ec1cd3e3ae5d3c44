"""The roofline-gap campaign's acceptance surface: the fused-select
kernel arm (ops.pallas_knn kernel="fused" — in-loop carry + sound
exclusion-bound early-out, bitwise-identical final results), the
select-overlap roofline semantics
(serialized select for non-fused kernels, overlapped for fused —
introduced at MODEL_VERSION 2, carried by 3), and
the roofline-pruned autotuner (auditable, winner-safe, off by
default)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import tuning
from knn_tpu.obs import roofline
from knn_tpu.ops.pallas_knn import (
    BIN_W,
    KERNEL_VERSION,
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


# --- roofline select-overlap semantics (MODEL_VERSION 2, kept by 3) -----


def test_roofline_v2_select_overlap_semantics():
    """Pinned: non-fused kernels serialize the select after the stream
    (ceiling = nq / (max(t_hbm, t_mxu) + t_vpu)); the fused kernel
    overlaps it (max of all three) — so the fused int8/streaming arm's
    modeled ceiling RISES above the non-fused one, which is the gap the
    in-kernel fused select exists to close."""
    base = dict(n=1_000_000, d=128, k=100, nq=4096,
                device_kind="TPU v5 lite", backend="tpu")
    m8s = roofline.pallas_cost_model(precision="int8",
                                     kernel="streaming", **base)
    m8f = roofline.pallas_cost_model(precision="int8", kernel="fused",
                                     **base)
    assert m8s["select_overlapped"] is False
    assert m8f["select_overlapped"] is True
    assert m8f["ceiling_qps"] > m8s["ceiling_qps"]
    assert m8f["bound_class"] == m8s["bound_class"] == "vpu_select_bound"
    # the formulas, recomputed from the block's own term times
    t = m8s["term_times_s"]
    assert m8s["ceiling_qps"] == pytest.approx(
        4096 / (max(t["hbm_bound"], t["mxu_bound"])
                + t["vpu_select_bound"]), rel=1e-3)
    t = m8f["term_times_s"]
    assert m8f["ceiling_qps"] == pytest.approx(
        4096 / max(t.values()), rel=1e-3)
    # v3 = the calibrated model (tests/test_calibrate.py owns the
    # overlay semantics); v4 = the multi-host DCN merge term
    # (tests/test_multihost.py/test_roofline.py own it); v5 = the IVF
    # probed-bytes term (tests/test_ivf.py owns it); v6 = the sub-int8
    # compressed-tier widths (tests/test_roofline.py owns it); v7 = the
    # bulk-join amortized db-bytes + h2d terms (tests/test_join.py owns
    # it); v8 = the tiled kernel's query block streams once a query
    # block (tests/test_roofline.py owns it); the select-overlap
    # formulas above are pinned version-independently
    assert roofline.MODEL_VERSION == 8
    # a fused config whose carry would exceed MAX_CARRY_DEPTH disarms
    # in the kernel — the model mirrors the disarm and falls back to
    # the serialized ceiling, so pruning/--best can never hold other
    # candidates to a ceiling no real config reaches
    deep = roofline.pallas_cost_model(precision="int8", kernel="fused",
                                      **{**base, "k": 1024})
    assert deep["select_overlapped"] is False
    assert deep["ceiling_qps"] == roofline.pallas_cost_model(
        precision="int8", kernel="streaming",
        **{**base, "k": 1024})["ceiling_qps"]
    # the cache token follows the model version: pre-bump entries miss
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    assert f"|rl{roofline.MODEL_VERSION}|" in key
    assert roofline.validate_block(
        roofline.attribute(m8f, 100.0)) == []
    with pytest.raises(ValueError, match="kernel"):
        roofline.pallas_cost_model(kernel="warp", **base)


# --- roofline-pruned autotuning -----------------------------------------


def test_prune_candidates_semantics():
    """The pruning function's guarantees: the best-modeled candidate is
    always kept, every pruned record's ceiling sits under threshold x
    best (auditable line by line), and a candidate the model cannot
    price is kept — a model gap widens the search, never hides."""
    grid = tuning.knob_grid("quick") + [
        {**tuning.DEFAULT_KNOBS, "precision": "bogus"}]  # unpriceable
    kept, pruned, best = tuning.prune_candidates(
        grid, n=1_000_000, d=128, k=100, nq=4096, threshold=0.8,
        device_kind="TPU v5 lite", backend="tpu")
    assert best is not None and best > 0
    assert len(kept) + len(pruned) == len(grid)
    for rec in pruned.values():
        assert rec["ceiling_qps"] < 0.8 * rec["best_ceiling_qps"]
        assert rec["best_ceiling_qps"] == best
    # the argmax-ceiling candidate survives any threshold <= 1: a kept
    # candidate must reach the best ceiling when re-modeled
    kept_ceilings = []
    for cand in kept:
        knobs = {**tuning.DEFAULT_KNOBS, **cand}
        if knobs["precision"] not in roofline.DB_ELEM_BYTES:
            continue  # the deliberately unpriceable candidate
        kept_ceilings.append(roofline.pallas_cost_model(
            n=1_000_000, d=128, k=100, nq=4096,
            precision=knobs["precision"], kernel=knobs["kernel"],
            grid_order=knobs["grid_order"], tile_n=knobs["tile_n"],
            block_q=knobs["block_q"], device_kind="TPU v5 lite",
            backend="tpu")["ceiling_qps"])
    assert best in kept_ceilings
    # the unpriceable candidate was kept, not silently dropped
    assert any(c.get("precision") == "bogus" for c in kept)


def test_autotune_pruning_never_hides_the_winner(rng, tmp_path):
    """THE acceptance property: with pruning OFF, run the full
    gate+timing search and take its winner; the pruning decision (at
    its threshold) must keep that winner — a gated-out-by-model
    candidate that would have won is a test failure, by design."""
    from knn_tpu.tuning.autotune import _label

    db = rng.normal(size=(700, 16)).astype(np.float32) * 10
    q = rng.normal(size=(9, 16)).astype(np.float32) * 10
    entry = tuning.autotune(db, q, 5, margin=8, grid_level="quick",
                            runs=1,
                            cache_path=str(tmp_path / "off.json"))
    assert "pruning" not in entry  # off by default: nothing modeled away
    winner = entry["winner"]
    _, pruned, _ = tuning.prune_candidates(
        tuning.knob_grid("quick"), n=700, d=16, k=5,
        nq=9, threshold=0.5, device_kind="cpu", backend="cpu")
    assert winner not in pruned, (
        f"roofline pruning at 0.5 would have hidden the measured "
        f"winner {winner!r}: {pruned}")
    # and an aggressive prune still completes with a kept winner plus a
    # full audit trail
    tuning.reset_counters()
    entry2 = tuning.autotune(db, q, 5, margin=8, grid_level="quick",
                             runs=1, prune=1.0,
                             cache_path=str(tmp_path / "on.json"))
    info = entry2["pruning"]
    assert info["threshold"] == 1.0
    assert info["candidates_pruned"] == len(info["pruned"])
    assert entry2["winner"] not in info["pruned"]
    for label, rec in info["pruned"].items():
        assert entry2["timings_ms"][label] is None  # never timed
        assert entry2["errors"][label].startswith("roofline-pruned")
        assert rec["ceiling_qps"] < rec["best_ceiling_qps"] * 1.0
    if info["candidates_pruned"]:
        assert tuning.counters()["candidates_pruned"] == \
            info["candidates_pruned"]
    # the winner label arithmetic is shared with the tune entry
    assert _label({**tuning.DEFAULT_KNOBS}) == "defaults"


def test_autotune_prune_env_switch(rng, tmp_path, monkeypatch):
    monkeypatch.setenv(tuning.PRUNE_ENV, "1.0")
    db = rng.normal(size=(700, 16)).astype(np.float32) * 10
    q = rng.normal(size=(9, 16)).astype(np.float32) * 10
    entry = tuning.autotune(db, q, 5, margin=8, grid_level="quick",
                            runs=1, cache_path=str(tmp_path / "t.json"))
    assert entry["pruning"]["threshold"] == 1.0
    # a typo'd value degrades to the exhaustive search, never a prune
    monkeypatch.setenv(tuning.PRUNE_ENV, "lots")
    assert tuning.prune_threshold_from_env() is None
    monkeypatch.setenv(tuning.PRUNE_ENV, "0")
    assert tuning.prune_threshold_from_env() is None
    monkeypatch.setenv(tuning.PRUNE_ENV, "7")  # clamps: best always kept
    assert tuning.prune_threshold_from_env() == 1.0


# --- defaults promotion (satellite) -------------------------------------


def test_block_q_256_promoted_with_kernel_version_bump(rng):
    """The r05-proven winner is the default at the tuning layer, the
    cache re-keys (kv4), and block_q is result-invariant — the whole
    basis of promoting it without touching the bitwise contract."""
    assert tuning.DEFAULT_KNOBS["block_q"] == 256
    assert KERNEL_VERSION >= 4
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    assert key.endswith(f"|kv{KERNEL_VERSION}")
    # block_q re-blocks the query grid only: results are bitwise
    # invariant to it (per-row arithmetic untouched)
    db = rng.normal(size=(3 * BIN_W + 17, 12)).astype(np.float32) * 10
    q = rng.normal(size=(16, 12)).astype(np.float32) * 10
    outs = [local_certified_candidates(
        jnp.asarray(q), jnp.asarray(db), m=9, block_q=bq,
        tile_n=2 * BIN_W, interpret=True) for bq in (8, 16)]
    for a, b in zip(*outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the fused arm rides the standard grid (the vpu-select attack)
    grid = tuning.knob_grid("standard")
    assert any(c["kernel"] == "fused" and c["precision"] == "int8"
               for c in grid)
    assert all(not (c["kernel"] == "fused"
                    and c["final_select"] == "approx") for c in grid)


# --- cli roofline --best ------------------------------------------------


def test_cli_roofline_best(capsys):
    from knn_tpu import cli

    rc = cli.main(["roofline", "--n", "1000000", "--dim", "128",
                   "--k", "100", "--device-kind", "TPU v5 lite",
                   "--best", "5"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "kernel=fused" in out  # the modeled frontier is the fused arm
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["model_version"] == roofline.MODEL_VERSION
    best = tail["best"]
    assert len(best) == 5
    assert all(b["bound_class"] in roofline.BOUND_CLASSES for b in best)
    # ranked: non-increasing modeled ceilings
    ceilings = [b["ceiling_qps"] for b in best]
    assert ceilings == sorted(ceilings, reverse=True)
