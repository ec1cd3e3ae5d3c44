"""The persistent autotuner (knn_tpu.tuning): winner persistence and
reload round-trips, cache-key mismatches fall back to defaults, the
bitwise gate keeps broken candidates from ever winning, explicit
pallas_knobs beat the cache, and a warm cache resolves with ZERO
re-timing (pinned via the module counters — the same evidence
`python -m knn_tpu.cli tune` prints)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import importlib

from knn_tpu import tuning

# the module object (the package re-exports the autotune FUNCTION under
# the same name, so attribute access would shadow it)
autotune_mod = importlib.import_module("knn_tpu.tuning.autotune")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def data(rng):
    db = rng.normal(size=(700, 16)).astype(np.float32) * 10
    q = rng.normal(size=(9, 16)).astype(np.float32) * 10
    return db, q


@pytest.fixture
def cache_path(tmp_path):
    return str(tmp_path / "autotune.json")


def test_winner_persistence_and_reload_roundtrip(data, cache_path):
    db, q = data
    tuning.reset_counters()
    entry = tuning.autotune(db, q, 5, margin=8, grid_level="quick", runs=1,
                            cache_path=cache_path)
    assert entry["cached"] is False
    assert tuning.counters()["candidates_timed"] >= 3
    assert os.path.exists(cache_path)
    # the file is the documented format and reloads to the same winner
    raw = json.load(open(cache_path))
    assert raw["version"] == 1
    (key,) = raw["entries"]
    assert key == tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    reloaded = tuning.TuneCache(cache_path).get(key)
    assert reloaded["knobs"] == entry["knobs"]
    assert reloaded["winner_ms"] == entry["winner_ms"]
    # resolve() for the same shape returns the persisted winner
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "cache"
    assert knobs == {**tuning.DEFAULT_KNOBS, **entry["knobs"]}


def test_warm_cache_zero_retiming(data, cache_path):
    db, q = data
    tuning.autotune(db, q, 5, margin=8, grid_level="quick", runs=1,
                    cache_path=cache_path)
    tuning.reset_counters()
    entry = tuning.autotune(db, q, 5, margin=8, grid_level="quick", runs=1,
                            cache_path=cache_path)
    assert entry["cached"] is True
    c = tuning.counters()
    assert c["candidates_timed"] == 0  # ZERO re-timing on a warm cache
    assert c["tune_searches"] == 0
    assert c["cache_hits"] == 1


def test_cache_key_mismatch_falls_back_to_defaults(data, cache_path):
    db, q = data
    tuning.autotune(db, q, 5, margin=8, grid_level="quick", runs=1,
                    cache_path=cache_path)
    # ANY key field mismatch must miss: different k, n, d, metric, dtype,
    # device kind — a winner tuned for one shape says nothing elsewhere
    for kwargs in (
        dict(n=700, d=16, k=7),                       # k differs
        dict(n=701, d=16, k=5),                       # n differs
        dict(n=700, d=32, k=5),                       # d differs
        dict(n=700, d=16, k=5, metric="cosine"),      # metric differs
        dict(n=700, d=16, k=5, dtype="bfloat16"),     # dtype differs
        dict(n=700, d=16, k=5, device_kind="TPU v5e"),  # device differs
    ):
        n = kwargs.pop("n")
        d = kwargs.pop("d")
        k = kwargs.pop("k")
        knobs, info = tuning.resolve_full(n, d, k, cache_path=cache_path,
                                          **kwargs)
        assert info["source"] == "default", kwargs
        assert knobs == tuning.DEFAULT_KNOBS


def test_gate_failed_candidate_can_never_win(data, cache_path, monkeypatch):
    db, q = data
    real_search = autotune_mod._search_once

    def corrupt_streaming(queries, dbx, k, margin, knobs):
        d, i = real_search(queries, dbx, k, margin, knobs)
        if knobs["kernel"] == "streaming":
            i = np.array(i)
            i[0, 0] = (i[0, 0] + 1) % dbx.shape[0]  # one wrong neighbor
        return d, i

    monkeypatch.setattr(autotune_mod, "_search_once", corrupt_streaming)
    tuning.reset_counters()
    entry = tuning.autotune(db, q, 5, margin=8, grid_level="quick", runs=1,
                            cache_path=cache_path)
    # the corrupted candidate is recorded ineligible (never timed) and
    # cannot be selected no matter how fast it would have been
    assert entry["timings_ms"]["kernel=streaming"] is None
    assert "bitwise gate" in entry["errors"]["kernel=streaming"]
    assert entry["knobs"]["kernel"] != "streaming"
    assert tuning.counters()["candidates_gated_out"] >= 1
    # and the persisted winner keeps the poison out of later resolves
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "cache"
    assert knobs["kernel"] != "streaming"


def test_full_width_kernels_default_to_their_own_block_q(cache_path):
    """streaming/fused hold every db tile's candidates in VMEM at once:
    left alone they resolve block_q=128 (the tiled default's 256 does
    not fit them beyond SIFT); a caller's or a cached winner's block_q
    still wins."""
    assert tuning.resolve(700, 16, 5, cache_path=cache_path)["block_q"] == 256
    for kern in ("streaming", "fused"):
        knobs, info = tuning.resolve_full(
            700, 16, 5, cache_path=cache_path, overrides={"kernel": kern})
        assert info["source"] == "default"
        assert knobs["block_q"] == tuning.FULL_WIDTH_BLOCK_Q == 128
        pinned = tuning.resolve(700, 16, 5, cache_path=cache_path,
                                overrides={"kernel": kern, "block_q": 256})
        assert pinned["block_q"] == 256
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    tuning.TuneCache(cache_path).put(key, {
        "knobs": {**tuning.DEFAULT_KNOBS, "kernel": "streaming"}})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "cache"
    assert (knobs["kernel"], knobs["block_q"]) == ("streaming", 256)


def test_explicit_knobs_beat_cache(data, cache_path, rng):
    db, q = data
    # seed the cache with a NON-default winner so the override direction
    # is unambiguous
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    tuning.TuneCache(cache_path).put(key, {
        "knobs": {**tuning.DEFAULT_KNOBS, "kernel": "streaming",
                  "tile_n": 256},
        "winner_ms": 1.0,
    })
    knobs, info = tuning.resolve_full(
        700, 16, 5, cache_path=cache_path,
        overrides={"kernel": "tiled", "block_q": 16})
    assert info["source"] == "cache"
    assert knobs["kernel"] == "tiled"      # override beat the cache
    assert knobs["tile_n"] == 256          # un-overridden cache knob kept
    assert knobs["block_q"] == 16
    assert info["overridden"] == ["block_q", "kernel"]

    # end to end through ShardedKNN.search_certified: explicit args win,
    # un-overridden knobs come from the cache, and the stats record both
    from knn_tpu.parallel import ShardedKNN, make_mesh

    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    _, i_cache, st = prog.search_certified(
        q, selector="pallas", margin=8, tune_cache=cache_path)
    assert st["tuning"]["source"] == "cache"
    assert st["pallas_knobs"]["kernel"] == "streaming"  # cache winner ran
    assert st["pallas_knobs"]["tile_n"] == 256
    _, i_over, st2 = prog.search_certified(
        q, selector="pallas", margin=8, tune_cache=cache_path,
        kernel="tiled", tile_n=384)
    assert st2["pallas_knobs"]["kernel"] == "tiled"
    assert st2["pallas_knobs"]["tile_n"] == 384
    assert set(st2["tuning"]["overridden"]) == {"kernel", "tile_n"}
    # exactness is knob-independent (the certified contract)
    np.testing.assert_array_equal(i_cache, i_over)


def test_resolve_rejects_unknown_knob():
    with pytest.raises(ValueError, match="unknown pallas knob"):
        tuning.resolve(100, 8, 3, overrides={"warp_speed": 9})


def test_corrupt_cache_degrades_to_defaults(cache_path):
    with open(cache_path, "w") as f:
        f.write("{not json")
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    assert knobs == tuning.DEFAULT_KNOBS


def test_cli_tune_roundtrip_zero_retiming(tmp_path):
    """The acceptance path verbatim: `python -m knn_tpu.cli tune` on CPU
    persists a cache file; a second run resolves from it with zero
    re-timing, asserted via the counters in the CLI's JSON output."""
    cache = str(tmp_path / "cli_tune.json")
    args = [sys.executable, "-m", "knn_tpu.cli", "tune", "--n", "600",
            "--dim", "8", "--k", "3", "--queries", "8", "--margin", "4",
            "--grid", "quick", "--runs", "1", "--cache", cache]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)

    def run():
        r = subprocess.run(args, capture_output=True, text=True, env=env,
                           timeout=420)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first = run()
    assert first["cached"] is False
    assert first["counters"]["candidates_timed"] >= 3
    assert os.path.exists(cache)
    second = run()
    assert second["cached"] is True
    assert second["counters"]["candidates_timed"] == 0
    assert second["counters"]["tune_searches"] == 0
    assert second["knobs"] == first["knobs"]


def test_cache_key_carries_kernel_version_token():
    from knn_tpu.ops.pallas_knn import KERNEL_VERSION

    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    assert key.endswith(f"|kv{KERNEL_VERSION}")


def test_stale_kernel_version_entry_falls_back_to_defaults(cache_path):
    """A persisted winner keyed for an OLDER kernel build (different —
    or missing — kv token) must miss: winners are measurements of one
    kernel's code, and a changed kernel invalidates them."""
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    base = key.rsplit("|kv", 1)[0]
    cache = tuning.TuneCache(cache_path)
    # pre-token entry (the old key format) AND a wrong-version entry
    cache.put(base, {"knobs": {**tuning.DEFAULT_KNOBS,
                               "kernel": "streaming"}})
    cache.put(base + "|kv-stale", {"knobs": {**tuning.DEFAULT_KNOBS,
                                             "tile_n": 256}})
    # ... and a KERNEL_VERSION-4 entry carrying a sub-int8 winner: the
    # 4 -> 5 bump (the pq arm changed the kernel) must invalidate
    # it even though "precision": "pq" is a perfectly current knob
    from knn_tpu.ops.pallas_knn import KERNEL_VERSION

    assert KERNEL_VERSION == 11
    cache.put(base + "|kv4", {"knobs": {**tuning.DEFAULT_KNOBS,
                                        "precision": "pq",
                                        "kernel": "streaming"}})
    # ... and a version-5 winner: timed before the final select's
    # bin-merge (5 -> 6) changed the tail its timing loop runs
    cache.put(base + "|kv5", {"knobs": {**tuning.DEFAULT_KNOBS,
                                        "block_q": 128}})
    # ... and a version-7 winner: timed when the bf16x3 product formed
    # all three terms on every corpus (7 -> 8)
    cache.put(base + "|kv7", {"knobs": {**tuning.DEFAULT_KNOBS,
                                        "tile_n": 512}})
    # ... and a version-9 winner: timed when the final top-(m+2) was
    # XLA's top_k and gather at every shape (9 -> 10)
    cache.put(base + "|kv9", {"knobs": {**tuning.DEFAULT_KNOBS,
                                        "block_q": 128}})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    assert knobs == tuning.DEFAULT_KNOBS
    # a current-version entry under the same shape DOES hit
    cache.put(key, {"knobs": {**tuning.DEFAULT_KNOBS, "block_q": 16}})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "cache"
    assert knobs["block_q"] == 16


@pytest.mark.parametrize("knob,value", [
    ("binning", "lane"), ("precision", "int4"),
])
def test_version_6_winner_naming_a_removed_knob_is_never_used(
        cache_path, rng, knob, value):
    """The knob domain narrowed at KERNEL_VERSION 7: a winner persisted
    by version 6 may name a select layout or a precision the kernel no
    longer has.  Its key carries ``kv6``, so the lookup misses, the
    defaults answer (``source == "default"``), and the removed value is
    never handed to a function that no longer takes it."""
    from knn_tpu.parallel import ShardedKNN, make_mesh

    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    v6_key = key.rsplit("|kv", 1)[0] + "|kv6"
    assert v6_key != key
    v6_knobs = {**tuning.DEFAULT_KNOBS, "binning": "grouped",
                "bin_w": None, knob: value}
    tuning.TuneCache(cache_path).put(
        v6_key, {"knobs": v6_knobs, "winner_ms": 1.0})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    assert knobs == tuning.DEFAULT_KNOBS
    # and the search that resolves through that cache file runs on them
    db = rng.normal(size=(700, 16)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    _, _, stats = prog.search_certified(
        db[:4], selector="pallas", tune_cache=cache_path)
    assert stats["tuning"]["source"] == "default"
    # beside the knobs: what the program resolved for itself, from the
    # backend (interpret), from the data (terms, mxu_passes) and from
    # the launch's shape (dim_chunk(s), row_block / row_steps,
    # final_select_stage, select_merge_short) and
    # from the device's memory (operands), and how the call was cut
    # (sub_batch, batches: analysis.subbatch)
    assert {kk: v for kk, v in stats["pallas_knobs"].items()
            if kk not in ("interpret", "terms", "mxu_passes", "dim_chunk",
                          "dim_chunks", "row_block", "row_steps",
                          "final_select_stage", "select_merge_short",
                          "operands", "sub_batch", "batches",
                           "survivor_depth")
            } == tuning.DEFAULT_KNOBS
    assert (stats["pallas_knobs"]["dim_chunk"],
            stats["pallas_knobs"]["dim_chunks"]) == (128, 1)
    assert stats["pallas_knobs"]["row_steps"] == 1


def test_default_knobs_are_the_kernel_shaping_arguments():
    """The knob list has ONE home: ``DEFAULT_KNOBS`` names exactly the
    kernel-shaping keyword arguments of ``search_certified`` and of
    ``_pallas_setup`` (what is left of their signatures once the
    arguments that shape the call, not the kernel, are taken out), so a
    knob added to one of the three and not the others fails here."""
    import inspect

    from knn_tpu.parallel import ShardedKNN

    def kwargs_of(fn, *not_knobs):
        return set(inspect.signature(fn).parameters) - {"self", *not_knobs}

    assert kwargs_of(
        ShardedKNN.search_certified, "queries", "margin", "selector",
        "batch_size", "return_distances", "recall_target", "tune_cache",
        "return_sqrt", "filter_tags", "filter_range",
        "_under") == set(tuning.DEFAULT_KNOBS)
    assert kwargs_of(
        ShardedKNN._pallas_setup, "margin", "include_distances", "terms",
        "batch_rows", "call_rows", "trace_id", "acct", "masked",
        "vote", "own_rows") == set(
            tuning.DEFAULT_KNOBS)


def test_standard_grid_includes_int8_candidate():
    grid = tuning.knob_grid("standard")
    assert any(c["precision"] == "int8" for c in grid)
    # quick stays int8-free (CPU-interpret friendly minimal set)
    assert all(c["precision"] != "int8" for c in tuning.knob_grid("quick"))
    # full covers int8 x streaming (the HBM-bound cross)
    assert any(c["precision"] == "int8" and c["kernel"] == "streaming"
               for c in tuning.knob_grid("full"))


def test_grid_covers_sub_int8_arms_and_refuses_pq_fused():
    """The compressed tier enters the grid where the roofline says
    it pays: both pq db-streaming strategies sit in standard.
    pq x fused appears at NO level — the kernel
    refuses it (carry soundness unproven for reconstruction-space
    scores), so a grid that emitted it would crash the tuner."""
    std = tuning.knob_grid("standard")
    assert any(c["precision"] == "pq" and c["kernel"] == "streaming"
               for c in std)
    assert any(c["precision"] == "pq" and c["kernel"] == "tiled"
               for c in std)
    for level in ("quick", "standard", "full"):
        assert all(not (c["precision"] == "pq" and c["kernel"] == "fused")
                   for c in tuning.knob_grid(level)), level
    # quick stays sub-int8-free (CPU-interpret friendly minimal set)
    assert all(c["precision"] != "pq"
               for c in tuning.knob_grid("quick"))


def test_gated_out_int8_candidate_can_never_win(data, cache_path,
                                                monkeypatch):
    """The acceptance clause verbatim: the bitwise end-result gate
    applies to the int8 candidate unchanged, and a gated-out int8
    candidate can never win — however fast it would have timed."""
    db, q = data
    real_search = autotune_mod._search_once

    def corrupt_int8(queries, dbx, k, margin, knobs):
        d, i = real_search(queries, dbx, k, margin, knobs)
        if knobs["precision"] == "int8":
            i = np.array(i)
            i[0, 0] = (i[0, 0] + 1) % dbx.shape[0]  # one wrong neighbor
        return d, i

    monkeypatch.setattr(autotune_mod, "_search_once", corrupt_int8)
    tuning.reset_counters()
    grid = [dict(tuning.DEFAULT_KNOBS),
            {**tuning.DEFAULT_KNOBS, "precision": "int8"}]
    entry = tuning.autotune(db, q, 5, margin=8, grid=grid, runs=1,
                            cache_path=cache_path)
    assert entry["timings_ms"]["precision=int8"] is None  # never timed
    assert "bitwise gate" in entry["errors"]["precision=int8"]
    assert entry["knobs"]["precision"] != "int8"
    assert tuning.counters()["candidates_gated_out"] >= 1


def test_int8_candidate_eligible_when_results_match(rng, cache_path):
    """On int8-exactly-representable data the int8 candidate passes the
    bitwise gate (final results == reference) and is timed — eligibility
    is decided by the gate, not by precision prejudice."""
    db = rng.integers(-100, 101, size=(700, 16)).astype(np.float32)
    db[:, 0] = 127.0  # pins every row scale at exactly 1.0
    q = rng.integers(-100, 101, size=(9, 16)).astype(np.float32)
    q[:, 0] = 127.0
    grid = [dict(tuning.DEFAULT_KNOBS),
            {**tuning.DEFAULT_KNOBS, "precision": "int8"}]
    entry = tuning.autotune(db, q, 5, margin=8, grid=grid, runs=1,
                            cache_path=cache_path)
    assert entry["timings_ms"]["precision=int8"] is not None
    assert "precision=int8" not in entry["errors"]


# -- the "throughput" grid profile (bulk kNN-join satellite) --------------
def test_throughput_profile_grid_is_a_strict_superset():
    """The throughput profile EXTENDS each level with the large-block_q
    ladder; the latency grids (and therefore every existing winner)
    are byte-identical to the pre-profile ones."""
    for level in ("quick", "standard", "full"):
        lat = tuning.knob_grid(level)
        thr = tuning.knob_grid(level, profile="throughput")
        assert lat == tuning.knob_grid(level, profile="latency")
        assert len(thr) > len(lat)
        for cand in lat:
            assert cand in thr
        # the extension IS the large-superblock ladder
        assert any((c.get("block_q") or 0) >= 512 for c in thr), level
        assert all((c.get("block_q") or 0) < 512 for c in lat), level
    with pytest.raises(ValueError, match="profile"):
        tuning.knob_grid("standard", profile="bulk")


def test_throughput_grid_fits_the_vmem_budget_everywhere():
    """No fits-nowhere arms: every throughput candidate places on at
    least one known device kind under the VMEM budget model at the
    headline shape — the same pricing check_vmem sweeps in CI."""
    from knn_tpu.analysis import vmem

    for knobs in tuning.knob_grid("full", profile="throughput"):
        full = {**tuning.DEFAULT_KNOBS, **knobs}
        assert vmem.fits_some_kind(full, **vmem.HEADLINE_SHAPE), knobs


def test_profile_cache_keys_are_disjoint_and_latency_is_unchanged():
    from knn_tpu.tuning.cache import cache_key

    assert tuning.PROFILES == ("latency", "throughput")
    base = cache_key("TPU v5e", 1_000_000, 128, 100, "l2", "bf16x3")
    lat = cache_key("TPU v5e", 1_000_000, 128, 100, "l2", "bf16x3",
                    profile="latency")
    thr = cache_key("TPU v5e", 1_000_000, 128, 100, "l2", "bf16x3",
                    profile="throughput")
    assert lat == base  # old persisted winners keep hitting
    assert thr == base + "|throughput"  # disjoint rows, never clobber
    with pytest.raises(ValueError, match="profile"):
        cache_key("TPU v5e", 1, 1, 1, "l2", None, profile="join")


def test_autotune_throughput_profile_keys_its_own_row(data, cache_path):
    db, q = data
    grid = [dict(tuning.DEFAULT_KNOBS)]
    entry = tuning.autotune(db, q, 5, margin=8, grid=grid, runs=1,
                            cache_path=cache_path, profile="throughput")
    assert entry["profile"] == "throughput"
    raw = json.load(open(cache_path))
    (key,) = raw["entries"]
    assert key == tuning.cache_key("cpu", 700, 16, 5, "l2", None,
                                   profile="throughput")
    assert key.endswith("|throughput")
    # a latency resolve for the same shape never sees the join winner
    _, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    _, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path,
                                  profile="throughput")
    assert info["source"] == "cache"
    assert info["profile"] == "throughput"
