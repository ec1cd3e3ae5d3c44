"""The roofline cost model (knn_tpu.obs.roofline): byte terms pinned
against the ACTUAL kernel operand arrays' nbytes, ceilings that bound
real interpret-mode runs, the pinned r05 SIFT1M bound-class
attribution, the tuning-cache version bump, registry publication, and
the obs-off no-op — the acceptance surface of the roofline ISSUE."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import obs, tuning
from knn_tpu.obs import health, roofline
from knn_tpu.obs import names as mn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_roofline_store():
    roofline.reset()
    yield
    roofline.reset()
    obs.reset()
    health.reset()


# --- byte counts vs actual operand nbytes ------------------------------


def _actual_operand_nbytes(db, precision):
    """Build the db-side operand arrays exactly as
    ops.pallas_knn._bin_candidates does and return their real nbytes."""
    n = db.shape[0]
    if precision == "bf16x3":
        th = db.astype(jnp.bfloat16)
        tl = (db - th.astype(jnp.float32)).astype(jnp.bfloat16)
        values = th.nbytes + tl.nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    elif precision == "bf16x3f":
        th = db.astype(jnp.bfloat16)
        tl = (db - th.astype(jnp.float32)).astype(jnp.bfloat16)
        t3 = jnp.concatenate([th, tl, th], axis=1)
        values = t3.nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    elif precision == "int8":
        from knn_tpu.ops.quantize import quantize_rows

        ti, ts = quantize_rows(db)
        values = ti.nbytes
        tn = jnp.sum(db * db, axis=-1)
        aux = jnp.concatenate([
            jnp.broadcast_to(tn[None, :], (8, n)),
            jnp.broadcast_to(ts[None, :].astype(jnp.float32), (8, n)),
        ], axis=0).nbytes
    elif precision == "pq":
        # the streamed operand is the [N, ceil(d/dsub)] uint8 code
        # array (shape-determined — training moves no extra bytes)
        # plus the 8-row pad-fill carrier
        m_sub = -(-db.shape[1] // 4)
        values = jnp.zeros((n, m_sub), jnp.uint8).nbytes
        aux = jnp.broadcast_to(
            jnp.zeros((n,), jnp.float32)[None, :], (8, n)).nbytes
    else:  # highest streams the raw f32 rows
        values = db.astype(jnp.float32).nbytes
        aux = jnp.broadcast_to(
            jnp.sum(db * db, axis=-1)[None, :], (8, n)).nbytes
    return int(values), int(aux)


@pytest.mark.parametrize("precision",
                         ["bf16x3", "bf16x3f", "int8", "pq", "highest"])
@pytest.mark.parametrize("kernel", ["tiled", "streaming"])
def test_db_byte_terms_match_actual_operand_nbytes(rng, precision, kernel):
    """Property: the model's per-pass db byte terms equal the nbytes of
    the arrays the kernel really streams, for both db-streaming
    strategies across the f32/bf16/int8 operand families."""
    n, d = 512, 128
    db = jnp.asarray(rng.random((n, d), dtype=np.float32) * 128)
    values_b, aux_b = _actual_operand_nbytes(db, precision)
    model_b = roofline.db_operand_nbytes(n, d, precision)
    assert model_b["db_values"] == values_b
    assert model_b["db_aux"] == aux_b
    # and the full model's hbm term is exactly passes x those bytes
    m = roofline.pallas_cost_model(
        n=n, d=d, k=5, nq=64, precision=precision, kernel=kernel,
        tile_n=128, block_q=32, device_kind="TPU v5e")
    passes = m["terms"]["hbm"]["db_passes"]
    assert passes == -(-64 // 32)  # query-major: one pass per block
    assert m["terms"]["hbm"]["bytes"]["db_stream"] == passes * values_b
    assert m["terms"]["hbm"]["bytes"]["db_aux"] == passes * aux_b


@pytest.mark.parametrize("kernel,grid_order,fetches", [
    ("tiled", "query_major", 1), ("tiled", "db_major", 62),
    ("streaming", "query_major", 62)])
def test_the_tiled_kernels_query_block_streams_once(kernel, grid_order,
                                                    fetches):
    """MODEL_VERSION 8: the tiled kernel multiplies the whole padded
    width a step (a tile too wide for VMEM is cut by rows), so under
    ``query_major`` its query block's index moves with the query block
    alone: the queries stream once, whatever the tiles and their row
    blocks; the db stream does not move."""
    kw = dict(n=1_000_000, d=960, k=100, nq=4096, block_q=256,
              device_kind="TPU v5e")
    b = roofline.pallas_cost_model(kernel=kernel, grid_order=grid_order,
                                   **kw)["terms"]["hbm"]["bytes"]
    assert b["queries"] == fetches * 4096 * 960 * 4
    # (db_major saves no row stream here: GIST's tile is cut by rows,
    # whose blocks cycle with the query blocks)
    assert b["db_stream"] == 16 * 1_000_000 * 960 * 4
    i8 = roofline.pallas_cost_model(
        kernel=kernel, grid_order=grid_order, precision="int8",
        **kw)["terms"]["hbm"]["bytes"]
    assert i8["queries"] == fetches * 4096 * (960 + 128 * 4)


def test_geometry_defaults_mirror_kernel_constants():
    """The jax-free module mirrors the kernel's geometry defaults; a
    drift here would silently mis-model every default-knob config."""
    from knn_tpu.ops import pallas_knn as pk

    assert roofline.TILE_N_DEFAULT == pk.TILE_N
    assert roofline.BLOCK_Q_DEFAULT == pk.BLOCK_Q
    assert roofline.BIN_W == pk.BIN_W
    assert roofline.DIM_CHUNK == pk.DIM_CHUNK
    # the fused-arm disarm threshold the overlapped-ceiling call mirrors
    assert roofline.MAX_CARRY_DEPTH == pk.MAX_CARRY_DEPTH
    n_bins, surv, out_w, bound_w = pk._geometry(pk.TILE_N)
    assert surv == roofline.SURVIVORS_GROUPED_DEFAULT
    # grouped default survivors=2 -> the out/bound widths the candidate
    # output term assumes
    assert out_w == surv * pk.BIN_W and bound_w == pk.BIN_W


# --- MODEL_VERSION 6: the sub-int8 compressed tier ----------------------


def test_sub_int8_row_bytes_pinned():
    """Pinned byte ratios at SIFT dims (docs/PERF.md precision
    ladder): int8 streams a quarter of the f32 row, pq at
    the default dsub=4 streams m = ceil(d/4) code bytes — m/(4d) of
    the f32 row, 1/16 at d=128."""
    from knn_tpu.analysis import widths

    f32 = widths.db_row_bytes(128, "highest")
    i8 = widths.db_row_bytes(128, "int8")
    pq = widths.db_row_bytes(128, "pq", dsub=4)
    assert (f32, i8, pq) == (512, 128, 32)
    assert pq / f32 == widths.pq_nsub(128, 4) / (4 * 128) == 1 / 16
    # int8's aux stacks 8 scale rows under the 8 norm rows every other
    # arm streams
    a = roofline.db_operand_nbytes(1000, 128, "bf16x3")
    b = roofline.db_operand_nbytes(1000, 128, "int8")
    assert 2 * a["db_aux"] == b["db_aux"]


def test_pq_model_prices_lut_width_and_composes_with_probes():
    """pq's full-db stream is NOT a free lunch: the one-hot LUT
    contraction prices at m*ncodes MXU width, so the full stream is
    mxu_bound; composed with IVF probing (MODEL_VERSION 5 knobs) the
    byte and flop reductions multiply and the ceiling climbs."""
    base = dict(n=1_000_000, d=128, k=10, nq=8, kernel="streaming",
                block_q=8, device_kind="TPU v5e", backend="tpu")
    full = roofline.pallas_cost_model(precision="pq", **base)
    assert full["bound_class"] == "mxu_bound"
    probed = roofline.pallas_cost_model(precision="pq", nprobe=32,
                                        ncentroids=1024, **base)
    assert probed["ceiling_qps"] > full["ceiling_qps"]
    assert roofline.validate_block(probed) == []


# --- ceilings bound measured reality -----------------------------------


def test_interpret_mode_run_sits_under_the_cpu_ceiling(rng):
    """roofline_pct <= 1 + tolerance against a real (interpret-mode,
    CPU) run: even against the deliberately modest generic-CPU fallback
    peaks, an interpreted kernel can never beat its own roofline."""
    import time

    from knn_tpu.ops.pallas_knn import knn_search_pallas

    n, d, k, nq = 2048, 64, 5, 16
    db = rng.random((n, d), dtype=np.float32) * 128
    q = rng.random((nq, d), dtype=np.float32) * 128
    import jax

    d_, i_, _ = knn_search_pallas(q, db, k, tile_n=512)  # compile/warm
    jax.block_until_ready((d_, i_))
    t0 = time.perf_counter()
    out = knn_search_pallas(q, db, k, tile_n=512)
    jax.block_until_ready(out[:2])
    qps = nq / (time.perf_counter() - t0)
    model = roofline.pallas_cost_model(
        n=n, d=d, k=k, nq=nq, tile_n=512, backend="cpu")
    assert model["estimated"] is True
    att = roofline.attribute(model, qps)
    assert att["roofline_pct"] is not None
    assert att["roofline_pct"] <= 1.05


def test_r05_sift1m_curated_line_is_hbm_bound():
    """Pinned regression: the r05 SIFT1M curated line (bf16x3, tiled,
    query_major on a v5e) attributes its MFU gap to the db-streaming
    term — hbm_bound, at a small measured fraction of the ceiling.
    This is THE named gap ROADMAP item 1's kernel campaign attacks."""
    with open(os.path.join(REPO, "tests", "fixtures",
                           "bench_line_sift1m_v5e.json")) as f:
        rec = json.load(f)
    block = roofline.block_for_bench_line(rec)
    assert block is not None
    assert block["estimated"] is False
    assert block["bound_class"] == "hbm_bound"
    # measured 24.2k device-phase q/s against a ~184k ceiling
    assert 0.05 < block["roofline_pct"] < 0.3
    assert roofline.validate_block(block) == []


def test_bound_class_moves_with_the_config():
    """The model names a different gap per campaign lever (the whole
    point of attribution): int8 x streaming leaves the select as the
    wall, db_major at single-chunk dims removes the streaming term,
    and the XLA exact path is selection-bound."""
    base = dict(n=1_000_000, d=128, k=100, nq=4096,
                device_kind="TPU v5 lite", backend="tpu")
    assert roofline.pallas_cost_model(**base)["bound_class"] == "hbm_bound"
    m8 = roofline.pallas_cost_model(
        precision="int8", kernel="streaming", **base)
    assert m8["bound_class"] == "vpu_select_bound"
    assert m8["ceiling_qps"] > roofline.pallas_cost_model(
        **base)["ceiling_qps"]
    mdb = roofline.pallas_cost_model(grid_order="db_major", **base)
    assert mdb["bound_class"] == "mxu_bound"
    assert mdb["terms"]["hbm"]["db_passes"] == 1
    mx = roofline.xla_cost_model(
        selector="exact", dtype="bfloat16", batch=512, **base)
    assert mx["bound_class"] == "vpu_select_bound"
    # approx runs two db passes — its hbm/mxu terms double
    ma = roofline.xla_cost_model(
        selector="approx", dtype="bfloat16", batch=512, **base)
    assert ma["terms"]["mxu"]["flops_executed"] == \
        2 * mx["terms"]["mxu"]["flops_executed"]


def test_cpu_fallback_peaks_flag_estimated():
    # an accelerator the table does not know is an error, not a default
    with pytest.raises(ValueError, match="PEAKS_BY_KIND"):
        roofline.pallas_cost_model(
            n=10_000, d=32, k=5, nq=64, device_kind="TPU v99",
            backend="tpu")
    m2 = roofline.pallas_cost_model(
        n=10_000, d=32, k=5, nq=64, device_kind="TPU v5e", backend="cpu")
    assert m2["estimated"] is True  # cpu backend beats a known kind
    line = {"metric": "knn_qps_x_n10000_d32_k5", "mode": "exact",
            "value": 100.0, "backend": "cpu", "compute_dtype": "float32",
            "batch": 32}
    block = roofline.block_for_bench_line(line)
    assert block["estimated"] is True
    assert block["roofline_pct"] is not None


# --- validation --------------------------------------------------------


def test_validate_block_accepts_real_and_rejects_malformed():
    good = roofline.attribute(
        roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), 50.0)
    assert roofline.validate_block(good) == []
    assert roofline.validate_block("nope")  # not a dict
    assert roofline.validate_block({})  # everything missing
    bad = dict(good, bound_class="gpu_bound")
    assert any("bound_class" in e for e in roofline.validate_block(bad))
    bad = dict(good, ceiling_qps=-3)
    assert any("ceiling_qps" in e for e in roofline.validate_block(bad))
    bad = dict(good, terms={"hbm": {"time_s": -1}})
    assert roofline.validate_block(bad)


# --- tuning cache integration ------------------------------------------


def test_cache_key_carries_roofline_token_and_pre_roofline_misses(
        tmp_path):
    """Satellite: the cache-key version bump — entries written before
    the roofline fields existed (no |rl token) must miss and fall back
    to defaults cleanly; current-token entries hit and surface their
    persisted attribution through resolve_full."""
    cache_path = str(tmp_path / "tune.json")
    key = tuning.cache_key("cpu", 700, 16, 5, "l2", None)
    assert f"|rl{roofline.MODEL_VERSION}|" in key
    # a pre-roofline entry: same shape, no rl token (the old format)
    pre = key.replace(f"|rl{roofline.MODEL_VERSION}", "")
    cache = tuning.TuneCache(cache_path)
    cache.put(pre, {"knobs": {**tuning.DEFAULT_KNOBS,
                              "kernel": "streaming"}})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    assert knobs == tuning.DEFAULT_KNOBS
    # a STALE-token entry (the MODEL_VERSION 5 key, before the
    # compressed-tier arms re-priced the grid) must miss the same way:
    # the version bump self-invalidates every pre-6 winner
    stale = key.replace(f"|rl{roofline.MODEL_VERSION}|", "|rl5|")
    assert stale != key
    cache.put(stale, {"knobs": {**tuning.DEFAULT_KNOBS,
                                "kernel": "streaming"}})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path)
    assert info["source"] == "default"
    # a current entry carrying the winner's attribution DOES hit, and
    # the verdict rides the resolve info + the /statusz store
    block = roofline.attribute(
        roofline.pallas_cost_model(n=700, d=16, k=5, nq=64), 500.0)
    cache.put(key, {"knobs": dict(tuning.DEFAULT_KNOBS),
                    "roofline_pct": block["roofline_pct"],
                    "bound_class": block["bound_class"],
                    "roofline": block})
    knobs, info = tuning.resolve_full(700, 16, 5, cache_path=cache_path,
                                      device_kind="cpu")
    assert info["source"] == "cache"
    assert info["roofline_pct"] == block["roofline_pct"]
    assert info["bound_class"] == block["bound_class"]
    reports = roofline.last_reports()
    label = roofline.config_label(700, 16, 5, device_kind="cpu")
    assert label in reports
    assert reports[label]["bound_class"] == block["bound_class"]


def test_autotune_persists_winner_attribution(rng, tmp_path):
    """The autotuner reports percent-of-roofline per candidate and
    persists the winner's verdict in the cache entry."""
    cache_path = str(tmp_path / "tune.json")
    db = rng.random((768, 16), np.float32) * 128
    q = rng.random((8, 16), np.float32) * 128
    entry = tuning.autotune(db, q, 5, grid_level="quick", runs=1,
                            cache_path=cache_path)
    assert entry["bound_class"] in roofline.BOUND_CLASSES
    assert 0 < entry["roofline_pct"] <= 1.05
    assert roofline.validate_block(entry["roofline"]) == []
    # every TIMED candidate got an attribution
    timed = [lbl for lbl, ms in entry["timings_ms"].items()
             if ms is not None]
    for lbl in timed:
        cand = entry["roofline_per_candidate"][lbl]
        assert cand["bound_class"] in roofline.BOUND_CLASSES
        assert cand["roofline_pct"] > 0
    # the persisted entry round-trips the fields on a warm read
    warm = tuning.autotune(db, q, 5, grid_level="quick", runs=1,
                           cache_path=cache_path)
    assert warm["cached"] is True
    assert warm["roofline_pct"] == entry["roofline_pct"]


# --- registry / statusz / obs-off --------------------------------------


def test_publish_exports_metrics_and_statusz_renders():
    block = roofline.attribute(
        roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8,
                                   device_kind="TPU v5e",
                                   backend="tpu"), 100.0)
    roofline.publish("TPU v5e|n1000|d16|k5|l2|float32", block)
    snap = obs.snapshot()
    series = snap[mn.ROOFLINE_PCT]["series"]
    assert series[0]["labels"]["config"] == \
        "TPU v5e|n1000|d16|k5|l2|float32"
    assert series[0]["value"] == block["roofline_pct"]
    bounds = {(s["labels"]["class"], s["value"])
              for s in snap[mn.ROOFLINE_BOUND]["series"]}
    assert (block["bound_class"], 1.0) in bounds
    assert obs.counter(mn.ROOFLINE_EVALUATIONS).get() == 1.0
    text = obs.prometheus_text()
    assert "knn_tpu_roofline_ceiling_qps" in text
    rep = health.report()
    assert "TPU v5e|n1000|d16|k5|l2|float32" in rep["roofline"]
    rendered = health.render_text(rep)
    assert "roofline TPU v5e|n1000|d16|k5|l2|float32" in rendered
    assert block["bound_class"] in rendered


def test_publish_is_a_noop_when_obs_disabled():
    obs.reset(enabled=False)
    try:
        block = roofline.attribute(
            roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), 10.0)
        roofline.publish("cpu|n1000|d16|k5|l2|float32", block)
        assert roofline.last_reports() == {}
        assert "knn_tpu_roofline" not in obs.prometheus_text()
    finally:
        obs.reset()


def test_last_reports_store_is_bounded():
    block = roofline.attribute(
        roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), 10.0)
    for i in range(roofline._LAST_MAX + 4):
        roofline.publish(f"cpu|n{i}|d16|k5|l2|float32", block)
    assert len(roofline.last_reports()) == roofline._LAST_MAX
    # the publish-once dedup survives the bounded store's eviction —
    # otherwise a warm-cache hot path serving many configs would
    # re-publish (and re-emit events) on every resolve
    assert "cpu|n0|d16|k5|l2|float32" not in roofline.last_reports()
    assert roofline.was_published("cpu|n0|d16|k5|l2|float32")


# --- profiler ----------------------------------------------------------


def test_profiler_gates(tmp_path, monkeypatch):
    from knn_tpu.obs import profiler

    # no env, no flag -> no capture, not even a directory
    monkeypatch.delenv(profiler.PROFILE_ENV, raising=False)
    with profiler.device_trace("sect") as tdir:
        assert tdir is None
    # env gate honors the obs switch
    monkeypatch.setenv(profiler.PROFILE_ENV, str(tmp_path / "amb"))
    obs.reset(enabled=False)
    try:
        assert profiler.profile_dir() is None
        # ... but an explicit flag is an explicit request either way
        with profiler.device_trace("m|ode x",
                                   base_dir=str(tmp_path / "exp")) as td:
            assert td == str(tmp_path / "exp" / "m_ode_x")
            jnp.square(jnp.arange(4.0)).block_until_ready()
        assert os.path.isdir(td)
    finally:
        obs.reset()
    # obs back on: the env gate opens
    with profiler.device_trace("tune") as td:
        assert td == str(tmp_path / "amb" / "tune")
        jnp.square(jnp.arange(4.0)).block_until_ready()
    assert os.path.isdir(td)
    events = [e for e in obs.get_event_log().recent()
              if e.get("name") == "profiler.trace"]
    assert events and events[-1]["trace_dir"] == td


# --- cli ---------------------------------------------------------------


def test_cli_roofline_subcommand(capsys):
    from knn_tpu import cli

    rc = cli.main(["roofline", "--n", "1000000", "--dim", "128",
                   "--k", "100", "--device-kind", "TPU v5 lite",
                   "--qps", "24199.3"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "hbm_bound" in out
    tail = json.loads(out.strip().splitlines()[-1])
    assert tail["bound_class"] == "hbm_bound"
    # MODEL_VERSION 2: the non-fused select serializes after the stream
    # (max(hbm, mxu) + vpu), so the default-knob SIFT ceiling is ~118k
    # and the r05 24.2k device phase reads ~21% of roofline
    assert tail["roofline_pct"] == pytest.approx(0.206, abs=0.01)
    rc = cli.main(["roofline", "--n", "100000", "--dim", "960",
                   "--k", "10", "--selector", "approx",
                   "--dtype", "bfloat16", "--batch", "512", "--json"])
    assert rc == 0
    block = json.loads(capsys.readouterr().out)
    assert roofline.validate_block(block) == []


# --- MODEL_VERSION 4: the multi-host DCN merge term ---------------------

def test_dcn_term_only_on_multihost_blocks():
    base = dict(n=1_000_000, d=128, k=10, nq=4096,
                device_kind="TPU v5e", backend="tpu", num_devices=8)
    single = roofline.pallas_cost_model(precision="int8", **base)
    multi = roofline.pallas_cost_model(precision="int8", db_hosts=4,
                                       dcn_merge="ring", **base)
    assert "dcn" not in single["terms"]
    dcn = multi["terms"]["dcn"]
    from knn_tpu.parallel.crossover import merge_bytes

    assert dcn["bytes"] == merge_bytes(4096, 10, 4, "ring")
    assert dcn["hosts"] == 4 and dcn["strategy"] == "ring"
    # the DCN merge serializes after compute: ceiling strictly drops
    assert multi["ceiling_qps"] < single["ceiling_qps"]
    # recompute the combined-time formula from the block's own terms
    # (tiled kernel: select serialized, then the DCN merge after it)
    t = multi["term_times_s"]
    assert multi["select_overlapped"] is False
    expect = 4096 / (max(t["hbm_bound"], t["mxu_bound"])
                     + t["vpu_select_bound"] + t["dcn_bound"])
    assert multi["ceiling_qps"] == pytest.approx(expect, rel=1e-3)
    assert roofline.validate_block(multi) == []


def test_dcn_bound_class_and_strategy_default():
    # a pathologically slow DCN makes the merge the binding resource
    peaks = dict(roofline.PEAKS_BY_KIND["TPU v5e"], dcn_gbps=1e-6)
    m = roofline.xla_cost_model(n=100_000, d=64, k=100, nq=2048,
                                selector="exact", db_hosts=8,
                                peaks=peaks)
    assert m["bound_class"] == "dcn_bound"
    # dcn_merge=None resolves through the measured crossover table
    from knn_tpu.parallel.crossover import choose_merge

    assert m["terms"]["dcn"]["strategy"] == choose_merge(100, 8)
    # multihost blocks carry an explicitly-absent calibration verdict
    assert m["calibration"]["applied"] is False
    assert "dcn" in roofline.render_text(m)


def test_validate_block_rejects_malformed_dcn_term():
    m = roofline.pallas_cost_model(
        n=1_000_000, d=128, k=10, nq=4096, precision="int8",
        device_kind="TPU v5e", backend="tpu", db_hosts=2)
    assert roofline.validate_block(m) == []
    bad = {**m, "terms": {**m["terms"],
                          "dcn": {**m["terms"]["dcn"], "hosts": 1,
                                  "strategy": "bogus"}}}
    errs = roofline.validate_block(bad)
    assert any("terms.dcn.hosts" in e for e in errs)
    assert any("terms.dcn.strategy" in e for e in errs)
