"""The calibrated roofline (knn_tpu.obs.{traceread,calibrate}):
trace parsing pinned against the checked-in
fixture, malformed-artifact loud errors, the reconcile math (a seeded
wrong-by-2x peak constant corrected by the overlay), the calibration
store's version-token self-invalidation, MODEL_VERSION-3 block
semantics (explicit ``calibration: absent`` on uncalibrated lines —
the r05 curated line included) — the acceptance surface of the
calibrated-roofline ISSUE."""

import gzip
import json
import os
import shutil

import pytest

from knn_tpu import obs
from knn_tpu.obs import calibrate, health, roofline, traceread

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures",
                       "minimal.trace.json.gz")


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    monkeypatch.delenv(calibrate.CAL_ENV, raising=False)
    calibrate.reset()
    roofline.reset()
    yield
    calibrate.reset()
    roofline.reset()
    obs.reset()
    health.reset()


def _model(**kw):
    base = dict(n=1_000_000, d=128, k=100, nq=4096,
                device_kind="TPU v5 lite", backend="tpu")
    base.update(kw)
    return roofline.pallas_cost_model(**base)


# --- traceread: the checked-in fixture ---------------------------------


def test_fixture_trace_parses_with_pinned_device_busy_time():
    """The minimal checked-in trace: two overlapping device kernels
    (union 700us) + one disjoint (100us) on the TPU track, one host
    event that must NOT bill — device busy time pinned at 800us."""
    events = traceread.read_trace_events(FIXTURE)
    s = traceread.summarize_events(events)
    assert s["device_tracks_matched"] is True
    assert s["device_busy_s"] == pytest.approx(800e-6)
    assert s["kernel_events"] == 3  # host track excluded
    assert "TPU" in s["busiest_track"]


def test_read_section_matches_event_to_config(tmp_path):
    """Event->config matching rides the profiler's capture convention:
    a section resolves to ITS artifact under the sanitized directory
    name, and a section that never captured raises instead of silently
    matching another config's kernels."""
    run = tmp_path / "traces" / "m_ode_x" / "plugins" / "profile" / "r1"
    run.mkdir(parents=True)
    shutil.copy(FIXTURE, run / "host.trace.json.gz")
    s = traceread.read_section(str(tmp_path / "traces"), "m|ode x")
    assert s["section"] == "m_ode_x"
    assert s["device_busy_s"] == pytest.approx(800e-6)
    assert s["trace_files"] == [str(run / "host.trace.json.gz")]
    sample = traceread.sample_from_trace(
        str(tmp_path / "traces"), "m|ode x", nq=64)
    assert sample["source"] == "device_trace"
    assert sample["qps"] == pytest.approx(64 / 800e-6, rel=1e-3)
    with pytest.raises(traceread.TraceReadError,
                       match="does not exist"):
        traceread.read_section(str(tmp_path / "traces"), "other_config")


def test_read_section_ignores_stale_runs(tmp_path):
    """Re-running a capture into the same trace dir leaves the older
    timestamped run dirs behind; merging them would ADD disjoint-epoch
    busy intervals and calibrate against a measurement the machine
    never produced — only the newest run's files may enter."""
    base = tmp_path / "traces" / "m" / "plugins" / "profile"
    old_run, new_run = base / "r_old", base / "r_new"
    for run in (old_run, new_run):
        run.mkdir(parents=True)
        shutil.copy(FIXTURE, run / "host.trace.json.gz")
    past = os.path.getmtime(new_run) - 60
    os.utime(old_run, (past, past))
    s = traceread.read_section(str(tmp_path / "traces"), "m")
    assert s["runs_found"] == 2
    assert s["trace_files"] == [str(new_run / "host.trace.json.gz")]
    # one fixture's busy time, not the sum of both runs'
    assert s["device_busy_s"] == pytest.approx(800e-6)


def test_calibration_key_separates_kernel_arms(tmp_path, monkeypatch):
    """The tiled/streaming/fused kernels at one shape measure
    different machines: their store keys must differ, and a factor fit
    on one arm must never apply to another's block."""
    keys = {kern: calibrate.key_for_block(_model(kernel=kern))
            for kern in ("tiled", "streaming", "fused")}
    assert len(set(keys.values())) == 3
    store = str(tmp_path / "cal.json")
    monkeypatch.setenv(calibrate.CAL_ENV, store)
    m = _model(kernel="streaming")
    entry = calibrate.reconcile(
        m, {"source": "host_phase",
            "device_s": 2 * 4096 / m["ceiling_qps_analytic"],
            "nq": 4096})
    calibrate.put(keys["streaming"], entry, path=store)
    assert _model(kernel="streaming")["calibration"]["applied"] is True
    assert _model(kernel="tiled")["calibration"] == {"applied": False}
    assert _model(kernel="fused")["calibration"] == {"applied": False}


def test_malformed_traces_error_loudly(tmp_path):
    """A silently-empty parse would calibrate the model against
    nothing and call it measured — every malformed shape raises."""
    p = tmp_path / "junk.trace.json.gz"
    p.write_bytes(b"this is not gzip")
    with pytest.raises(traceread.TraceReadError):
        traceread.read_trace_events(str(p))
    p2 = tmp_path / "notjson.trace.json.gz"
    with gzip.open(p2, "wt") as f:
        f.write("not json {{{")
    with pytest.raises(traceread.TraceReadError, match="not trace"):
        traceread.read_trace_events(str(p2))
    p3 = tmp_path / "noevents.trace.json.gz"
    with gzip.open(p3, "wt") as f:
        json.dump({"metadata": {}}, f)
    with pytest.raises(traceread.TraceReadError,
                       match="no traceEvents"):
        traceread.read_trace_events(str(p3))
    # events but none complete: nothing measured -> loud
    with pytest.raises(traceread.TraceReadError, match="no complete"):
        traceread.summarize_events([{"ph": "M", "pid": 1,
                                     "name": "process_name",
                                     "args": {"name": "/device:TPU:0"}}])
    with pytest.raises(traceread.TraceReadError):
        traceread.find_trace_files(str(tmp_path / "absent"))


def test_host_phase_sample_takes_only_the_device_phase():
    """Only the fenced device phase enters the sample — the h2d/d2h
    phases are host-link time; a breakdown without device_s is loudly
    unusable."""
    pb = {"device_s": 0.5, "device_qps": 8192.0,
          "h2d_queries_s": 1.2, "d2h_transfer_s": 2.4}
    s = traceread.sample_from_phases(pb, nq=4096)
    assert s == {"source": "host_phase", "device_s": 0.5, "nq": 4096,
                 "qps": 8192.0}
    with pytest.raises(traceread.TraceReadError, match="device_s"):
        traceread.sample_from_phases({"note": "no probe"}, nq=4096)


# --- reconcile math -----------------------------------------------------


def test_wrong_by_2x_peak_constant_is_corrected_by_the_overlay(
        tmp_path, monkeypatch):
    """ACCEPTANCE pin: seed a measurement consistent with the HBM peak
    being claimed 2x too high — measured device time = 2x the modeled
    combined time on an hbm_bound config.  The reconciler attributes
    the residual to the hbm term, and the re-rendered block's
    CALIBRATED ceiling reproduces the measured qps within the stated
    tolerance (the analytic ceiling stays wrong by ~2x beside it)."""
    m = _model()
    assert m["bound_class"] == "hbm_bound"
    assert m["calibration"] == {"applied": False}
    measured_t = 2.0 * (4096 / m["ceiling_qps_analytic"])
    measured = {"source": "host_phase", "device_s": measured_t,
                "nq": 4096}
    entry = calibrate.reconcile(m, measured,
                                provenance={"commit": "abc",
                                            "round": 6})
    assert entry["method"] == "bound_term"
    assert entry["factors"]["mxu"] == 1.0
    assert entry["factors"]["vpu_select"] == 1.0
    assert entry["factors"]["hbm"] > 2.0  # absorbs the hidden terms too
    assert entry["model_residual_pct"] == pytest.approx(100.0, abs=0.1)
    assert entry["source"] == "host_phase"
    assert entry["provenance"]["commit"] == "abc"
    assert entry["provenance"]["round"] == 6

    store = str(tmp_path / "cal.json")
    calibrate.put(calibrate.key_for_block(m), entry, path=store)
    monkeypatch.setenv(calibrate.CAL_ENV, store)
    m2 = _model()
    cal = m2["calibration"]
    assert cal["applied"] is True
    assert cal["source"] == "host_phase"
    assert cal["age_s"] is not None and cal["age_s"] < 3600
    measured_qps = 4096 / measured_t
    resid = abs(m2["ceiling_qps"] - measured_qps) / measured_qps * 100
    assert resid <= calibrate.RESIDUAL_TOLERANCE_PCT
    # the analytic ceiling still stands beside it, 2x off
    assert m2["ceiling_qps_analytic"] == m["ceiling_qps_analytic"]
    assert m2["ceiling_qps_analytic"] / m2["ceiling_qps"] == \
        pytest.approx(2.0, rel=0.01)
    att = roofline.attribute(m2, measured_qps)
    assert att["roofline_pct"] == pytest.approx(1.0, abs=0.02)
    assert roofline.validate_block(att) == []
    txt = roofline.render_text(att)
    assert "CALIBRATED" in txt and "analytic" in txt


def test_reconcile_falls_back_to_uniform_when_bound_term_cannot():
    """A measurement FASTER than the hidden terms allows cannot be
    explained by scaling the bound term alone — every term scales
    uniformly and the entry says so."""
    m = _model()  # hbm_bound, serialized: combined = t_hbm + t_vpu
    t = m["terms"]
    fast_t = 0.5 * t["vpu_select"]["time_s"]  # under the hidden select
    entry = calibrate.reconcile(
        m, {"source": "host_phase", "device_s": fast_t, "nq": 4096})
    assert entry["method"] == "uniform"
    f = set(entry["factors"].values())
    assert len(f) == 1
    cal_t = calibrate._combined_time(
        calibrate.apply_to_times(
            {k: t[k]["time_s"] for k in calibrate.TERMS},
            entry["factors"]),
        m["select_overlapped"])
    assert cal_t == pytest.approx(fast_t, rel=1e-6)


def test_reconcile_refuses_garbage():
    m = _model()
    with pytest.raises(ValueError, match="source"):
        calibrate.reconcile(m, {"source": "vibes", "device_s": 1,
                                "nq": 4})
    with pytest.raises(ValueError, match="device_s"):
        calibrate.reconcile(m, {"source": "host_phase",
                                "device_s": 0, "nq": 4})
    with pytest.raises(ValueError, match="sane clamp"):
        calibrate.reconcile(m, {"source": "host_phase",
                                "device_s": 1e9, "nq": 4096})
    with pytest.raises(ValueError, match="roofline model"):
        calibrate.reconcile({"nope": 1}, {"source": "host_phase",
                                          "device_s": 1, "nq": 4})


# --- the store: keys, tokens, self-invalidation ------------------------


def test_store_version_token_self_invalidates(tmp_path, monkeypatch):
    """ACCEPTANCE pin: pre-calibration-model entries self-invalidate —
    an entry persisted under an older ``cal<N>`` token (or another
    shape) misses on lookup and the block renders analytic with an
    explicit ``applied: false``, never a stale overlay."""
    store = str(tmp_path / "cal.json")
    monkeypatch.setenv(calibrate.CAL_ENV, store)
    m = _model()
    key = calibrate.key_for_block(m)
    assert key.endswith(f"|cal{roofline.MODEL_VERSION}")
    entry = calibrate.reconcile(
        m, {"source": "host_phase",
            "device_s": 2 * 4096 / m["ceiling_qps_analytic"],
            "nq": 4096})
    # same shape, previous model version token: the old-format entry
    stale_key = key.replace(f"|cal{roofline.MODEL_VERSION}",
                            f"|cal{roofline.MODEL_VERSION - 1}")
    calibrate.put(stale_key, entry, path=store)
    # and a different shape under the current token
    calibrate.put(calibrate.calibration_key(
        "TPU v5 lite", 999, 128, 100, "pallas", "bf16x3"), entry,
        path=store)
    m2 = _model()
    assert m2["calibration"] == {"applied": False}
    assert m2["ceiling_qps"] == m2["ceiling_qps_analytic"]
    # the live store status counts only current-token entries
    st = calibrate.status()
    assert st["entries"] == 1  # the other-shape current-token entry
    # the real key now hits
    calibrate.put(key, entry, path=store)
    assert _model()["calibration"]["applied"] is True
    # repeated put counts samples
    calibrate.put(key, entry, path=store)
    assert calibrate.get(key, store)["samples"] == 2


def test_corrupt_store_degrades_to_analytic(tmp_path, monkeypatch):
    store = tmp_path / "cal.json"
    store.write_text("{ torn json")
    monkeypatch.setenv(calibrate.CAL_ENV, str(store))
    m = _model()
    assert m["calibration"]["applied"] is False
    assert m["ceiling_qps"] == m["ceiling_qps_analytic"]


def test_put_without_a_store_is_a_loud_caller_bug():
    with pytest.raises(ValueError, match="no calibration store"):
        calibrate.put("k", {"factors": {}})


# --- MODEL_VERSION 3 block semantics -----------------------------------


def test_estimated_flag_semantics_preserved_under_calibration(
        tmp_path, monkeypatch):
    """``estimated`` names the PEAK TABLE's provenance, not the
    overlay's: a generic-CPU-peaks block stays flagged estimated
    whether or not a calibration applies."""
    store = str(tmp_path / "cal.json")
    monkeypatch.setenv(calibrate.CAL_ENV, store)
    m = roofline.pallas_cost_model(n=2048, d=32, k=5, nq=64,
                                   backend="cpu")
    assert m["estimated"] is True
    assert m["calibration"]["applied"] is False
    entry = calibrate.reconcile(
        m, {"source": "host_phase", "device_s": 0.05, "nq": 64})
    calibrate.put(calibrate.key_for_block(m), entry, path=store)
    m2 = roofline.pallas_cost_model(n=2048, d=32, k=5, nq=64,
                                    backend="cpu")
    assert m2["calibration"]["applied"] is True
    assert m2["estimated"] is True  # still the generic peak table


def test_r05_curated_line_rerenders_with_explicit_calibration_absent():
    """ACCEPTANCE pin: the r05 SIFT1M curated line back-derives to a
    current-MODEL_VERSION block whose calibration verdict is EXPLICITLY
    absent — pre-calibration history re-renders honestly instead of
    silently claiming calibrated."""
    with open(os.path.join(REPO, "tests", "fixtures",
                           "bench_line_sift1m_v5e.json")) as f:
        rec = json.load(f)
    block = roofline.block_for_bench_line(rec)
    assert block["model_version"] == roofline.MODEL_VERSION
    assert block["calibration"] == {"applied": False}
    assert block["ceiling_qps"] == block["ceiling_qps_analytic"]
    assert roofline.validate_block(block) == []
    assert "calibration: absent" in roofline.render_text(block)


def test_validate_block_rejects_malformed_calibration():
    good = roofline.attribute(
        roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), 50.0)
    assert roofline.validate_block(good) == []
    bad = dict(good, calibration={"applied": "yes"})
    assert any("applied" in e for e in roofline.validate_block(bad))
    bad = dict(good, calibration={
        "applied": True, "factors": {"hbm": -1, "mxu": 1,
                                     "vpu_select": 1},
        "source": "host_phase", "model_residual_pct": 5.0})
    assert any("factor" in e for e in roofline.validate_block(bad))
    bad = dict(good, calibration={
        "applied": True,
        "factors": {"hbm": 1, "mxu": 1, "vpu_select": 1},
        "source": "vibes", "model_residual_pct": 5.0})
    assert any("source" in e for e in roofline.validate_block(bad))


# --- registry / statusz / obs-off --------------------------------------


def test_calibration_gauges_publish_with_roofline(tmp_path,
                                                  monkeypatch):
    from knn_tpu.obs import names as mn

    store = str(tmp_path / "cal.json")
    monkeypatch.setenv(calibrate.CAL_ENV, store)
    m = _model()
    entry = calibrate.reconcile(
        m, {"source": "host_phase",
            "device_s": 2 * 4096 / m["ceiling_qps_analytic"],
            "nq": 4096})
    calibrate.put(calibrate.key_for_block(m), entry, path=store)
    att = roofline.attribute(_model(), 1000.0)
    roofline.publish("lbl", att)
    snap = obs.snapshot()
    applied = snap[mn.CALIBRATION_APPLIED]["series"]
    assert applied[0]["labels"]["config"] == "lbl"
    assert applied[0]["value"] == 1.0
    assert snap[mn.CALIBRATION_RESIDUAL]["series"][0]["value"] == \
        pytest.approx(100.0, abs=0.1)
    assert mn.CALIBRATION_AGE in snap
    # /statusz + doctor surface the store state
    rep = health.report()
    assert rep["calibration"]["entries"] == 1
    assert rep["calibration"]["worst_residual_pct"] is not None
    rendered = health.render_text(rep)
    assert "calibration: 1 entry at" in rendered
    assert "[calibrated]" in rendered  # the roofline line's tag


def test_calibration_publish_is_noop_when_obs_disabled(tmp_path,
                                                       monkeypatch):
    obs.reset(enabled=False)
    try:
        att = roofline.attribute(
            roofline.pallas_cost_model(n=1000, d=16, k=5, nq=8), 10.0)
        roofline.publish("lbl", att)
        assert "knn_tpu_calibration" not in obs.prometheus_text()
    finally:
        obs.reset()


def test_new_switches_are_catalogued_and_isolated():
    from knn_tpu.analysis.switches import isolation_names, lookup

    assert lookup("KNN_TPU_CALIBRATION") is not None
    assert "KNN_TPU_CALIBRATION" in isolation_names()


# --- profiler: a real capture parses (slow) ----------------------------


@pytest.mark.slow
def test_real_cpu_profiler_trace_parses(tmp_path):
    """Satellite: a REAL jax.profiler.trace on CPU produces an
    artifact traceread parses — the capture convention and the reader
    agree about what lands on disk."""
    import jax.numpy as jnp

    from knn_tpu.obs import profiler

    base = str(tmp_path / "traces")
    with profiler.device_trace("real|cpu run", base_dir=base) as td:
        assert td == os.path.join(base, "real_cpu_run")
        jnp.dot(jnp.ones((256, 256)),
                jnp.ones((256, 256))).block_until_ready()
    assert profiler.captures().get("real_cpu_run") == td
    s = traceread.read_section(base, "real|cpu run")
    assert s["kernel_events"] > 0
    assert s["device_busy_s"] > 0
    sample = traceread.sample_from_trace(base, "real|cpu run", nq=8)
    assert sample["source"] == "device_trace"
    assert sample["qps"] > 0
