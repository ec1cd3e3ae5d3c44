"""Quality observability (knn_tpu.obs.audit / knn_tpu.obs.drift): the
shadow audit sampler replays served answers against the f64 exact
oracle OFF the serving path; a seeded index-perturbation fault yields
audited recall < 1, exactly one edge-triggered audit_recall alert and
one postmortem bundle embedding the failing records, while the
unfaulted twin run audits recall == 1.0 with zero alerts; KNN_TPU_OBS=0
pins the whole tier off with served results bitwise identical — the
acceptance surface of the quality-observability ISSUE."""

import json
import os
import threading

import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import audit, names as mn

REPO = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts from an empty ENABLED registry, event ring,
    SLO engine, health registrations, and a torn-down auditor."""
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    obs.reset_slo_engine()
    obs.health.reset()
    audit.clear_fault()
    audit.reset_auditor()
    yield
    audit.clear_fault()
    audit.reset_auditor()
    obs.reset()
    obs.reset_event_log(from_env=True)
    obs.reset_slo_engine()
    obs.health.reset()


def _alerts():
    return [e for e in obs.get_event_log().recent()
            if e.get("name") == "slo.alert" and e.get("state") == "firing"]


def _record(k=3, n=64, d=8, cost_rows=None, tenant=None, oracle=None,
            trace_id="t0", seed=0):
    """A self-consistent audit record over a synthetic corpus: the
    served answer IS the exact answer (recall 1.0 unless faulted)."""
    rng = np.random.default_rng(seed)
    db = rng.standard_normal((n, d))
    q = rng.standard_normal((2, d))
    d2 = ((q[:, None, :] - db[None, :, :]) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")
    ids = order[:, :k]
    dk = np.take_along_axis(d2, ids, axis=1)

    def exact_oracle(queries, served_ids):
        sd = np.take_along_axis(d2, np.asarray(served_ids)[:, :k], axis=1)
        return dk, ids, sd

    return audit.AuditRecord(
        trace_id=trace_id, tenant=tenant, k=k, queries=q,
        served_d=dk.copy(), served_ids=ids.copy(), epoch=None,
        cost_rows=cost_rows if cost_rows is not None else 2 * n,
        oracle=oracle or exact_oracle)


# --- sampler semantics ---------------------------------------------------
def test_sampler_deterministic_and_rate_monotone(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "0.25")
    a = audit.reset_auditor()
    ids = [f"trace{i:04d}" for i in range(400)]
    first = [a.sampled(t) for t in ids]
    # the decision is a pure function of the trace id
    assert [a.sampled(t) for t in ids] == first
    frac = sum(first) / len(first)
    assert 0.1 < frac < 0.45  # deterministic hash, loose band
    # a request sampled at rate r stays sampled at every r' > r
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "0.75")
    b = audit.reset_auditor()
    assert all(b.sampled(t) for t, s in zip(ids, first) if s)
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    assert all(audit.reset_auditor().sampled(t) for t in ids)


def test_unset_rate_arms_nothing():
    a = audit.get_auditor()
    assert a.rate == 0.0 and not a.enabled()
    assert not a.sampled("deadbeef")
    assert not a.submit(_record())
    assert a.summary()["sampled_requests"] == 0
    assert not a.worker_alive()


def test_malformed_knobs_rejected(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "nope")
    with pytest.raises(ValueError, match="KNN_TPU_AUDIT_RATE"):
        audit.reset_auditor()
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.5")
    with pytest.raises(ValueError, match="KNN_TPU_AUDIT_RATE"):
        audit.reset_auditor()
    monkeypatch.delenv(audit.AUDIT_RATE_ENV)
    monkeypatch.setenv(audit.AUDIT_BUDGET_ENV, "-3")
    with pytest.raises(ValueError, match="KNN_TPU_AUDIT_BUDGET_ROWS_S"):
        audit.reset_auditor()


# --- the replay worker ---------------------------------------------------
def test_replay_runs_on_audit_thread_never_the_submitter(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    a = audit.reset_auditor()
    seen = {}

    rec = _record()
    inner = rec.oracle

    def spying_oracle(queries, served_ids):
        seen["thread"] = threading.current_thread().name
        return inner(queries, served_ids)

    rec.oracle = spying_oracle
    assert a.submit(rec)
    assert a.drain(timeout=10.0)
    assert seen["thread"] == "knn-audit"
    assert seen["thread"] != threading.current_thread().name
    s = a.summary()
    assert s["replayed_queries"] == 2 and s["deficient_queries"] == 0
    assert s["last_recall_at_k"] == 1.0


def test_budget_drops_are_loud(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    monkeypatch.setenv(audit.AUDIT_BUDGET_ENV, "10")
    a = audit.reset_auditor()

    def never(queries, served_ids):  # pragma: no cover - must not run
        raise AssertionError("over-budget record must never replay")

    assert not a.submit(_record(cost_rows=10_000, oracle=never))
    s = a.summary()
    assert s["sampled_requests"] == 1
    assert s["dropped"] == {"budget": 1}
    assert s["replayed_queries"] == 0
    assert obs.counter(mn.AUDIT_DROPPED, reason="budget").get() == 1.0
    assert obs.counter(mn.AUDIT_SAMPLED, tenant="-").get() == 1.0


def test_oracle_error_counts_as_dropped_and_worker_survives(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    a = audit.reset_auditor()

    def boom(queries, served_ids):
        raise RuntimeError("oracle exploded")

    assert a.submit(_record(oracle=boom, trace_id="bad"))
    assert a.drain(timeout=10.0)
    assert a.summary()["dropped"] == {"error": 1}
    # the worker survives a scoring error and keeps replaying
    assert a.submit(_record(trace_id="good"))
    assert a.drain(timeout=10.0)
    assert a.summary()["replayed_queries"] == 2
    assert a.worker_alive()


def test_fault_seam_surfaces_deficiency_per_tenant(monkeypatch):
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    a = audit.reset_auditor()

    def perturb(rec):
        # swap the queries' answers: same valid ids, wrong neighbors
        rec.served_ids = np.roll(rec.served_ids, 1, axis=0)
        return rec

    audit.set_fault(perturb)
    try:
        assert a.submit(_record(tenant="acme", trace_id="f1"))
        assert a.drain(timeout=10.0)
    finally:
        audit.clear_fault()
    s = a.summary()
    assert s["deficient_queries"] > 0
    assert s["last_recall_at_k"] < 1.0
    assert obs.counter(mn.AUDIT_DEFICIENT, tenant="acme").get() > 0
    ev = a.evidence()
    assert ev["failures"], "a deficient replay must leave evidence"
    f = ev["failures"][-1]
    assert f["trace_id"] == "f1" and f["tenant"] == "acme"
    assert f["worst_served_ids"] != f["worst_oracle_ids"]


# --- drift detection -----------------------------------------------------
def test_psi_zero_on_identical_and_large_on_shifted():
    from knn_tpu.obs.drift import psi

    base = np.array([100, 200, 300, 400], dtype=float)
    assert psi(base, base * 7) == pytest.approx(0.0, abs=1e-9)
    shifted = np.array([400, 300, 200, 100], dtype=float)
    assert psi(base, shifted) > 0.2


def test_drift_monitor_sets_gauges_and_status():
    from knn_tpu.obs.drift import QueryDriftMonitor

    rng = np.random.default_rng(3)
    train = rng.normal(10.0, 1.0, size=2048)
    mon = QueryDriftMonitor(train_norms=train,
                            assign_baseline=np.array([512, 512, 512, 512]))
    mon.observe(norms=rng.normal(10.0, 1.0, size=512),
                assignments=rng.integers(0, 4, size=512))
    st = mon.status()
    assert st["queries_observed"] == 512
    assert st["norm_psi"] < 0.1  # same distribution
    assert obs.gauge(mn.DRIFT_NORM_PSI).get() == pytest.approx(
        st["norm_psi"])
    # a shifted live population moves the PSI decisively
    mon2 = QueryDriftMonitor(train_norms=train)
    mon2.observe(norms=rng.normal(16.0, 1.0, size=512))
    assert mon2.status()["norm_psi"] > 0.5
    assert obs.counter(mn.DRIFT_QUERIES).get() == 1024.0


def test_index_health_gauges():
    from knn_tpu.obs.drift import index_health

    index_health(list_sizes=np.array([10, 10, 40]), tail_rows=20,
                 n_all=100, live_rows=80)
    assert obs.gauge(mn.INDEX_LIST_IMBALANCE).get() == pytest.approx(2.0)
    assert obs.gauge(mn.INDEX_TAIL_FRACTION).get() == pytest.approx(0.2)
    assert obs.gauge(mn.INDEX_TOMBSTONE_DENSITY).get() == pytest.approx(0.2)


# --- exemplar retention knobs -------------------------------------------
def test_exemplar_cap_knob(monkeypatch):
    monkeypatch.setenv("KNN_TPU_OBS_EXEMPLAR_CAP", "2")
    obs.reset(enabled=True)
    h = obs.histogram(mn.QUEUE_WAIT)
    for i in range(10):
        h.observe(float(i), exemplar=f"trace{i}")
    ex = h.exemplars()
    assert len(ex) == 2
    assert [e["trace_id"] for e in ex] == ["trace9", "trace8"]
    monkeypatch.setenv("KNN_TPU_OBS_EXEMPLAR_CAP", "0")
    obs.reset(enabled=True)
    h0 = obs.histogram(mn.QUEUE_WAIT)
    h0.observe(1.0, exemplar="t")
    assert h0.exemplars() == []


def test_exemplar_age_knob(monkeypatch):
    monkeypatch.setenv("KNN_TPU_OBS_EXEMPLAR_AGE_S", "0.05")
    obs.reset(enabled=True)
    import time as _time

    h = obs.histogram(mn.QUEUE_WAIT)
    h.observe(1.0, exemplar="old")
    assert [e["trace_id"] for e in h.exemplars()] == ["old"]
    _time.sleep(0.08)
    assert h.exemplars() == []  # aged out on read


# --- serving-engine integration (the acceptance criterion) ---------------
@pytest.fixture(scope="module")
def placed():
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.parallel.sharded import ShardedKNN

    rng = np.random.default_rng(11)
    db = rng.standard_normal((192, 12)).astype(np.float32)
    return ShardedKNN(db, mesh=make_mesh(4, 2), k=4), db, rng


def _replay(prog, rng, n_req=6, tenant=None):
    from knn_tpu.serving.engine import ServingEngine

    eng = ServingEngine(prog, buckets=(8, 16))
    eng.warmup()
    out = []
    for i in range(n_req):
        q = rng.standard_normal((5, 12)).astype(np.float32)
        h = eng.submit(q, tenant=tenant)
        out.append(h.result())
    return eng, out


def test_engine_audit_clean_run_recall_one(placed):
    prog, db, _ = placed
    rng = np.random.default_rng(21)
    os.environ[audit.AUDIT_RATE_ENV] = "1.0"
    try:
        audit.reset_auditor()
        slo_eng = obs.get_slo_engine()
        slo_eng.evaluate(now=0.0)
        eng, results = _replay(prog, rng)
        a = audit.get_auditor()
        assert a.drain(timeout=30.0)
        s = a.summary()
        assert s["sampled_requests"] == 6
        assert s["replayed_queries"] == 30
        assert s["deficient_queries"] == 0
        assert s["dropped"] == {}
        assert s["last_recall_at_k"] == 1.0
        # engine stats grow the quality section while armed
        assert eng.stats()["quality"]["replayed_queries"] == 30
        rep = slo_eng.evaluate(now=300.0)
        assert rep["breached"] == []
        assert _alerts() == []
    finally:
        os.environ.pop(audit.AUDIT_RATE_ENV, None)


def test_engine_seeded_fault_alerts_once_with_postmortem(placed, tmp_path,
                                                         monkeypatch):
    prog, db, _ = placed
    rng = np.random.default_rng(21)  # the SAME trace as the clean run
    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    monkeypatch.setenv("KNN_TPU_POSTMORTEM_DIR", str(tmp_path))
    audit.reset_auditor()

    def perturb(rec):
        # seeded index-perturbation fault, applied on the WORKER
        # thread: each query is served another query's (valid but
        # wrong) neighbors — the serving path stays untouched
        rec.served_ids = np.roll(rec.served_ids, 1, axis=0)
        return rec

    audit.set_fault(perturb)
    try:
        slo_eng = obs.get_slo_engine()
        slo_eng.evaluate(now=0.0)
        eng, faulted = _replay(prog, rng)
        a = audit.get_auditor()
        assert a.drain(timeout=30.0)
        s = a.summary()
        assert s["deficient_queries"] > 0
        assert s["last_recall_at_k"] < 1.0
        rep = slo_eng.evaluate(now=300.0)
        assert rep["breached"] == ["audit_recall:-"]
        fired = _alerts()
        assert [(e["objective"], e["state"]) for e in fired] == [
            ("audit_recall:-", "firing")]
        # still breached on re-evaluation: reported, not re-alerted
        slo_eng.evaluate(now=310.0)
        assert len(_alerts()) == 1
        # exactly one postmortem bundle, embedding the failing records
        from knn_tpu.obs import blackbox

        bundles = sorted(p for p in os.listdir(tmp_path)
                         if p.endswith(".json"))
        assert len(bundles) == 1
        payload = blackbox.read_bundle(str(tmp_path / bundles[0]))
        ev = payload["audit"]
        assert ev["summary"]["deficient_queries"] > 0
        assert ev["failures"]
        assert ev["failures"][-1]["max_rank_displacement"] >= 1
    finally:
        audit.clear_fault()
    # the fault perturbed only the AUDIT copy: served results of the
    # faulted run match a fault-free rerun bitwise
    audit.clear_fault()
    monkeypatch.delenv(audit.AUDIT_RATE_ENV)
    audit.reset_auditor()
    rng2 = np.random.default_rng(21)
    _, clean = _replay(prog, rng2)
    for (df, if_), (dc, ic) in zip(faulted, clean):
        np.testing.assert_array_equal(np.asarray(df), np.asarray(dc))
        np.testing.assert_array_equal(np.asarray(if_), np.asarray(ic))


def test_obs_off_pins_audit_fully_dark(placed):
    prog, db, _ = placed
    obs.reset(enabled=False)
    os.environ[audit.AUDIT_RATE_ENV] = "1.0"
    try:
        a = audit.reset_auditor()
        assert not a.enabled()
        assert not a.sampled("deadbeefdeadbeef")
        rng = np.random.default_rng(33)
        eng, res_off = _replay(prog, rng, n_req=3)
        assert not a.worker_alive()
        assert a.summary()["sampled_requests"] == 0
        assert "quality" not in eng.stats()
        assert not any(t.name == "knn-audit"
                       for t in threading.enumerate())
        # bitwise-identical served results with the sampler armed + on
        obs.reset(enabled=True)
        audit.reset_auditor()
        rng = np.random.default_rng(33)
        _, res_on = _replay(prog, rng, n_req=3)
        assert audit.get_auditor().drain(timeout=30.0)
        for (d0, i0), (d1, i1) in zip(res_off, res_on):
            np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
            np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    finally:
        os.environ.pop(audit.AUDIT_RATE_ENV, None)


def test_stats_quality_section_absent_when_sampler_off(placed):
    prog, db, _ = placed
    rng = np.random.default_rng(5)
    eng, _ = _replay(prog, rng, n_req=1)
    assert "quality" not in eng.stats()


# --- certificate margins -------------------------------------------------
def test_sharded_certified_margin_histogram(placed):
    prog, db, _ = placed
    rng = np.random.default_rng(9)
    q = rng.standard_normal((8, 12)).astype(np.float32)
    prog.search_certified(q)
    s = obs.histogram(mn.CERTIFIED_MARGIN, path="sharded").summary()
    assert s["count"] > 0
    assert s["min"] >= 0.0  # certified queries sit clear of the bound


def test_ivf_quality_gauges_margins_and_drift():
    from knn_tpu.ivf import IVFIndex
    from knn_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(17)
    db = rng.standard_normal((512, 8)).astype(np.float32)
    idx = IVFIndex(db, mesh=make_mesh(), k=4, ncentroids=16, seed=0)
    q = rng.standard_normal((16, 8)).astype(np.float32)
    idx.search_certified(q, nprobe=4)
    for name in (mn.IVF_FALLBACK_RATE, mn.IVF_RECALL_AT_K,
                 mn.IVF_PROBE_FRACTION, mn.IVF_BYTES_STREAMED_RATIO):
        v = obs.gauge(name, selector="exact").get()
        assert 0.0 <= v <= 1.5
    assert obs.histogram(mn.CERTIFIED_MARGIN, path="ivf"
                         ).summary()["count"] > 0
    st = idx.stats()["drift"]
    assert st["queries_observed"] == 16
    assert "centroid_assign_psi" in st
    assert obs.gauge(mn.INDEX_LIST_IMBALANCE).get() >= 1.0


def test_ivf_obs_off_skips_drift_and_gauges():
    from knn_tpu.ivf import IVFIndex
    from knn_tpu.parallel.mesh import make_mesh

    obs.reset(enabled=False)
    rng = np.random.default_rng(17)
    db = rng.standard_normal((256, 8)).astype(np.float32)
    idx = IVFIndex(db, mesh=make_mesh(), k=3, ncentroids=8, seed=0)
    assert idx._drift is None
    idx.search_certified(rng.standard_normal((4, 8)).astype(np.float32),
                         nprobe=2)
    assert "drift" not in idx.stats()


# --- surfaces: statusz / doctor / cli audit ------------------------------
def test_health_report_carries_quality_and_renders(monkeypatch):
    from knn_tpu.obs import health

    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    a = audit.reset_auditor()
    assert a.submit(_record(trace_id="rep1"))
    assert a.drain(timeout=10.0)
    rep = health.report()
    q = rep["quality"]
    assert q["enabled"] and q["replayed_queries"] == 2
    text = health.render_text(rep)
    assert "quality: audit rate=1.0" in text
    # sampler off: the section says so instead of vanishing
    monkeypatch.delenv(audit.AUDIT_RATE_ENV)
    audit.reset_auditor()
    assert "audit sampler off" in health.render_text(health.report())


def test_cli_audit_renders_snapshot_and_bundle(tmp_path, monkeypatch,
                                               capsys):
    from knn_tpu import cli
    from knn_tpu.obs import blackbox, export

    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "1.0")
    a = audit.reset_auditor()
    assert a.submit(_record(trace_id="snap1"))
    assert a.drain(timeout=10.0)
    snap = tmp_path / "snap.json"
    export.write_json_snapshot(str(snap))
    rc = cli.run_audit(cli.build_audit_parser().parse_args(
        ["--snapshot", str(snap)]))
    out = capsys.readouterr().out
    assert rc == 0
    assert "replayed=2q" in out and "last_recall@k=1.0" in out
    # a bundle source renders the embedded failing records and exits 2
    def perturb(rec):
        rec.served_ids = np.roll(rec.served_ids, 1, axis=0)
        return rec

    audit.set_fault(perturb)
    try:
        assert a.submit(_record(trace_id="bund1", tenant="acme"))
        assert a.drain(timeout=10.0)
    finally:
        audit.clear_fault()
    monkeypatch.setenv("KNN_TPU_POSTMORTEM_DIR", str(tmp_path / "pm"))
    blackbox.on_breach("audit_recall:acme", {"seed": "test"})
    bundles = os.listdir(tmp_path / "pm")
    assert len(bundles) == 1
    rc = cli.run_audit(cli.build_audit_parser().parse_args(
        ["--bundle", str(tmp_path / "pm" / bundles[0])]))
    out = capsys.readouterr().out
    assert rc == 2
    assert "bund1" in out
    rc = cli.run_audit(cli.build_audit_parser().parse_args(
        ["--snapshot", str(tmp_path / "missing.json")]))
    assert rc == 1


def test_cli_audit_json_flag_round_trips(tmp_path, monkeypatch, capsys):
    from knn_tpu import cli
    from knn_tpu.obs import export

    monkeypatch.setenv(audit.AUDIT_RATE_ENV, "0.5")
    audit.reset_auditor()
    snap = tmp_path / "snap.json"
    export.write_json_snapshot(str(snap))
    rc = cli.run_audit(cli.build_audit_parser().parse_args(
        ["--snapshot", str(snap), "--json"]))
    payload = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert payload["quality"]["rate"] == 0.5


# --- the quality artifact block ------------------------------------------
def test_quality_block_schema_round_trip():
    from knn_tpu.analysis import artifacts as A

    assert A.version_value("quality") == audit.QUALITY_VERSION
    block = {
        "quality_version": audit.QUALITY_VERSION,
        "audit_rate": 1.0,
        "audit_sampled_requests": 6,
        "audit_replayed_queries": 30,
        "audit_deficient_queries": 0,
        "audit_dropped_records": 0,
        "audit_recall_at_k": 1.0,
        "audit_rank_displacement_p99": 0.0,
        "audit_distance_rel_error_p99": 1e-7,
        "wall_s": 0.5,
    }
    assert A.validate("quality", block) == []
    assert A.validate("quality", {"error": "mode died"}) == []
    bad = dict(block, audit_recall_at_k=1.5)
    assert any("audit_recall_at_k" in e
               for e in A.validate("quality", bad))
