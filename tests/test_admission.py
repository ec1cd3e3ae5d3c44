"""Admission control (knn_tpu.serving.admission + QueryQueue wiring):
bounded depth with explicit rejection, per-tenant token-bucket quotas,
deadline-aware shedding (submit-time estimate + queued expiry),
starvation-safe aged-priority ordering, per-tenant metrics/SLOs, the
brownout acceptance (at 5x the measured capacity the queue sheds with
explicit outcomes, admitted p99 stays within the SLO, no tenant is
starved, and throughput recovers after the burst), and the
disabled-mode bitwise-identity contract."""

import time

import numpy as np
import pytest

from knn_tpu import loadgen, obs
from knn_tpu.obs import names as mn
from knn_tpu.obs import slo
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.serving import (
    AdmissionConfig,
    AdmissionController,
    DeadlineError,
    QueryQueue,
    QueueFullError,
    QuotaExceededError,
    ServingEngine,
)

K = 7
DIM = 12
BUCKETS = (8, 16, 32)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts from an empty ENABLED registry/ring/SLO/health
    state (queues register health hooks and mint counters)."""
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    obs.reset_slo_engine()
    obs.health.reset()
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)
    obs.reset_slo_engine()
    obs.health.reset()


# -- a controllable fake engine (queue mechanics without device noise) ----
class _FakeHandle:
    trace_id = None

    def __init__(self, n, k, result_s=0.0):
        self._n, self._k, self._s = n, k, result_s

    def result(self):
        if self._s:
            time.sleep(self._s)
        return (np.zeros((self._n, self._k), np.float32),
                np.zeros((self._n, self._k), np.int64))


class _FakeEngine:
    """QueryQueue-facing engine stub: ``submit_s`` blocks the batcher
    (dispatch saturation), ``result_s`` blocks the completer."""

    buckets = BUCKETS

    def __init__(self, dim=DIM, submit_s=0.0, result_s=0.0):
        self._dim = dim
        self.submit_s = submit_s
        self.result_s = result_s

    def submit(self, cat, op="search"):
        if self.submit_s:
            time.sleep(self.submit_s)
        return _FakeHandle(cat.shape[0], K, self.result_s)

    def stats(self):
        return {"fake": True}


@pytest.fixture(scope="module")
def served():
    rng = np.random.default_rng(5)
    db = (rng.random((400, DIM)) * 10).astype(np.float32)
    q = (rng.random((64, DIM)) * 10).astype(np.float32)
    mesh = make_mesh(4, 2)
    prog = ShardedKNN(db, mesh=mesh, k=K)
    engine = ServingEngine(prog, buckets=BUCKETS)
    engine.warmup()
    return prog, engine, q


ROW = np.zeros((1, DIM), np.float32)


# -- bounded depth (the hook everything else builds on) -------------------
def test_max_depth_bounds_queue_growth_with_explicit_rejection():
    eng = _FakeEngine(submit_s=0.25)
    with QueryQueue(eng, max_wait_ms=0.0, max_depth=2) as q:
        f0 = q.submit(ROW)  # batcher grabs it, then blocks in submit_s
        time.sleep(0.05)  # pending drained, but f0 is still IN FLIGHT
        f1 = q.submit(ROW)
        # depth counts OUTSTANDING work (queued + in flight): f0 has
        # not completed, so the third submit finds 2 >= max_depth
        with pytest.raises(QueueFullError) as exc:
            q.submit(ROW)
        assert exc.value.reason == "queue_full"
        st = q.stats()
        assert st["admission"]["rejected"] == {"queue_full": 1}
        assert st["admission"]["admitted"] == 2
        # the accepted requests still complete normally, freeing slots
        for f in (f0, f1):
            f.result()
        time.sleep(0.05)  # completer retires the slots
        f2 = q.submit(ROW)  # depth back under the bound -> admitted
        f2.result()
    assert obs.counter(mn.ADMISSION_REJECTED, tenant="-",
                       reason="queue_full").get() == 1.0


def test_default_queue_remains_unbounded_regression():
    """Pre-admission behavior IS the default: no depth bound, no
    rejection, however deep the backlog grows (the regression guard:
    bounding is strictly opt-in)."""
    eng = _FakeEngine(submit_s=0.1)
    with QueryQueue(eng, max_wait_ms=50.0) as q:
        futs = [q.submit(ROW) for _ in range(100)]  # never raises
        st = q.stats()
        assert "admission" not in st  # pre-PR stats shape
        for f in futs:
            f.result()
        assert q.stats()["requests"] == 100


def test_conflicting_depth_bounds_raise():
    eng = _FakeEngine()
    with pytest.raises(ValueError, match="conflicting"):
        QueryQueue(eng, max_depth=4,
                   admission=AdmissionConfig(max_depth=8))
    # agreeing or one-sided specs are fine (merged)
    q = QueryQueue(eng, max_depth=4,
                   admission=AdmissionConfig(shed=True))
    assert q._ctrl.config.max_depth == 4
    assert q._ctrl.config.shed is True
    q.close()


# -- per-tenant quotas ----------------------------------------------------
def test_token_bucket_quota_rejects_over_rate_tenant():
    eng = _FakeEngine()
    cfg = AdmissionConfig(quotas={"a": (1.0, 2.0)})  # 1 q/s, burst 2
    with QueryQueue(eng, max_wait_ms=0.0, admission=cfg) as q:
        oks, rejs = 0, 0
        for _ in range(5):
            try:
                q.submit(ROW, tenant="a")
                oks += 1
            except QuotaExceededError as e:
                assert e.reason == "quota"
                rejs += 1
        assert (oks, rejs) == (2, 3)  # burst admits, then the wall
        # an unquota'd tenant is untouched by a's exhaustion
        for _ in range(5):
            q.submit(ROW, tenant="b")
        st = q.stats()["admission"]
        assert st["per_tenant"]["a"] == {"admitted": 2, "rejected": 3,
                                         "shed": 0}
        assert st["per_tenant"]["b"]["admitted"] == 5
    assert obs.counter(mn.ADMISSION_REJECTED, tenant="a",
                       reason="quota").get() == 3.0


def test_token_bucket_refills_over_time():
    now = [0.0]
    ctrl = AdmissionController(AdmissionConfig(quotas={"a": (10.0, 1.0)}))
    ctrl.admit(tenant="a", depth=0, rows=0,
               deadline_s=None, now=now[0])
    with pytest.raises(QuotaExceededError):
        ctrl.admit(tenant="a", depth=0, rows=0,
                   deadline_s=None, now=0.01)
    # 0.2 s at 10 tokens/s = 2 tokens accrued (capped at burst 1)
    ctrl.admit(tenant="a", depth=0, rows=0,
               deadline_s=None, now=0.2)


# -- deadline-aware shedding ----------------------------------------------
def test_submit_time_shed_uses_wait_estimate():
    ctrl = AdmissionController(AdmissionConfig(shed=True))
    # no estimator history yet: never shed on a fabricated estimate
    ctrl.admit(tenant=None, depth=0, rows=500,
               deadline_s=0.01, now=0.0)
    ctrl.observe_service(rows=100, seconds=1.0)  # 10 ms/row
    # 500 queued rows -> ~5 s wait; a 100 ms deadline cannot be met
    with pytest.raises(DeadlineError) as exc:
        ctrl.admit(tenant="t", depth=1, rows=500,
                   deadline_s=0.1, now=0.0)
    assert exc.value.reason == "deadline"
    # a 10 s deadline can
    ctrl.admit(tenant="t", depth=1, rows=500,
               deadline_s=10.0, now=0.0)
    assert obs.counter(mn.ADMISSION_REJECTED, tenant="t",
                       reason="deadline").get() == 1.0


def test_queued_requests_shed_on_expiry_before_dispatch():
    eng = _FakeEngine(submit_s=0.2)  # batcher saturated per dispatch
    cfg = AdmissionConfig(shed=True)
    with QueryQueue(eng, max_wait_ms=0.0, admission=cfg) as q:
        f0 = q.submit(ROW)  # occupies the batcher ~200 ms
        time.sleep(0.05)
        f1 = q.submit(ROW, deadline_ms=50.0)  # expires at ~100 ms
        f2 = q.submit(ROW)  # no deadline: must survive the sweep
        with pytest.raises(DeadlineError):
            f1.result(timeout=5)
        assert f2.result(timeout=5) is not None
        f0.result(timeout=5)
        st = q.stats()
        assert st["admission"]["shed"] == {"expired": 1}
        assert st["errors"] == 0  # a shed is an outcome, not an error
    assert obs.counter(mn.ADMISSION_SHED, tenant="-",
                       reason="expired").get() == 1.0


def test_deadline_rejection_never_spends_quota_token():
    """A request the deadline check sheds consumed zero capacity, so
    it must not drain the tenant's bucket — transient overload must
    not morph into spurious quota rejections after the drain."""
    ctrl = AdmissionController(
        AdmissionConfig(shed=True, quotas={"a": (1.0, 1.0)}))
    ctrl.observe_service(rows=10, seconds=1.0)  # 100 ms/row
    for _ in range(3):
        with pytest.raises(DeadlineError):
            ctrl.admit(tenant="a", depth=1, rows=100,
                       deadline_s=0.1, now=0.0)
    # the single burst token is still there: the first feasible
    # request after the overload is admitted, not quota-rejected
    ctrl.admit(tenant="a", depth=0, rows=0, deadline_s=100.0, now=0.0)


def test_expired_shed_delivered_promptly_under_large_max_wait():
    """The batcher's sleep is capped by the earliest pending deadline,
    not only the batch clock: a 10 s max-wait must not hold a 60 ms
    deadline's DeadlineError for 10 s."""
    eng = _FakeEngine()
    cfg = AdmissionConfig(shed=True)
    with QueryQueue(eng, max_wait_ms=10_000.0, admission=cfg) as q:
        t0 = time.monotonic()
        fut = q.submit(ROW, deadline_ms=60.0)
        with pytest.raises(DeadlineError):
            fut.result(timeout=5)
        assert time.monotonic() - t0 < 2.0  # promptly, not at max-wait


def test_default_deadline_applies_to_untagged_requests():
    ctrl = AdmissionController(
        AdmissionConfig(shed=True, default_deadline_ms=100.0))
    ctrl.observe_service(rows=10, seconds=1.0)  # 100 ms/row
    with pytest.raises(DeadlineError):
        # no explicit deadline -> the default one, unmeetable here
        ctrl.admit(tenant=None, depth=1, rows=100,
                   deadline_s=None, now=0.0)


# -- priority + starvation safety -----------------------------------------
def test_aged_priority_ordering_is_starvation_safe():
    eng = _FakeEngine()
    cfg = AdmissionConfig(priorities={"gold": 0, "free": 5},
                          aging_s=0.1)
    # a huge max-wait parks the batcher so _select_indices is
    # inspectable deterministically
    q = QueryQueue(eng, max_wait_ms=10_000.0, admission=cfg)
    try:
        q.submit(ROW, tenant="free")
        q.submit(ROW, tenant="gold")
        now = time.monotonic()
        order = [q._pending[i].tenant for i in q._select_indices(now)]
        # fresh: configured priority wins, arrival order loses
        assert order == ["gold", "free"]
        # age the free request one second: 10 levels of decay beats
        # gold's 5-level head start — no request starves forever
        q._pending[0].t_arr -= 1.0
        order = [q._pending[i].tenant for i in q._select_indices(now)]
        assert order == ["free", "gold"]
    finally:
        q.close()
    # the aging function itself is monotone: more wait, higher rank
    ctrl = AdmissionController(cfg)
    effs = [ctrl.effective_priority(5, w) for w in (0.0, 0.5, 1.0, 5.0)]
    assert effs == sorted(effs, reverse=True)
    assert ctrl.effective_priority(5, 1.0) < ctrl.effective_priority(
        0, 0.0)


def test_fifo_preserved_without_priorities():
    eng = _FakeEngine()
    q = QueryQueue(eng, max_wait_ms=10_000.0,
                   admission=AdmissionConfig(max_depth=100))
    try:
        for tenant in ("a", "b", "c"):
            q.submit(ROW, tenant=tenant)
        order = [q._pending[i].tenant
                 for i in q._select_indices(time.monotonic())]
        assert order == ["a", "b", "c"]
        # an explicit per-request priority= reorders even without a
        # configured tenant priority table (submit's documented
        # override contract)
        q.submit(ROW, tenant="d", priority=-1)
        order = [q._pending[i].tenant
                 for i in q._select_indices(time.monotonic())]
        assert order[0] == "d"
    finally:
        q.close()


# -- env configuration ----------------------------------------------------
def test_admission_config_from_env(monkeypatch):
    assert AdmissionConfig.from_env({}) is None  # no knobs -> disabled
    env = {
        "KNN_TPU_ADMISSION_MAX_DEPTH": "64",
        "KNN_TPU_ADMISSION_SHED": "1",
        "KNN_TPU_ADMISSION_DEFAULT_DEADLINE_MS": "250",
        "KNN_TPU_ADMISSION_QUOTAS": "gold:100:20, free:10",
        "KNN_TPU_ADMISSION_PRIORITIES": "gold:0,free:5",
        "KNN_TPU_ADMISSION_AGING_MS": "500",
    }
    cfg = AdmissionConfig.from_env(env)
    assert cfg.max_depth == 64
    assert cfg.shed is True
    assert cfg.default_deadline_ms == 250.0
    assert cfg.quotas == {"gold": (100.0, 20.0), "free": (10.0, 10.0)}
    assert cfg.priorities == {"gold": 0, "free": 5}
    assert cfg.aging_s == pytest.approx(0.5)
    with pytest.raises(ValueError, match="QUOTAS"):
        AdmissionConfig.from_env({"KNN_TPU_ADMISSION_QUOTAS": "bad"})
    with pytest.raises(ValueError, match="max_depth"):
        AdmissionConfig.from_env({"KNN_TPU_ADMISSION_MAX_DEPTH": "0"})
    # a typo'd knob must FAIL, not silently enable an unbounded config
    with pytest.raises(ValueError, match="unrecognized"):
        AdmissionConfig.from_env({"KNN_TPU_ADMISSION_MAX_DEPT": "64"})


def test_admission_config_validation():
    with pytest.raises(ValueError, match="quota"):
        AdmissionConfig(quotas={"a": (0.0, 1.0)}).validate()
    with pytest.raises(ValueError, match="aging_s"):
        AdmissionConfig(aging_s=0).validate()
    with pytest.raises(ValueError, match="default_deadline_ms"):
        AdmissionConfig(default_deadline_ms=-1).validate()


# -- per-tenant metrics + grouped SLOs ------------------------------------
def test_tenant_tagging_produces_per_tenant_series(served):
    prog, engine, qdata = served
    with QueryQueue(engine, max_wait_ms=1.0) as q:
        q.submit(qdata[:3], tenant="gold").result()
        q.submit(qdata[:2], tenant="free").result()
        q.submit(qdata[:2]).result()  # untagged: NO tenant series
    assert obs.counter(mn.TENANT_REQUESTS, tenant="gold").get() == 1.0
    assert obs.counter(mn.TENANT_REQUESTS, tenant="free").get() == 1.0
    snap = obs.snapshot()
    tenants = {s["labels"]["tenant"]
               for s in snap[mn.TENANT_REQUESTS]["series"]}
    assert tenants == {"gold", "free"}
    lat = {s["labels"]["tenant"]: s["value"]
           for s in snap[mn.TENANT_REQUEST_LATENCY]["series"]}
    assert lat["gold"]["count"] == 1 and lat["gold"]["sum"] > 0
    # direct engine submissions tag the same family
    engine.submit(qdata[:2], tenant="gold").result()
    assert obs.counter(mn.TENANT_REQUESTS, tenant="gold").get() == 2.0


def test_grouped_slo_fires_per_tenant_not_globally():
    eng = slo.SLOEngine()
    eng.evaluate(now=0.0)  # baseline counter sample BEFORE the burst
    obs.counter(mn.TENANT_REQUESTS, tenant="a").inc(100)
    obs.counter(mn.TENANT_ERRORS, tenant="a").inc(50)
    obs.counter(mn.TENANT_REQUESTS, tenant="b").inc(100)
    rep = eng.evaluate(now=300.0)
    entry = rep["objectives"]["tenant_availability"]
    assert entry["group_by"] == "tenant"
    assert entry["breached"] == ["a"]  # b is healthy
    assert rep["breached"] == ["tenant_availability:a"]
    assert entry["groups"]["a"]["windows"]["slow"]["burn_rate"] > 6
    assert entry["groups"]["b"]["breached"] is False
    # the alert is edge-triggered, per tenant, and carries the tenant
    alerts = [e for e in obs.get_event_log().recent()
              if e.get("name") == "slo.alert"]
    assert [(a["objective"], a["state"], a.get("tenant"))
            for a in alerts] == [("tenant_availability:a", "firing", "a")]
    assert obs.gauge(mn.SLO_BREACHED,
                     objective="tenant_availability:a").get() == 1.0
    assert obs.gauge(mn.SLO_BREACHED,
                     objective="tenant_availability:b").get() == 0.0
    # recovery clears exactly a's breach
    obs.counter(mn.TENANT_REQUESTS, tenant="a").inc(5000)
    rep = eng.evaluate(now=900.0)
    assert rep["breached"] == []
    states = [(a["objective"], a["state"]) for a in
              obs.get_event_log().recent() if a.get("name") == "slo.alert"]
    assert states == [("tenant_availability:a", "firing"),
                      ("tenant_availability:a", "resolved")]


def test_errors_without_request_growth_breach_instead_of_hiding():
    """A tenant whose every request fails before the success-side
    counter increments (errors grow, requests don't) must read as the
    worst ratio, not as healthy-by-division-by-zero."""
    eng = slo.SLOEngine()
    eng.evaluate(now=0.0)
    obs.counter(mn.TENANT_ERRORS, tenant="broken").inc(50)
    rep = eng.evaluate(now=300.0)
    entry = rep["objectives"]["tenant_availability"]
    assert entry["breached"] == ["broken"]
    assert rep["breached"] == ["tenant_availability:broken"]


def test_grouped_quantile_slo_per_tenant():
    eng = slo.SLOEngine()
    h = obs.histogram(mn.TENANT_REQUEST_LATENCY, tenant="slowpoke")
    for _ in range(20):
        h.observe(3.0)  # p99 3 s >> 1 s threshold
    obs.histogram(mn.TENANT_REQUEST_LATENCY, tenant="quick").observe(0.01)
    rep = eng.evaluate(now=0.0)
    entry = rep["objectives"]["tenant_request_p99"]
    assert entry["breached"] == ["slowpoke"]
    assert entry["groups"]["slowpoke"]["value_s"] == pytest.approx(3.0)
    assert entry["groups"]["quick"]["breached"] is False
    # the doctor/statusz text renders grouped objectives per tenant
    # (not the ungrouped-shape garbage lines)
    text = obs.health.render_text({"slo": rep})
    assert "tenant_request_p99 (per tenant): 1/2 breached" in text
    assert "tenant_request_p99:slowpoke: BREACHED" in text
    assert "tenant_request_p99:quick: ok" in text
    assert "burn={}" not in text and "None=Nones" not in text
    # idle grouped objectives render as a quiet one-liner
    idle = obs.health.render_text(
        {"slo": {"objectives": {"tenant_availability": {
            "kind": "ratio", "group_by": "tenant", "groups": {},
            "breached": []}}}})
    assert "tenant_availability: no tenant traffic" in idle


# -- disabled-mode bitwise identity ---------------------------------------
def test_admission_off_is_bitwise_identical_prepr_behavior(served):
    """The contract the whole PR hangs off: a default-built queue has
    the pre-admission stats() shape, produces bitwise-identical
    results, and mints NO admission/tenant metric series."""
    prog, engine, qdata = served
    with QueryQueue(engine, max_wait_ms=1.0) as q:
        d_q, i_q = q.submit(qdata[:5]).result()
    # bitwise vs the engine's own bucketed dispatch of the same rows
    d_e, i_e = engine.submit(qdata[:5]).result()
    assert np.array_equal(d_q, d_e) and np.array_equal(i_q, i_e)
    st = q.stats()
    assert set(st) == {"requests", "dispatches", "coalesced_rows",
                       "errors", "latency_ms", "engine"}
    snap = obs.snapshot()
    assert not any(name.startswith(("knn_tpu_admission_",
                                    "knn_tpu_tenant_"))
                   for name in snap)
    # engine stats shape untouched either (no admission section)
    assert "admission" not in engine.stats()


# -- the brownout acceptance ----------------------------------------------
def test_brownout_sheds_holds_slo_serves_both_tenants_and_recovers(served):
    """At ~5x the measured closed-loop capacity the admission-enabled
    queue sheds with explicit outcomes while both tenants keep being
    served; the backlog drains, and after the burst a normal-rate run on
    the same queue recovers — shed, don't collapse.  Every assertion is
    a count or an outcome: the admitted tail's p99 is the SLO engine's
    to judge on a machine that serves, not this suite's on one that
    shares its cores."""
    prog, engine, qdata = served
    # closed-loop anchor: the rate one-at-a-time round trips sustain
    with QueryQueue(engine, max_wait_ms=1.0) as q0:
        t0 = time.monotonic()
        futs = [q0.submit(qdata[:2]) for _ in range(24)]
        for f in futs:
            f.result()
        anchor = 24 / (time.monotonic() - t0)
    deadline_ms = 100.0
    cfg = AdmissionConfig(
        max_depth=16, shed=True,
        # finite but per-tenant-fair quotas: each tenant may use up to
        # ~60% of capacity, so neither can crowd the other out
        quotas={"gold": (max(1.5, 0.6 * anchor), max(4.0, anchor / 4)),
                "free": (max(1.5, 0.6 * anchor), max(4.0, anchor / 4))},
        priorities={"gold": 0, "free": 2}, aging_s=0.05)
    tenants = (
        loadgen.TenantSpec("gold", weight=1, batch_sizes=(1, 2),
                           deadline_ms=deadline_ms, priority=0),
        loadgen.TenantSpec("free", weight=1, batch_sizes=(1, 2),
                           deadline_ms=deadline_ms, priority=2),
    )
    burst = loadgen.WorkloadSpec(rate_qps=5 * anchor, duration_s=1.0,
                                 seed=21, tenants=tenants)
    with QueryQueue(engine, max_wait_ms=1.0, admission=cfg) as q:
        rep = loadgen.run_workload(q, loadgen.generate(burst),
                                   queries=qdata, submitters=4,
                                   waiters=4)
        # overload produced explicit outcomes, not a collapse
        assert rep["rejected"] + rep["shed"] > 0
        declined = {k: v for k, v in rep["outcomes"].items()
                    if k != "ok"}
        assert all(k.startswith(("rejected:", "shed:"))
                   for k in declined), declined
        # some were admitted and answered, and each has its latency
        assert rep["ok"] > 0
        assert rep["latency_ms"]["count"] == rep["ok"]
        # no tenant starved: both kept completing under overload
        for tenant in ("gold", "free"):
            assert rep["per_tenant"][tenant]["ok"] > 0, rep["per_tenant"]
        # the burst ENDS: wait for the in-flight backlog to drain (the
        # recovery claim is about post-burst behavior, not about racing
        # the tail of the burst through a still-full depth bound)
        for _ in range(200):
            if q._out_req == 0:
                break
            time.sleep(0.05)
        assert q._out_req == 0  # cleanly drained, nothing wedged
        # recovery on the SAME queue: calm traffic flows again.  The
        # closed-loop anchor over-estimates open-loop capacity (burst
        # probes coalesce maximally), so "calm" is well below it, and
        # its deadline is one only a wedged queue misses.
        calm_tenants = tuple(
            loadgen.TenantSpec(t.name, weight=t.weight,
                               batch_sizes=t.batch_sizes,
                               deadline_ms=10_000.0, priority=t.priority)
            for t in tenants)
        calm = loadgen.WorkloadSpec(rate_qps=0.2 * anchor,
                                    duration_s=0.8, seed=22,
                                    tenants=calm_tenants)
        rep2 = loadgen.run_workload(q, loadgen.generate(calm),
                                    queries=qdata, submitters=2,
                                    waiters=2)
        assert rep2["ok"] >= 0.6 * rep2["offered"], rep2["outcomes"]
        assert rep2["errors"] == 0
        st = q.stats()["admission"]
        assert st["admitted"] == rep["ok"] + rep2["ok"] + rep["shed"] \
            + rep2["shed"] + rep["errors"] + rep2["errors"]
    # admission surfaced through the catalog metrics
    snap = obs.snapshot()
    assert mn.ADMISSION_ADMITTED in snap
    assert any(name in snap for name in (mn.ADMISSION_REJECTED,
                                         mn.ADMISSION_SHED))
