"""Tail forensics (knn_tpu.obs.waterfall + blackbox): per-request
waterfalls tile measured latency within the stated tolerance (gaps
explicit as ``unattributed``), histogram exemplars join the worst
samples back to traces, the flight recorder writes exactly one
postmortem bundle per SLO breach transition, rotation-straddling
requests reconstruct from the merged log generations, and the whole
layer is jax-free and absent under KNN_TPU_OBS=0 — the acceptance
surface of the tail-forensics ISSUE."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from knn_tpu import loadgen, obs
from knn_tpu.obs import blackbox, names as mn, slo, trace, waterfall

REPO = __file__.rsplit("/tests/", 1)[0]

K = 5
DIM = 12
BUCKETS = (8, 16)


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Every test starts from an empty ENABLED registry/ring/SLO/health
    state (the forensics layer reads all four)."""
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    obs.reset_slo_engine()
    obs.health.reset()
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)
    obs.reset_slo_engine()
    obs.health.reset()


@pytest.fixture(scope="module")
def served():
    """One placed engine for the module (warmup once); queues are
    built per test."""
    from knn_tpu.parallel import ShardedKNN, make_mesh
    from knn_tpu.serving.engine import ServingEngine

    rng = np.random.default_rng(3)
    db = rng.standard_normal((400, DIM)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(), k=K)
    eng = ServingEngine(prog, buckets=BUCKETS)
    eng.warmup()
    qdata = rng.standard_normal((64, DIM)).astype(np.float32)
    return eng, qdata


def _tile_error(w):
    """|total - sum(segments incl. unattributed)| — zero by
    construction up to the per-segment rounding."""
    return abs(w["total_s"] - sum(s["dur_s"] for s in w["segments"])
               + w["overlap_s"])


# -- registry exemplars ----------------------------------------------------
def test_exemplars_bounded_worst_first_and_thread_safe():
    h = obs.histogram(mn.QUEUE_REQUEST_LATENCY)

    def hammer(base):
        for i in range(200):
            h.observe((base + i) / 1e4, exemplar=f"tid{base + i:012d}")

    ts = [threading.Thread(target=hammer, args=(b,))
          for b in (0, 1000, 2000, 3000)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    ex = h.exemplars()
    # bounded at the cap, sorted worst-first, and exactly the global
    # worst values survived the races
    from knn_tpu.obs.registry import EXEMPLAR_CAP

    assert len(ex) == EXEMPLAR_CAP
    vals = [e["value"] for e in ex]
    assert vals == sorted(vals, reverse=True)
    assert vals[0] == pytest.approx(3199 / 1e4)
    assert all(e["trace_id"].startswith("tid") for e in ex)
    # summaries carry them; exemplar-free histograms stay unchanged
    assert "exemplars" in h.summary()
    h2 = obs.histogram(mn.QUEUE_WAIT)
    h2.observe(0.5)
    assert "exemplars" not in h2.summary()


def test_exemplar_rides_prometheus_comment_line():
    obs.histogram(mn.QUEUE_REQUEST_LATENCY).observe(
        0.25, exemplar="feedface00000001")
    text = obs.prometheus_text()
    ex = [ln for ln in text.splitlines() if ln.startswith("# EXEMPLAR ")]
    assert ex == [
        f"# EXEMPLAR {mn.QUEUE_REQUEST_LATENCY}"
        '{quantile="0.99"} {trace_id="feedface00000001"} '
        + ex[0].rsplit("} ", 1)[1]]
    # ...and the value/ts tail parses
    val, ts = ex[0].rsplit("} ", 1)[1].split()
    assert float(val) == 0.25 and float(ts) > 0
    # every NON-comment line stays plain `name{labels} value` — a
    # text-0.0.4 scraper must keep parsing when exemplars appear
    for ln in text.splitlines():
        if not ln.startswith("#"):
            assert " # " not in ln
    assert text.count("# EXEMPLAR") == 1


def test_disabled_mode_exemplars_are_noop():
    obs.reset(enabled=False)
    h = obs.histogram(mn.QUEUE_REQUEST_LATENCY)
    h.observe(0.5, exemplar="dead000000000001")  # must not raise
    assert h.exemplars() == []
    assert waterfall.slowest_table() == []


# -- reconstruction over real serving traffic ------------------------------
def test_queued_requests_tile_measured_latency(served):
    from knn_tpu.serving.queue import QueryQueue

    eng, qdata = served
    rng = np.random.default_rng(5)
    sizes = (2, 3, 4, 1, 5, 2, 3, 4)
    with QueryQueue(eng, max_wait_ms=10.0) as qq:
        futs = [qq.submit(qdata[: s],
                          tenant=("gold" if i % 2 else "free"))
                for i, s in enumerate(sizes)]
        tids = [f.trace_id for f in futs]
        for f in futs:
            f.result(timeout=60)
    assert all(tids) and len(set(tids)) == len(sizes)
    wfs = waterfall.reconstruct(obs.get_event_log().recent())
    for i, tid in enumerate(tids):
        w = wfs[tid]
        assert w["kind"] == "queued"
        assert w["tenant"] == ("gold" if i % 2 else "free")
        assert w["rows"] == sizes[i]
        assert w["bucket"] in BUCKETS
        # the ACCEPTANCE: segments tile the measured arrival-to-result
        # latency — any remainder is the explicit unattributed segment,
        # and the whole thing closes within the stated tolerance
        assert _tile_error(w) < 1e-4
        assert w["complete"], w
        assert w["unattributed_s"] <= w["tolerance_s"]
        names_ = [s["name"] for s in w["segments"]]
        assert names_[: len(waterfall.SEGMENTS)] == list(waterfall.SEGMENTS)
        # every queued request chains to a real batch-level request
        assert w["batch_trace_id"] in wfs
        assert wfs[w["batch_trace_id"]]["kind"] == "batch"
    # batch plumbing never double-counts in attribution
    agg = waterfall.attribute(wfs)
    assert agg["requests"] == len(sizes)
    assert set(agg["by_tenant"]) == {"gold", "free"}
    assert all(str(b) in {str(x) for x in BUCKETS}
               for b in agg["by_bucket"])
    for bands in (agg["overall"], *agg["by_tenant"].values()):
        assert bands["p50_band"]["dominant"] in (
            waterfall.SEGMENTS + ("unattributed",))
        assert bands["p99_band"]["dominant"] in (
            waterfall.SEGMENTS + ("unattributed",))


def test_direct_engine_request_reconstructs(served):
    eng, qdata = served
    h = eng.submit(qdata[:3], tenant="direct-t")
    h.result()
    w = waterfall.reconstruct(obs.get_event_log().recent())[h.trace_id]
    assert w["kind"] == "direct"
    assert w["tenant"] == "direct-t"
    assert w["bucket"] == 8
    assert w["complete"] and _tile_error(w) < 1e-4
    assert [s["name"] for s in w["segments"]][:4] == list(
        waterfall.DIRECT_SEGMENTS)


def test_engine_stats_and_statusz_carry_slowest_requests(served):
    eng, qdata = served
    obs.health.register_engine(eng)  # module fixture predates reset
    for s in (2, 4, 3):
        eng.submit(qdata[:s]).result()
    st = eng.stats()
    rows = st["slowest_requests"]
    assert rows and all(r["trace_id"] and r["latency_ms"] > 0
                        for r in rows)
    assert "waterfall" not in rows[0]  # stats() stays light
    lats = [r["latency_s"] for r in rows]
    assert lats == sorted(lats, reverse=True)
    rep = obs.health.report()
    deep = [r for r in rep["slowest_requests"] if r.get("waterfall")]
    assert deep, "statusz slowest must carry inline waterfalls"
    assert deep[0]["waterfall"]["complete"] in (True, False)
    text = obs.health.render_text(rep)
    assert "slowest recent request" in text
    assert deep[0]["trace_id"] in text


def test_loadgen_records_trace_ids_and_every_admitted_reconstructs(served):
    from knn_tpu.serving.queue import QueryQueue

    eng, qdata = served
    spec = loadgen.WorkloadSpec(
        rate_qps=120, duration_s=0.4, seed=11,
        tenants=(loadgen.TenantSpec("a", batch_sizes=(1, 2)),
                 loadgen.TenantSpec("b", batch_sizes=(2, 4))))
    reqs = loadgen.generate(spec)
    with QueryQueue(eng, max_wait_ms=5.0) as qq:
        rep = loadgen.run_workload(qq, reqs, queries=qdata,
                                   include_records=True)
    ok = [r for r in rep["records"] if r["outcome"] == "ok"]
    assert ok
    wfs = waterfall.reconstruct(obs.get_event_log().recent())
    for r in ok:
        # the satellite: every request's record carries the trace id
        # the queue stamped, joinable against its waterfall
        assert r["trace_id"], r
        w = wfs.get(r["trace_id"])
        assert w is not None, f"no waterfall for {r['trace_id']}"
        assert w["complete"], w
        assert _tile_error(w) < 1e-4
    # report() surfaces the worst admitted requests' ids
    slowest = rep["slowest"]
    assert slowest and all(e["trace_id"] for e in slowest)
    assert slowest[0]["latency_ms"] >= slowest[-1]["latency_ms"]
    assert slowest[0]["trace_id"] in wfs


def test_synthetic_target_and_knee_steps_carry_slowest():
    pool = np.zeros((8, 4), np.float32)
    spec = loadgen.WorkloadSpec(
        rate_qps=300, duration_s=0.2, seed=2,
        tenants=(loadgen.TenantSpec("t", batch_sizes=(1,)),))
    block = loadgen.knee_sweep(
        lambda: loadgen.SyntheticTarget(2000.0), spec, [100.0, 300.0],
        queries=pool, slo_p99_ms=100.0)
    steps = [s for s in block["rate_steps"] if s["ok"]]
    assert steps
    for s in steps:
        assert s["slowest"], "knee steps must surface the worst ids"
        assert all(e["trace_id"] for e in s["slowest"])
    assert not loadgen.validate_knee_block(block)


# -- explicit gaps, tolerance, rotation ------------------------------------
def _emit_queued(tid, bid, *, queue_wait=0.010, dispatch=0.002,
                 join=0.003, request=0.006, deliver=0.0005,
                 admission=0.001, total=None, batch_spans=True):
    trace.record_span("serving.admission", tid, admission, rows=1)
    trace.record_span("serving.queue_wait", tid, queue_wait, rows=1,
                      tenant="t")
    if batch_spans:
        trace.record_span("serving.dispatch", bid, dispatch, rows=1,
                          buckets=[8], op="search")
        trace.record_span("serving.join", bid, join, op="search")
        trace.record_span("serving.request", bid, request, rows=1,
                          op="search")
    trace.record_span("serving.deliver", tid, deliver, tenant="t")
    if total is None:
        total = queue_wait + request + deliver + 0.001
    trace.record_span("serving.queued_request", tid, total, rows=1,
                      op="search", batch_trace_id=bid, tenant="t")
    return total


def test_missing_spans_surface_as_explicit_unattributed_gap():
    # the batch's spans never made it (rotated away / lost): the gap
    # must appear as the explicit unattributed segment and fail the
    # completeness check — never be silently absorbed
    total = _emit_queued("aaaa000000000001", "bbbb000000000001",
                        total=0.5, batch_spans=False)
    w = waterfall.reconstruct(obs.get_event_log().recent())[
        "aaaa000000000001"]
    assert w["segments"][-1]["name"] == "unattributed"
    gap = w["unattributed_s"]
    assert gap == pytest.approx(
        total - 0.010 - 0.0005 - 0.001 + 0.001, abs=1e-5)
    assert gap > w["tolerance_s"]
    assert not w["complete"]
    # tolerance is STATED on the waterfall, not implied
    assert w["tolerance_s"] == pytest.approx(
        waterfall.tolerance_s(total), abs=1e-9)


def test_overlapping_spans_reported_not_clamped_silently():
    # segments summing PAST the total: overlap_s carries the excess
    _emit_queued("cccc000000000001", "dddd000000000001",
                 queue_wait=0.4, request=0.4, total=0.05)
    w = waterfall.reconstruct(obs.get_event_log().recent())[
        "cccc000000000001"]
    assert w["overlap_s"] > w["tolerance_s"]
    assert not w["complete"]


def test_rotation_straddling_request_reconstructs(tmp_path):
    path = str(tmp_path / "events.jsonl")
    # cap sized so the filler below forces exactly ONE rotation and
    # the tail spans fit the fresh generation without a second one
    obs.reset_event_log(path, max_bytes=2000)
    tid, bid = "eeee000000000001", "ffff000000000001"
    # head of the request's span chain lands in the first generation
    # (queue_wait big enough that losing it MUST blow the tolerance)
    trace.record_span("serving.admission", tid, 0.001, rows=1)
    trace.record_span("serving.queue_wait", tid, 0.030, rows=1)
    # filler traffic forces the rotation between the head and the tail
    i = 0
    while not os.path.exists(path + ".1"):
        trace.emit_event("filler", i=i)
        i += 1
        assert i < 100, "rotation never triggered"
    trace.record_span("serving.dispatch", bid, 0.002, rows=1,
                      buckets=[8], op="search")
    trace.record_span("serving.join", bid, 0.003, op="search")
    trace.record_span("serving.request", bid, 0.006, rows=1, op="search")
    trace.record_span("serving.deliver", tid, 0.0005)
    trace.record_span("serving.queued_request", tid, 0.0375, rows=1,
                      op="search", batch_trace_id=bid)
    obs.get_event_log().close()
    # the head spans are ONLY in the rotated generation
    cur = open(path).read()
    assert "serving.queue_wait" not in cur
    assert "serving.queue_wait" in open(path + ".1").read()
    # the current generation alone cannot complete the request...
    cur_events = [json.loads(ln) for ln in cur.splitlines()]
    w_cur = waterfall.reconstruct(cur_events)[tid]
    assert not w_cur["complete"]
    # ...the merged reader can (the satellite's pin)
    events = waterfall.read_jsonl_events(path)
    w = waterfall.reconstruct(events)[tid]
    assert w["complete"], w
    assert _tile_error(w) < 1e-4
    assert w["unattributed_s"] <= w["tolerance_s"]


# -- flight recorder -------------------------------------------------------
def _force_breach(eng, *, now0=0.0, now1=300.0):
    eng.evaluate(now=now0)
    obs.counter(mn.SERVING_REQUESTS, op="search").inc(100)
    obs.counter(mn.SERVING_ERRORS, op="search").inc(50)
    return eng.evaluate(now=now1)


def test_flight_recorder_exactly_one_bundle_per_breach_transition(
        tmp_path, monkeypatch):
    d = tmp_path / "pm"
    monkeypatch.setenv(blackbox.DIR_ENV, str(d))
    # an exemplar request whose spans are still in the ring: the
    # bundle must carry its waterfall
    tid = "cafe000000000001"
    trace.record_span("serving.dispatch", tid, 0.002, rows=4,
                      buckets=[8], op="search")
    trace.record_span("serving.join", tid, 0.001, op="search")
    trace.record_span("serving.request", tid, 0.4, rows=4, op="search")
    obs.histogram(mn.SERVING_REQUEST_LATENCY, op="search").observe(
        0.4, exemplar=tid)
    eng = slo.SLOEngine()
    rep = _force_breach(eng)
    assert "serving_availability" in rep["breached"]
    bundles = sorted(os.listdir(d))
    assert len(bundles) == 1, bundles
    # still breached on re-evaluation: reported, NOT re-dumped
    eng.evaluate(now=310.0)
    assert len(os.listdir(d)) == 1
    assert obs.counter(mn.POSTMORTEMS_WRITTEN,
                       objective="serving_availability").get() == 1.0
    b = blackbox.read_bundle(str(d / bundles[0]))
    assert b["version"] == blackbox.BUNDLE_VERSION
    assert b["objective"] == "serving_availability"
    assert b["state"] == "firing"
    for key in ("breach_detail", "slo", "statusz", "metrics", "events",
                "slowest", "attribution", "env"):
        assert key in b, key
    # the exemplar request's waterfall rides the bundle
    ex = [r for r in b["slowest"] if r["trace_id"] == tid]
    assert ex and ex[0]["waterfall"]["kind"] == "direct"
    # the statusz inside reused the firing evaluation (no re-pass)
    assert b["slo"]["breached"] == rep["breached"]
    # statusz lists the inventory
    pm = obs.health.report()["postmortems"]
    assert pm["dir"] == str(d)
    assert [x["file"] for x in pm["bundles"]] == bundles
    # recovery then a second burst: a SECOND transition, a second bundle
    obs.counter(mn.SERVING_REQUESTS, op="search").inc(100000)
    eng.evaluate(now=700.0)
    obs.counter(mn.SERVING_ERRORS, op="search").inc(60000)
    rep = eng.evaluate(now=1400.0)
    assert "serving_availability" in rep["breached"]
    assert len(os.listdir(d)) == 2


def test_flight_recorder_retention_cap_and_disabled_modes(
        tmp_path, monkeypatch):
    d = tmp_path / "pm"
    monkeypatch.setenv(blackbox.DIR_ENV, str(d))
    monkeypatch.setenv(blackbox.KEEP_ENV, "2")
    for i in range(4):
        assert blackbox.on_breach(f"obj_{i}", {"i": i}) is not None
    files = sorted(os.listdir(d))
    assert len(files) == 2
    assert files[0].endswith("obj_2.json") and files[1].endswith(
        "obj_3.json")
    # unwritable destination degrades to an event, never an exception
    monkeypatch.setenv(blackbox.DIR_ENV, "/proc/nope/denied")
    assert blackbox.on_breach("obj_x", {}) is None
    errs = [e for e in obs.get_event_log().recent()
            if e.get("name") == "postmortem.error"]
    assert errs
    # no destination -> disarmed
    monkeypatch.delenv(blackbox.DIR_ENV)
    assert not blackbox.enabled()
    assert blackbox.on_breach("obj_y", {}) is None
    assert blackbox.status() == {"dir": None, "keep": 2, "bundles": []}
    # obs off -> disarmed even with a destination
    monkeypatch.setenv(blackbox.DIR_ENV, str(d))
    obs.reset(enabled=False)
    assert not blackbox.enabled()
    assert blackbox.on_breach("obj_z", {}) is None
    assert len(os.listdir(d)) == 2


def test_obs_off_pins_no_forensics_and_stats_sections_absent(served):
    from knn_tpu.serving.queue import QueryQueue

    eng, qdata = served
    obs.reset(enabled=False)
    obs.reset_event_log(None)
    with QueryQueue(eng, max_wait_ms=1.0) as qq:
        fut = qq.submit(qdata[:3])
        fut.result(timeout=60)
    assert fut.trace_id is None  # ids are an obs feature
    assert obs.get_event_log().recent() == []  # no spans at all
    st = eng.stats()
    assert "slowest_requests" not in st
    assert "slo" not in st
    assert waterfall.slowest_table() == []
    assert waterfall.reconstruct([]) == {}
    assert "# EXEMPLAR" not in obs.prometheus_text()


# -- the jax-free CLI ------------------------------------------------------
def test_cli_waterfall_renders_bundle_and_log_jax_free(
        tmp_path, monkeypatch):
    d = tmp_path / "pm"
    monkeypatch.setenv(blackbox.DIR_ENV, str(d))
    tid = "beef000000000001"
    log_path = str(tmp_path / "events.jsonl")
    obs.reset_event_log(log_path)
    trace.record_span("serving.dispatch", tid, 0.002, rows=2,
                      buckets=[8], op="search")
    trace.record_span("serving.join", tid, 0.001, op="search")
    trace.record_span("serving.request", tid, 0.02, rows=2, op="search")
    obs.histogram(mn.SERVING_REQUEST_LATENCY, op="search").observe(
        0.02, exemplar=tid)
    bundle = blackbox.on_breach("serving_availability", {"w": 1})
    assert bundle
    obs.get_event_log().close()
    env = {**os.environ, "KNN_TPU_OBS": "1"}
    for args in (["--bundle", bundle], ["--log", log_path],
                 ["--log", log_path, "--trace-id", tid]):
        code = (
            "import sys\n"
            "from knn_tpu import cli\n"
            f"rc = cli.main(['waterfall'] + {args!r})\n"
            "assert 'jax' not in sys.modules, 'waterfall imported jax'\n"
            "sys.exit(rc)\n")
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=120)
        assert r.returncode == 0, r.stderr
        assert tid in r.stdout
        assert "attribution over" in r.stdout
    # --json stdout must parse as ONE JSON document (no headers)
    r = subprocess.run(
        [sys.executable, "-c",
         "from knn_tpu import cli\n"
         f"cli.main(['waterfall', '--bundle', {bundle!r}, '--json'])"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["objective"] == "serving_availability"
    # unreadable source exits 1
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\nfrom knn_tpu import cli\n"
         "sys.exit(cli.main(['waterfall', '--bundle',"
         " '/nope/missing.json']))"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
