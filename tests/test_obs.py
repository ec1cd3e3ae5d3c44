"""The unified telemetry subsystem (knn_tpu.obs): registry exactness and
thread-safety, disabled-mode no-op identity, exporter round-trips, span
propagation through micro-batch coalescing, and the ground-truth match
between scraped counters and independently counted serving/certified
activity — the acceptance surface of the obs ISSUE."""

import json
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn

REPO = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Every test starts from an empty ENABLED registry + event ring and
    leaves the env-driven state behind for the rest of the suite."""
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


# --- registry exactness -------------------------------------------------
def test_counter_gauge_histogram_exactness():
    c = obs.counter(mn.QUEUE_REQUESTS)
    c.inc()
    c.inc(4)
    assert c.get() == 5.0
    g = obs.gauge(mn.QUEUE_DEPTH_ROWS)
    g.set(10)
    g.inc(2)
    g.dec(5)
    assert g.get() == 7.0
    h = obs.histogram(mn.QUEUE_WAIT)
    for v in range(1, 101):
        h.observe(v / 100.0)
    s = h.summary()
    assert s["count"] == 100
    assert s["sum"] == pytest.approx(50.5)
    assert s["min"] == pytest.approx(0.01) and s["max"] == pytest.approx(1.0)
    assert s["p50"] == pytest.approx(0.505, abs=0.02)
    assert s["p99"] == pytest.approx(0.99, abs=0.02)


def test_histogram_window_is_bounded_but_lifetime_is_not():
    from knn_tpu.obs.registry import Histogram

    h = Histogram(window=16)
    h.observe_many(range(1000))
    s = h.summary()
    assert s["count"] == 1000  # lifetime
    assert s["window"] == 16  # bounded percentile window
    assert s["p50"] >= 983  # percentiles over the RECENT window


def test_labels_create_distinct_series_and_same_handle():
    a = obs.counter(mn.SERVING_REQUESTS, op="search")
    b = obs.counter(mn.SERVING_REQUESTS, op="predict")
    assert a is not b
    assert obs.counter(mn.SERVING_REQUESTS, op="search") is a
    a.inc(3)
    snap = obs.snapshot()[mn.SERVING_REQUESTS]
    by_op = {s["labels"]["op"]: s["value"] for s in snap["series"]}
    assert by_op == {"search": 3.0, "predict": 0.0}


def test_uncataloged_names_and_label_mismatches_refused():
    with pytest.raises(ValueError, match="not in the catalog"):
        obs.counter("knn_tpu_made_up_total")
    with pytest.raises(ValueError, match="is a counter"):
        obs.gauge(mn.QUEUE_REQUESTS)
    with pytest.raises(ValueError, match="takes labels"):
        obs.counter(mn.SERVING_REQUESTS)  # missing the op label
    with pytest.raises(ValueError):
        obs.counter(mn.QUEUE_REQUESTS, op="x")  # spurious label
    # the disabled registry validates identically (fail fast in dev)
    obs.reset(enabled=False)
    with pytest.raises(ValueError, match="not in the catalog"):
        obs.counter("knn_tpu_made_up_total")


def test_thread_hammer_counts_exact():
    c = obs.counter(mn.QUEUE_REQUESTS)
    h = obs.histogram(mn.QUEUE_WAIT)
    g = obs.gauge(mn.QUEUE_DEPTH_ROWS)
    n_threads, per = 8, 2000

    def work():
        for i in range(per):
            c.inc()
            h.observe(i)
            g.inc()

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.get() == n_threads * per
    assert h.summary()["count"] == n_threads * per
    assert g.get() == n_threads * per


# --- disabled mode ------------------------------------------------------
def test_disabled_mode_noop_identity():
    obs.reset(enabled=False)
    c = obs.counter(mn.QUEUE_REQUESTS)
    # ONE shared inert instrument across names/kinds/labels — no
    # allocation, no state, nothing exported
    assert c is obs.counter(mn.QUEUE_DISPATCHES)
    assert c is obs.gauge(mn.QUEUE_DEPTH_ROWS)
    assert c is obs.histogram(mn.QUEUE_WAIT)
    assert c is obs.NOOP
    c.inc()
    c.observe(3.0)
    assert c.get() == 0.0
    assert obs.snapshot() == {}
    assert obs.new_trace_id() is None
    with obs.span("serving.dispatch") as sp:
        sp.set("k", 1)
    assert sp.trace_id is None
    assert obs.get_event_log().recent() == []
    assert not obs.enabled()


def test_env_controls_default(monkeypatch):
    monkeypatch.setenv("KNN_TPU_OBS", "0")
    obs.reset()
    assert not obs.enabled()
    monkeypatch.delenv("KNN_TPU_OBS")
    obs.reset()
    assert obs.enabled()  # default-on


# --- exporters ----------------------------------------------------------
def test_prometheus_text_and_json_snapshot_round_trip(tmp_path):
    obs.counter(mn.SERVING_REQUESTS, op="search").inc(7)
    obs.gauge(mn.QUEUE_DEPTH_REQUESTS).set(3)
    obs.histogram(mn.QUEUE_WAIT).observe_many([0.1, 0.2, 0.3])
    text = obs.prometheus_text()
    assert '# TYPE knn_tpu_serving_requests_total counter' in text
    assert 'knn_tpu_serving_requests_total{op="search"} 7.0' in text
    assert 'knn_tpu_queue_depth_requests 3.0' in text
    assert '# TYPE knn_tpu_queue_wait_seconds summary' in text
    assert 'knn_tpu_queue_wait_seconds{quantile="0.5"} 0.2' in text
    assert 'knn_tpu_queue_wait_seconds_count 3' in text
    # JSON snapshot: atomic file -> identical Prometheus rendering
    path = tmp_path / "snap.json"
    obs.write_json_snapshot(str(path))
    payload = json.loads(path.read_text())
    assert payload["enabled"] is True
    assert obs.prometheus_text(payload["metrics"]) == text
    assert not list(tmp_path.glob("*.tmp"))  # no torn temp left behind


def test_http_metrics_endpoint():
    obs.counter(mn.QUEUE_REQUESTS).inc(11)
    server = obs.start_metrics_server(0)  # OS-assigned port
    try:
        port = server.server_address[1]
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "knn_tpu_queue_requests_total 11.0" in text
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert js["metrics"][mn.QUEUE_REQUESTS]["series"][0]["value"] == 11.0
    finally:
        server.shutdown()


def test_metrics_cli_renders_snapshot(tmp_path):
    obs.counter(mn.QUEUE_REQUESTS).inc(5)
    path = tmp_path / "snap.json"
    obs.write_json_snapshot(str(path))
    r = subprocess.run(
        [sys.executable, "-m", "knn_tpu.cli", "metrics",
         "--snapshot", str(path), "--format", "prom"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "knn_tpu_queue_requests_total 5.0" in r.stdout


def test_jsonl_event_log_sink(tmp_path):
    path = tmp_path / "events.jsonl"
    obs.reset_event_log(str(path))
    tid = obs.new_trace_id()
    with obs.span("serving.dispatch", trace_id=tid, op="search", rows=4):
        pass
    with obs.span("serving.compile", op="search"):  # warmup-style: no id
        pass
    obs.emit_event("queue.dispatch", rows=4)
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 3
    assert lines[0]["type"] == "span"
    assert lines[0]["span"] == "serving.dispatch"
    assert lines[0]["trace_id"] == tid and "ts" in lines[0]
    # ids are propagated, never minted inside span(): a span with no
    # request behind it must not fabricate a phantom trace
    assert "trace_id" not in lines[1]
    # every FILE line carries the process identity stamp (the fleet
    # aggregator's attribution key) — the in-memory ring does not
    assert all("identity" in ln for ln in lines)
    ident = lines[2].pop("identity")
    assert ident["process_index"] == 0 and "host" in ident
    assert ident["catalog_version"] == obs.names.catalog_version()
    assert lines[2] == {"ts": lines[2]["ts"], "type": "event",
                        "name": "queue.dispatch", "rows": 4}


def test_current_span_is_the_innermost_open_scope_of_its_thread():
    from knn_tpu.obs.trace import NOOP_SPAN

    assert obs.current_span() is NOOP_SPAN
    with obs.span("certified.call") as outer:
        with obs.span("certified.rank_correct", parent="certified.call"):
            # what code below a stage does: it never sees the scope
            obs.current_span().set("members", 12)
            seen = []
            t = threading.Thread(
                target=lambda: seen.append(obs.current_span()))
            t.start()
            t.join(timeout=30)
            assert seen == [NOOP_SPAN]  # a scope belongs to its thread
        assert obs.current_span() is outer
    assert obs.current_span() is NOOP_SPAN
    inner, call = obs.get_event_log().recent()[-2:]
    assert (inner["span"], inner["members"]) == ("certified.rank_correct", 12)
    assert "members" not in call
    obs.reset(enabled=False)
    with obs.span("certified.call"):
        assert obs.current_span() is NOOP_SPAN
        obs.current_span().set("members", 1)  # inert, as the scope is


# --- PhaseTimer (thin view over the registry) ---------------------------
def test_phase_timer_feeds_registry_and_rejects_nesting():
    from knn_tpu.utils.timing import PhaseTimer

    t = PhaseTimer()
    with t.phase("ingest"):
        pass
    with t.phase("ingest"):
        pass
    assert t.summary()["ingest"] >= 0.0
    h = obs.snapshot()[mn.PHASE_SECONDS]["series"]
    assert {"phase": "ingest"} in [s["labels"] for s in h]
    assert [s["value"]["count"] for s in h
            if s["labels"] == {"phase": "ingest"}] == [2]
    with pytest.raises(RuntimeError, match="nested"):
        with t.phase("outer"):
            with t.phase("inner"):
                pass
    # the failed nesting attempt must not wedge the timer
    with t.phase("after"):
        pass
    assert "after" in t.summary()


def test_phase_timer_concurrent_threads():
    from knn_tpu.utils.timing import PhaseTimer

    t = PhaseTimer()
    errs = []

    def work(name):
        try:
            for _ in range(200):
                with t.phase(name):
                    pass
        except Exception as e:  # pragma: no cover - the assertion surface
            errs.append(e)

    ts = [threading.Thread(target=work, args=(f"p{i}",)) for i in range(6)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert not errs
    s = t.summary()
    assert all(f"p{i}" in s for i in range(6))
    assert s["total"] >= max(s[f"p{i}"] for i in range(6)) - 1e-9


# --- serving ground truth (the acceptance criterion) --------------------
@pytest.fixture(scope="module")
def placed():
    from knn_tpu.parallel.mesh import make_mesh
    from knn_tpu.parallel.sharded import ShardedKNN

    rng = np.random.default_rng(7)
    db = rng.standard_normal((256, 16)).astype(np.float32)
    return ShardedKNN(db, mesh=make_mesh(4, 2), k=5), rng


def test_serving_trace_prometheus_matches_ground_truth(placed):
    from knn_tpu.serving.buckets import bucket_for, split_sizes
    from knn_tpu.serving.engine import ServingEngine

    prog, rng = placed
    buckets = (8, 16, 32)
    eng = ServingEngine(prog, buckets=buckets)
    eng.warmup()
    sizes = [3, 8, 17, 1, 32, 9, 2, 2]
    reqs = [rng.standard_normal((s, 16)).astype(np.float32) for s in sizes]
    _, report = eng.replay(reqs, depth=2)

    # independent ground truth: the bucket each chunk of each request
    # must land in, recomputed here from the public ladder helpers
    expect = {}
    for s in sizes:
        for chunk in split_sizes(s, buckets[-1]):
            b = bucket_for(buckets, chunk)
            expect[b] = expect.get(b, 0) + 1

    text = obs.prometheus_text()
    assert (f'knn_tpu_serving_requests_total{{op="search"}} '
            f'{float(len(sizes))}') in text
    assert (f'knn_tpu_serving_queries_total{{op="search"}} '
            f'{float(sum(sizes))}') in text
    for b, n in expect.items():
        assert (f'knn_tpu_serving_dispatches_total'
                f'{{bucket="{b}",op="search"}} {float(n)}') in text
    # engine-side lifetime counters agree with the same ground truth
    assert report["requests_total"] == len(sizes)
    assert report["queries_total"] == sum(sizes)
    assert report["errors_total"] == 0
    # per-bucket registry counters == the engine's own tallies
    assert report["per_bucket_dispatches"] == expect
    # latency histogram recorded one sample per request
    lat = obs.snapshot()[mn.SERVING_REQUEST_LATENCY]["series"]
    assert [s["value"]["count"] for s in lat
            if s["labels"] == {"op": "search"}] == [len(sizes)]


def test_lifetime_counters_outlive_latency_window(placed):
    from knn_tpu.serving.engine import ServingEngine

    prog, rng = placed
    eng = ServingEngine(prog, buckets=(8,), latency_window=2)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    for _ in range(5):
        eng.submit(q).result()
    st = eng.stats()
    # the bounded window reports 2 samples; the lifetime counters 5 —
    # the window-only-truth bug this satellite fixes
    assert st["latency_ms"]["count"] == 2
    assert st["requests_total"] == 5
    assert st["queries_total"] == 15


def test_queue_coalescing_preserves_per_request_trace_ids(placed):
    from knn_tpu.serving.engine import ServingEngine
    from knn_tpu.serving.queue import QueryQueue

    prog, rng = placed
    eng = ServingEngine(prog, buckets=(8, 16, 32))
    eng.warmup()
    reqs = [rng.standard_normal((s, 16)).astype(np.float32)
            for s in (2, 3, 4, 5)]
    with QueryQueue(eng, max_wait_ms=200.0) as qq:
        futs = [qq.submit(r) for r in reqs]
        ref = [eng.submit(r).result() for r in reqs]  # direct ground truth
        got = [f.result(timeout=60) for f in futs]
        st = qq.stats()
    # coalesced: fewer engine dispatches than requests, results intact
    assert st["dispatches"] < st["requests"] == len(reqs)
    for (gd, gi), (rd, ri) in zip(got, ref):
        np.testing.assert_array_equal(gi, ri)
        np.testing.assert_array_equal(gd, rd)

    evts = obs.get_event_log().recent()
    waits = [e for e in evts if e.get("span") == "serving.queue_wait"]
    done = [e for e in evts if e.get("span") == "serving.queued_request"]
    # one trace id per REQUEST, unique, consistent across its spans —
    # even though the requests rode one coalesced engine dispatch
    wait_ids = [e["trace_id"] for e in waits]
    assert len(wait_ids) == len(reqs) and len(set(wait_ids)) == len(reqs)
    assert sorted(e["trace_id"] for e in done) == sorted(wait_ids)
    disp = [e for e in evts if e.get("name") == "queue.dispatch"]
    members = [tid for e in disp for tid in e["member_trace_ids"]]
    assert sorted(members) == sorted(wait_ids)
    # the batch-level engine trace id is linked from every member join
    batch_ids = {e["batch_trace_id"] for e in disp}
    assert {e["batch_trace_id"] for e in done} <= batch_ids
    # queue lifetime counters in the registry match ground truth
    assert obs.counter(mn.QUEUE_REQUESTS).get() == len(reqs)
    assert obs.counter(mn.QUEUE_COALESCED_ROWS).get() == 14.0
    # depth gauges drained back to zero
    assert obs.gauge(mn.QUEUE_DEPTH_REQUESTS).get() == 0.0
    assert obs.gauge(mn.QUEUE_DEPTH_ROWS).get() == 0.0


# --- certified search ground truth --------------------------------------
def test_certified_counters_match_stats(placed):
    prog, rng = placed
    q = rng.standard_normal((12, 16)).astype(np.float32)
    _, _, stats = prog.search_certified(q, selector="approx", margin=8)
    assert obs.counter(
        mn.CERTIFIED_QUERIES, selector="approx").get() == 12.0
    assert obs.counter(
        mn.CERTIFIED_FALLBACKS, selector="approx").get() == float(
            stats["fallback_queries"])
    assert obs.counter(
        mn.CERTIFIED_GENUINE_MISSES, selector="approx").get() == float(
            stats.get("fallback_genuine_misses", 0))


def test_int8_quant_bound_distribution_recorded(rng):
    from knn_tpu.parallel import ShardedKNN, make_mesh
    from knn_tpu.ops.quantize import score_error_bound

    db = rng.integers(0, 256, size=(900, 16), dtype=np.uint8)
    q = rng.integers(0, 256, size=(7, 16)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(2, 4), k=4)
    prog.search_certified(
        q, selector="pallas", margin=8, tile_n=256, precision="int8")
    s = obs.snapshot()[mn.CERTIFIED_QUANT_BOUND]["series"][0]["value"]
    assert s["count"] == q.shape[0]
    pl8 = prog._int8_cache
    eps = score_error_bound(q, pl8["stats"], offset=pl8["offset"])
    assert s["max"] == pytest.approx(float(np.max(eps)))
    assert s["min"] == pytest.approx(float(np.min(eps)))


def test_results_bitwise_identical_obs_on_vs_off(placed, tmp_path,
                                                 monkeypatch):
    prog, rng = placed
    q = rng.standard_normal((8, 16)).astype(np.float32)
    d_on, i_on, _ = prog.search_certified(q, selector="approx", margin=8)
    obs.reset(enabled=False)
    d_off, i_off, _ = prog.search_certified(q, selector="approx", margin=8)
    # instrumentation never touches numerics: disabled vs enabled output
    # is bitwise identical
    np.testing.assert_array_equal(i_on, i_off)
    np.testing.assert_array_equal(d_on, d_off)
    # ...and no tail-forensics work happens either: exemplars are the
    # shared no-op, reconstruction has nothing to read, and the flight
    # recorder stays disarmed even with a destination configured
    from knn_tpu.obs import blackbox, waterfall

    h = obs.histogram(mn.SERVING_REQUEST_LATENCY, op="search")
    h.observe(1.0, exemplar="feed000000000001")
    assert h.exemplars() == []
    assert waterfall.slowest_table() == []
    monkeypatch.setenv(blackbox.DIR_ENV, str(tmp_path / "pm"))
    assert not blackbox.enabled()
    assert blackbox.on_breach("serving_availability", {}) is None
    assert not (tmp_path / "pm").exists()


# --- compile hook --------------------------------------------------------
def test_jax_compile_events_counted():
    if not obs.install_compile_hook():
        pytest.skip("jax.monitoring listener API unavailable")
    import jax
    import jax.numpy as jnp

    # a shape this process has never compiled: forces a fresh compile
    x = jnp.arange(677.0)
    jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    snap = obs.snapshot()
    assert mn.JAX_COMPILES in snap
    assert sum(s["value"] for s in snap[mn.JAX_COMPILES]["series"]) >= 1
    secs = sum(s["value"]
               for s in snap[mn.JAX_COMPILE_SECONDS]["series"])
    assert secs > 0


# --- JSONL sink rotation -------------------------------------------------
def test_jsonl_sink_rotates_preserving_valid_jsonl(tmp_path):
    """A long-running process's event log is size-capped: when the cap
    is crossed the file rotates to <path>.1 via atomic rename, on a
    LINE boundary — both sides of the cut must parse as valid JSONL and
    jointly hold every emitted event."""
    path = tmp_path / "events.jsonl"
    # each event line is ~70 bytes; a 1 KiB cap forces several cuts
    obs.reset_event_log(str(path), max_bytes=1024)
    n = 200
    for i in range(n):
        obs.emit_event("queue.dispatch", rows=i, pad="x" * 16)
    assert path.exists() and (tmp_path / "events.jsonl.1").exists()
    live = [json.loads(ln) for ln in path.read_text().splitlines()]
    rotated = [json.loads(ln) for ln in
               (tmp_path / "events.jsonl.1").read_text().splitlines()]
    # both generations are whole JSON lines, under the cap, and the
    # newest events are in the live file in order
    assert all(e["name"] == "queue.dispatch" for e in live + rotated)
    assert path.stat().st_size <= 1024
    assert (tmp_path / "events.jsonl.1").stat().st_size <= 1024
    assert [e["rows"] for e in rotated + live] == list(
        range(n - len(rotated) - len(live), n))
    # the in-memory ring still holds everything regardless of rotation
    assert len(obs.get_event_log().recent()) == n


def test_jsonl_rotation_keeps_exactly_two_generations(tmp_path):
    path = tmp_path / "e.jsonl"
    obs.reset_event_log(str(path), max_bytes=256)
    for i in range(300):
        obs.emit_event("queue.dispatch", rows=i)
    files = sorted(p.name for p in tmp_path.iterdir())
    assert files == ["e.jsonl", "e.jsonl.1"]  # older generations replaced


# --- metrics server under concurrent load --------------------------------
def test_http_server_concurrent_load_never_tears(placed):
    """Hammer /metrics, /metrics.json, /healthz, /statusz from several
    threads while the registry mutates underneath: every response must
    parse (text exposition / JSON), and no torn snapshot may surface —
    the server's view is always a consistent point-in-time read."""
    import urllib.error

    from knn_tpu.serving.engine import ServingEngine

    prog, rng = placed
    eng = ServingEngine(prog, buckets=(8,))
    eng.warmup()
    server = obs.start_metrics_server(0)
    errors = []
    stop = threading.Event()
    try:
        port = server.server_address[1]

        def mutate():
            i = 0
            while not stop.is_set():
                obs.counter(mn.QUEUE_REQUESTS).inc()
                obs.histogram(mn.QUEUE_WAIT).observe(i * 1e-4)
                obs.gauge(mn.QUEUE_DEPTH_ROWS).set(i % 7)
                i += 1

        def fetch(path, check):
            try:
                for _ in range(25):
                    try:
                        body = urllib.request.urlopen(
                            f"http://127.0.0.1:{port}{path}",
                            timeout=10).read().decode()
                    except urllib.error.HTTPError as e:
                        body = e.read().decode()  # /healthz 503 is fine
                    check(body)
            except Exception as e:  # noqa: BLE001 — the assertion surface
                errors.append((path, repr(e)))

        def check_prom(body):
            assert "# TYPE knn_tpu_queue_requests_total counter" in body
            for ln in body.splitlines():
                assert ln.startswith("#") or " " in ln

        def check_json(body):
            json.loads(body)

        mut = threading.Thread(target=mutate, daemon=True)
        mut.start()
        ts = []
        for _ in range(2):
            for path, check in (("/metrics", check_prom),
                                ("/metrics.json", check_json),
                                ("/healthz", check_json),
                                ("/statusz", check_json)):
                ts.append(threading.Thread(target=fetch,
                                           args=(path, check)))
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        stop.set()
        mut.join(10)
        assert not errors, errors
    finally:
        stop.set()
        server.shutdown()


# --- the lint gate -------------------------------------------------------
def test_lint_metric_names_green():
    r = subprocess.run(
        [sys.executable, f"{REPO}/scripts/lint_metric_names.py"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
