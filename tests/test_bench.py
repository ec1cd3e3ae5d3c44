"""bench.py contract tests — round 1 died because the bench crashed in
backend init and emitted nothing parseable.  These pin the contract: one
JSON line on stdout, success or failure, with the documented fields."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, timeout=300):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, BENCH], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    return proc.returncode, lines


@pytest.mark.slow
def test_bench_emits_one_parseable_success_line():
    rc, lines = _run({
        "KNN_BENCH_PLATFORM": "cpu",
        "KNN_BENCH_N": "4000", "KNN_BENCH_NQ": "64", "KNN_BENCH_BATCH": "32",
        "KNN_BENCH_K": "5", "KNN_BENCH_MARGIN": "4", "KNN_BENCH_TILE": "2048",
        "KNN_BENCH_CPU_QUERIES": "8", "KNN_BENCH_RUNS": "1",
        "KNN_BENCH_MODES": "certified_approx",
    })
    assert rc == 0, lines
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    for field in ("metric", "value", "unit", "vs_baseline", "runs",
                  "selectors", "mode", "backend"):
        assert field in rec, field
    assert rec["value"] > 0
    assert rec["unit"] == "queries/s"
    sel = rec["selectors"]["certified_approx"]
    assert sel["certified_stats"]["certified"] + \
        sel["certified_stats"]["fallback_queries"] == 64
    # VERDICT r4 item 6: EVERY selector carries its own device-phase
    # rate, at the sweep's batch shape
    pb = sel["phase_breakdown"]
    assert pb["device_batch"] == 32 and pb["device_qps"] > 0
    # the line is self-reproducing: the grid-order knob is part of the
    # recorded pallas geometry
    assert rec["pallas_knobs"]["grid_order"] == "query_major"
    # roofline attribution beside mfu on the selector entry AND the
    # line top-level; a CPU run models against the generic fallback
    # peaks and says so (roofline_estimated)
    assert sel["roofline"]["bound_class"] in (
        "hbm_bound", "mxu_bound", "vpu_select_bound")
    assert sel["roofline"]["roofline_pct"] is not None
    assert rec["roofline"]["ceiling_qps"] > 0
    assert rec["roofline_pct"] == rec["roofline"]["roofline_pct"]
    assert rec["roofline_estimated"] is True
    assert rec["roofline"]["estimated"] is True


def test_bench_bad_config_still_emits_json_line():
    rc, lines = _run({"KNN_BENCH_CONFIG": "not_a_config"}, timeout=60)
    assert rc == 1
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] is None
    assert "error" in rec


def test_bench_bad_platform_still_emits_json_line():
    rc, lines = _run({"KNN_BENCH_PLATFORM": "bogus"}, timeout=120)
    assert rc == 1
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] is None
    assert "backend_init" in rec["error"]


def test_bench_without_a_tpu_fails_instead_of_falling_back():
    # no KNN_BENCH_PLATFORM: the bench wants a TPU, this host has none,
    # and a CPU number must never stand in for it
    rc, lines = _run({}, timeout=120)
    assert rc == 1
    assert len(lines) == 1
    rec = json.loads(lines[0])
    assert rec["value"] is None
    assert "backend_init" in rec["error"] and "'cpu'" in rec["error"]


def test_bench_refuses_more_chips_than_the_host_has():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)  # one CPU device, not the suite's eight
    env.update({"KNN_BENCH_PLATFORM": "cpu", "KNN_BENCH_N": "4000",
                "KNN_BENCH_NQ": "32", "KNN_BENCH_CPU_QUERIES": "4"})
    proc = subprocess.run(
        [sys.executable, BENCH, "--chips", "4"], capture_output=True,
        text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "--chips 4" in rec["error"]


@pytest.mark.slow
def test_bench_failed_requested_mode_fails_the_run_after_the_line():
    # one good mode, one that cannot run: the line is still printed
    # (with the bad mode's error on it), and the exit code is non-zero
    rc, lines = _run({
        "KNN_BENCH_PLATFORM": "cpu",
        "KNN_BENCH_N": "4000", "KNN_BENCH_NQ": "32", "KNN_BENCH_BATCH": "32",
        "KNN_BENCH_K": "5", "KNN_BENCH_MARGIN": "4", "KNN_BENCH_TILE": "2048",
        "KNN_BENCH_CPU_QUERIES": "8", "KNN_BENCH_RUNS": "1",
        "KNN_BENCH_MODES": "exact,no_such_mode",
    })
    assert rc == 1, lines
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["value"] > 0 and rec["mode"] == "exact"
    assert "error" in rec["selectors"]["no_such_mode"]


@pytest.mark.slow
def test_bench_glove_cosine_runs_certified_library_path():
    # VERDICT r3 item 4: the cosine config must run the certified
    # machinery through the LIBRARY (ShardedKNN normalizes at placement),
    # not a harness-side normalize-and-relabel trick.  Tiny-shape glove
    # on CPU: all three modes must report recall 1.0 vs the raw-cosine
    # native oracle.
    rc, lines = _run({
        "KNN_BENCH_PLATFORM": "cpu",
        "KNN_BENCH_CONFIG": "glove",
        "KNN_BENCH_N": "3000", "KNN_BENCH_NQ": "48", "KNN_BENCH_BATCH": "24",
        "KNN_BENCH_K": "7", "KNN_BENCH_MARGIN": "6", "KNN_BENCH_TILE": "1024",
        "KNN_BENCH_CPU_QUERIES": "8", "KNN_BENCH_RUNS": "1",
        "KNN_BENCH_DIM": "24", "KNN_BENCH_CPU_CACHE": "0",
    })
    assert rc == 0, lines
    assert len(lines) == 1, lines
    rec = json.loads(lines[0])
    assert rec["value"] > 0
    assert rec["metric_fn"].startswith("cosine")
    sels = rec["selectors"]
    assert set(sels) == {"exact", "certified_approx", "certified_pallas",
                         "serving"}
    for name, sel in sels.items():
        if name == "serving":
            # trace replay, not a recall-gated sweep: sustained rate +
            # tail latency + the compile bound instead of recall_at_k
            assert sel["sustained_qps"] > 0, sel
            assert {"p50", "p95", "p99"} <= set(sel["latency_ms"]), sel
            assert sel["compile_count"] <= len(sel["bucket_ladder"]), sel
            continue
        assert sel.get("recall_at_k") == 1.0, (name, sel)
    # the traffic numbers are hoisted to the top level of the JSON line
    assert rec["serving_sustained_qps"] > 0
    assert rec["serving_latency_ms"]["p99"] >= rec["serving_latency_ms"]["p50"]
