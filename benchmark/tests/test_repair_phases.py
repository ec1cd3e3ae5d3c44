"""The nine per-layer entries of ``data/repair_phases_cell.json``: the
repair's host half (``repair_refine_ms``, ``repair_host_scan_ms``,
``repair_host_scan_pct``), the range completion's phases
(``range_wait_ms``, ``range_decode_ms``, ``range_score_ms``,
``range_order_ms``) and both sides of ``metric_map``
(``metric_map_before_ms``, ``metric_map_after_ms``).

They are data that no cell lists yet (the file's ``what`` says why: the
driver's traced run of the PARENT would lack them).  What is held here is
what the PR that lists them will rely on: each layer file reads its
number from a live registry, each span series holds one record a CALL
however the call or its completion is cut, a program without the series
leaves the metric out and raises nothing, and with the entries merged
into BENCHMARK.json a traced run of each of their cells prints them
through the harness as it stands.
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_filter  # noqa: F401  tinyroot's sweep_filter entry, on import
import tiny_graph  # noqa: F401  its graph_build entry
import tiny_vote  # noqa: F401  its sweep_vote entry

import harness  # noqa: E402
import lastline  # noqa: E402
import system  # noqa: E402

with open(os.path.join(tinyroot.HERE, "data",
                       "repair_phases_cell.json")) as _f:
    HELD = json.load(_f)["per_layer"]
NEW = [e["name"] for e in HELD]
BENCH = tinyroot.load_bench()
REPAIR = ["repair_refine_ms", "repair_host_scan_ms", "repair_host_scan_pct"]
RANGE = ["range_wait_ms", "range_decode_ms", "range_score_ms",
         "range_order_ms"]
MAP = ["metric_map_before_ms", "metric_map_after_ms"]
CELLS = sorted({w for e in HELD for w in e["workloads"]})


def merged_bench() -> dict:
    """BENCHMARK.json as it will read once the held entries are in it."""
    bench = tinyroot.load_bench()
    bench["per_layer"] = bench["per_layer"] + HELD
    return bench


FULL = merged_bench()


def layer_file(metric: str) -> dict:
    with open(os.path.join(tinyroot.BENCH_DIR, "layers",
                           f"{metric}.json")) as f:
        return json.load(f)


def read(name: str, registry: dict):
    outcome = harness.Outcome(attempted=1, failed=0, end_to_end={},
                              checks=None, bench={}, registry=registry,
                              resident_bytes=0)
    return harness.read_metric(layer_file(name),
                               harness.Readings(None, outcome, {}, None))


def span_key(span: str):
    return ("knn_tpu_span_seconds", (("span", span),))


def series_key(name: str):
    return span_key(layer_file(name)["reader"]["labels"]["span"])


# --- the files ----------------------------------------------------------------
def test_the_entries_are_the_nine_and_fit_the_benchmark():
    assert NEW == REPAIR + RANGE + MAP
    listed = {m["name"] for m in BENCH["per_layer"]}
    layers = {m["layer"] for m in BENCH["per_layer"]}
    (qps,) = [m for m in BENCH["end_to_end"] if m["name"] == "sweep_qps"]
    for e in HELD:
        f = layer_file(e["name"])
        assert f["metric"] == e["name"]
        for key in ("layer", "unit", "moves", "source", "better"):
            assert f[key] == e[key], (e["name"], key)
        # not listed yet, a layer the benchmark already names, and only
        # cells that report what the metric moves
        assert e["name"] not in listed and e["layer"] in layers
        assert set(e["workloads"]) <= set(qps["workloads"])
        if e["name"].endswith("_ms"):
            assert (f["reader"]["type"], f["reader"]["scale"]) == (
                "span", 1000)
            assert "a CALL" in f["what"]
    by = {e["name"]: e["workloads"] for e in HELD}
    assert {tuple(by[n]) for n in REPAIR} == {(
        "bigann5m.sweep", "gist1m.sweep", "ssnpp2m5.sweep_range",
        "openai500k.sweep_cos")}
    assert {tuple(by[n]) for n in RANGE} == {("ssnpp2m5.sweep_range",)}
    assert {tuple(by[n]) for n in MAP} == {(
        "text2image2m5.sweep_ip", "openai500k.sweep_cos",
        "imagenet-knn768.sweep_vote")}
    # the line of a cell with them merged in holds them beside the rest
    for cell in CELLS:
        mine = {n for n in NEW if cell in by[n]}
        assert {m["name"] for m in lastline.per_layer_of(FULL, cell)} == (
            {m["name"] for m in lastline.per_layer_of(BENCH, cell)} | mine)


# --- a live registry ------------------------------------------------------------
CALLS = 2


@pytest.fixture(scope="module")
def live():
    """The registry's change over two calls of each kind, the first
    call's passes made before: an l2 call whose every query falls back
    (rows repeated past the analysis window), a range call whose
    completion is cut into two sub-batches, an inner-product, a cosine
    and a voted call."""
    import jax

    from knn_tpu import obs
    from knn_tpu.parallel import ShardedKNN, make_mesh

    obs.reset(enabled=True)
    rng = np.random.default_rng(2**31 + 53)
    base = rng.normal(size=(8, 16)).astype(np.float32)
    tied = np.repeat(base, 64, axis=0)
    db = rng.random((3000, 32), dtype=np.float32)
    queries = rng.random((96, 32), dtype=np.float32)
    spread = db * rng.uniform(0.5, 2.0, size=(3000, 1)).astype(np.float32)
    labels = rng.integers(0, 30, 3000).astype(np.int32)

    def place(rows, metric="l2", **kw):
        return system.place({"k": 10, "metric": metric, "train_tile": 1024,
                             **kw}, rows, 1)

    def search(prog, q):
        return lambda: prog.search_certified(q, selector="pallas")

    plain, dot, cos = place(db), place(spread, "dot"), place(spread,
                                                             "cosine")
    voted = ShardedKNN(spread, mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
                       k=10, metric="cosine", labels=labels, num_classes=30)
    calls = {
        "tied": search(place(tied), base[:4] + np.float32(0.01)),
        "plain": search(plain, queries),
        # every query's list is longer than k: 96 truncated, two
        # completion sub-batches of 64
        "range": lambda: plain.range_search_certified(queries,
                                                      radius_sq=3.4),
        "dot": search(dot, queries), "cosine": search(cos, queries),
        "voted": lambda: voted.predict_certified(
            queries, vote="softmax", temperature=0.07, classes_out=5,
            selector="pallas"),
    }
    out = {}
    for kind, call in calls.items():
        call()
        before = system.registry_snapshot()
        stats = [call()[-1] for _ in range(CALLS)]
        out[kind] = (system.registry_delta(
            before, system.registry_snapshot()), stats)
    obs.reset()
    return out


def test_the_repairs_three_read_a_call_with_fallbacks(live):
    delta, stats = live["tied"]
    fallbacks = sum(s["fallback_queries"] for s in stats)
    assert fallbacks == 4 * CALLS
    for name in REPAIR[:2]:
        assert delta[series_key(name)][0] == CALLS  # one record a call
    assert read("repair_refine_ms", delta) > 0
    scanned = sum(s.get("host_exact_queries", 0) for s in stats)
    assert (read("repair_host_scan_ms", delta) > 0) == (scanned > 0)
    by = {dict(labels)["outcome"]: v[0] for (name, labels), v in
          delta.items() if name == "knn_tpu_repair_queries_total"}
    assert by == {"proven": fallbacks - scanned, "host_scan": scanned}
    assert read("repair_host_scan_pct", delta) == pytest.approx(
        100.0 * scanned / fallbacks)
    # inside the repair, beside the re-select
    repair = delta[span_key("certified.repair")][1]
    inside = (delta[span_key("certified.repair.reselect")][1]
              + delta[series_key("repair_refine_ms")][1]
              + delta[series_key("repair_host_scan_ms")][1])
    assert 0 < inside <= repair + 1e-4


@pytest.mark.parametrize("kind", ["plain", "range", "dot", "cosine",
                                  "voted"])
def test_every_kind_of_call_records_the_repairs_phases(live, kind):
    delta, stats = live[kind]
    fallbacks = sum(s["fallback_queries"] for s in stats)
    for name in REPAIR[:2]:
        assert delta[series_key(name)][0] == CALLS
        # 0.0, never absent, where no query fell back
        assert read(name, delta) >= 0.0
    assert (read("repair_refine_ms", delta) > 0) == (fallbacks > 0)
    by = {dict(labels)["outcome"]: v[0] for (name, labels), v in
          delta.items() if name == "knn_tpu_repair_queries_total"}
    assert set(by) == {"proven", "host_scan"}
    assert sum(by.values()) == fallbacks
    # 0 over 0 is no reading
    assert (read("repair_host_scan_pct", delta) is None) == (fallbacks == 0)


def test_a_call_without_fallbacks_reads_zero_and_no_share(live):
    none = [delta for delta, stats in live.values()
            if not sum(s["fallback_queries"] for s in stats)]
    assert none, "every kind's draw fell back"
    for delta in none:
        assert [read(name, delta) for name in REPAIR] == [0.0, 0.0, None]


def test_the_ranges_four_read_a_completion_of_two_sub_batches(live):
    delta, stats = live["range"]
    assert {s["range"]["sub_batches"] for s in stats} == {2}
    for name in RANGE:
        assert delta[series_key(name)][0] == CALLS  # not sub-batches
        assert read(name, delta) > 0
    assert delta[span_key("certified.range_complete.host_scan")] == (
        CALLS, 0.0)
    whole = read("range_complete_ms", delta)
    assert 0.5 * whole < sum(read(n, delta) for n in RANGE) <= whole + 1e-3


@pytest.mark.parametrize("kind", ["dot", "cosine", "voted"])
def test_metric_maps_two_sides_make_up_the_span(live, kind):
    delta, _ = live[kind]
    for name in MAP:
        assert delta[series_key(name)][0] == CALLS
    before, after = (read(n, delta) for n in MAP)
    assert before > 0
    # the inner product scores its answers afterwards; cosine has nothing
    assert (after > 0) == (kind == "dot")
    assert before + after == pytest.approx(read("metric_map_ms", delta),
                                           rel=1e-9)


def test_a_program_without_the_series_leaves_the_nine_out(live):
    """The parent's case, and why no cell lists the nine yet: a reader
    returns None and raises nothing, the line leaves the metric out, and
    ``lastline.validate`` refuses a traced line that does."""
    for kind, names in (("tied", REPAIR), ("range", RANGE), ("dot", MAP)):
        delta, _ = live[kind]
        parents = {k: v for k, v in delta.items()
                   if k[0] != "knn_tpu_repair_queries_total"
                   and not dict(k[1]).get("span", "").startswith((
                       "certified.repair.refine",
                       "certified.repair.host_scan",
                       "certified.range_complete.",
                       "certified.metric_map."))}
        for name in names:
            assert read(name, delta) is not None
            assert read(name, parents) is None
    cell = "ssnpp2m5.sweep_range"
    values = {m["name"]: 1.0 for m in lastline.per_layer_of(FULL, cell)
              if m["name"] != "range_wait_ms"}
    units = {m["name"]: m["unit"] for m in FULL["per_layer"]}
    line = lastline.build(
        correct=True, attempted=1, failed=0, values=values,
        units={k: units[k] for k in values},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "window_s": 1.0, "busy_s": 0.5})
    with pytest.raises(lastline.LastLineError, match="range_wait_ms"):
        lastline.validate(line, FULL, cell, True)
    # the committed lists ask for none of them: the same line passes
    lastline.validate(line, BENCH, cell, True)


# --- through the whole harness ------------------------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_phases")))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(FULL, f)
    return root


@pytest.fixture
def a_fallback_a_call(monkeypatch):
    """A tiny cell's draws seldom fall back, and ``repair_host_scan_pct``
    divides by the window's fallbacks: every certified call is told that
    its first query failed its certificate, so the repair re-selects,
    refines and proves it (the answer stays exact: ``correct`` holds)."""
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN._certify_pallas

    def flagged(self, *args, **kw):
        bad, n_corrected, n_by_slack = real(self, *args, **kw)
        return np.union1d(bad, [0]), n_corrected, n_by_slack

    monkeypatch.setattr(ShardedKNN, "_certify_pallas", flagged)
    real_info = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real_info(resident or 1))


@pytest.mark.parametrize("workload", CELLS)
def test_a_traced_run_prints_the_held_entries_of_its_cell(
        root, a_fallback_a_call, workload):
    lines = []
    out = harness.run_cell(root, workload, 2**31 + 53, 1.5, True,
                           time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], FULL, workload, True) == out
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(FULL, workload,
                                                         True)}
    assert set(out["metrics"]) == want
    mine = {e["name"] for e in HELD if workload in e["workloads"]}
    assert mine and mine <= want
    m = {k: v["value"] for k, v in out["metrics"].items()}
    if "repair_refine_ms" in mine:
        repair = m.get("repair_ms", m.get("repair_ms.sweep"))
        assert 0 < m["repair_refine_ms"] + m["repair_host_scan_ms"] <= repair
        assert 0 <= m["repair_host_scan_pct"] <= 100
    if "range_wait_ms" in mine:
        assert sum(m[n] for n in RANGE) <= m["range_complete_ms"] + 1e-3
    if "metric_map_before_ms" in mine:
        assert m["metric_map_before_ms"] > 0
        if "metric_map_ms" in m:
            assert m["metric_map_before_ms"] + m[
                "metric_map_after_ms"] == pytest.approx(m["metric_map_ms"])
