"""The cell ``openai500k.sweep_cos`` through the whole harness at a tiny
size on the CPU (``test_cells.py`` runs it traced and untraced with
every other cell and breaks its answers as it breaks a plain sweep's:
its traffic kind is ``sweep_ip``, so ``tinyroot`` and ``BREAKERS`` know
it), and what is this cell's own: the oracle against brute force with
the contract's edges in, the three per-layer entries this PR could not
list (``data/cos_cell.json`` says why) read from a live registry and
printed by a traced run with them merged in, a program without the
counters leaving them out, and two broken TIMED paths that each have to
come out ``correct: false`` (the host ranking by the float32 unit rows,
as the parent did; squared-L2 distances of the unit rows handed back
unhalved).

Importing this module joins the cell's name to ``test_call_account``'s
five entries, as ``tiny_filter.join_the_call_account`` does for the
filter cell: ``data/call_account_cell.json`` lists "every sweep cell" by
name, ``test_call_account.py:83`` holds that list to BENCHMARK.json's,
and neither file was this change's to edit.
"""

import json
import os
import time

import numpy as np
import pytest

import tinyroot
import tiny_cos
import tiny_filter  # tinyroot's sweep_filter entry, on import
import test_call_account
from tiny_cos import CELL, HELD, NEW, brute


def join_the_call_account(module) -> None:
    # after the filter cell's, whichever file is imported first: the
    # lists are compared in BENCHMARK.json's order
    tiny_filter.join_the_call_account(module)
    for entry in module.ENTRIES:
        if CELL not in entry["workloads"]:
            entry["workloads"].append(CELL)


join_the_call_account(test_call_account)

import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_cos  # noqa: E402
import system  # noqa: E402

BENCH = tinyroot.load_bench()
FULL = tiny_cos.merged_bench()


def _json(*parts):
    with open(os.path.join(tinyroot.ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "openai500k.json")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_cos")))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(FULL, f)
    return root


@pytest.fixture(autouse=True)
def cpu_memory_reading(monkeypatch):
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run(root, traced=False, seed=2**31 + 43):
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.0, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], FULL, CELL, traced) == parsed
    return parsed


# --- the files ----------------------------------------------------------------
def test_the_cells_files_agree():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "openai500k"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] == list(CONFIG["reduced_from_source"])
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "openai500k", "sweep_cos", 1)
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["k"], CONFIG["metric"],
            CONFIG["reference"]) == (500_000, 1536, 100, "cosine", "cos")
    assert _json("benchmark", "traffic", "sweep_cos.json")["kind"] == (
        "sweep_ip")
    # the committed lists hold what the parent's program can report too;
    # the held entries add what only this PR's program has
    committed = {m["name"] for m in lastline.per_layer_of(BENCH, CELL)}
    assert not committed & set(NEW)
    assert {m["name"] for m in lastline.per_layer_of(FULL, CELL)} == (
        committed | set(NEW))
    assert [e["name"] for e in HELD] == NEW
    for e in HELD:
        layer = _json("benchmark", "layers", f"{e['name']}.json")
        for key in ("layer", "unit", "moves", "source", "better"):
            assert layer[key] == e[key], (e["name"], key)


# --- the plain reference ------------------------------------------------------
def test_the_oracle_is_brute_force_with_the_edges_in():
    rows = dict(CONFIG["rows"], clusters=32, scale_sigma=0.5)
    db = datagen_mix.draw(rows, 70_000, 24, 7, datagen.STREAM_ROWS)
    q = datagen_mix.draw(rows, 16, 24, 7, datagen.STREAM_QUERIES)
    db[3] = 0.0                      # a zero row: cosine 0 to everything
    db[66_000] = db[40]              # a duplicate in the second block
    db[66_001] = 2.0 * db[40]        # and a power-of-two copy: ties
    q[2] = 0.0                       # a zero query: the first k rows
    q[5] = db[40]
    want_i, want_c = brute(db, q, 10)
    got_i, got_c = reference_cos.oracle_topk(db, q, 10)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_c, want_c, rtol=0, atol=1e-15)
    np.testing.assert_array_equal(got_i[2], np.arange(10))
    assert (got_c[2] == 1.0).all()
    np.testing.assert_array_equal(got_i[5, :3], [40, 66_000, 66_001])
    for precision in reference_cos.PRECISIONS:
        low_i, low_c = reference_cos.lowprec_topk(db, q, 10, precision)
        assert low_i.shape == want_i.shape and low_c.dtype == np.float64
        # the lower precision ranks the same search: most of it agrees
        assert reference_cos.compare(low_i, low_c, want_i, want_c, db, q)[
            "recall"] > 0.8


# --- the held entries ---------------------------------------------------------
def read(name: str, registry: dict):
    outcome = harness.Outcome(attempted=1, failed=0, end_to_end={},
                              checks=None, bench={}, registry=registry,
                              resident_bytes=0)
    with open(os.path.join(tinyroot.BENCH_DIR, "layers",
                           f"{name}.json")) as f:
        return harness.read_metric(
            json.load(f), harness.Readings(None, outcome, {}, None))


@pytest.fixture(scope="module")
def live():
    """The registry's change over two cosine calls, the first call's
    passes made before; and over two l2 calls, which have no slack."""
    from knn_tpu import obs

    obs.reset(enabled=True)
    rows = dict(CONFIG["rows"], clusters=16)
    db = datagen_mix.draw(rows, 3000, 32, 11, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(rows, 64, 32, 11, datagen.STREAM_QUERIES)
    # copies of rows at another length: the same cosine but for float32
    # rounding, so tie runs for the host wherever a query is near one
    db[1000:1032] = 3.0 * db[:32]
    queries[:32] = db[:32] + 0.05 * queries[:32]
    out = {}
    for metric in ("cosine", "l2"):
        prog = system.place({"k": 10, "metric": metric, "train_tile": 1024},
                            db, 1)
        prog.search_certified(queries, selector="pallas")
        before = system.registry_snapshot()
        stats = [prog.search_certified(queries, selector="pallas")[2]
                 for _ in range(2)]
        out[metric] = (system.registry_delta(
            before, system.registry_snapshot()), stats)
    obs.reset()
    return out


def test_the_three_read_their_numbers_from_the_live_program(live):
    delta, stats = live["cosine"]
    assert read("metric_map_ms", delta) > 0
    members = read("rank_members_per_query", delta)
    assert members == delta[("knn_tpu_rank_correct_members_total", ())][
        0] / 128
    assert members > 0
    pct = read("slack_fallback_pct", delta)
    assert pct == 100.0 * sum(s["slack_fallback_queries"]
                              for s in stats) / 128
    # with the two other outcomes it makes the window's queries
    by = {dict(labels)["outcome"]: v[0] for (name, labels), v in
          delta.items() if name == "knn_tpu_certified_slack_queries_total"}
    assert set(by) == {"certified", "uncertified", "uncertified_by_slack"}
    assert sum(by.values()) == 128
    assert by["certified"] == sum(s["certified"] for s in stats)


def test_a_program_without_the_slack_leaves_two_of_them_out(live):
    """An l2 call (and the parent's cosine call) has no metric_map span
    and no slack counter: the readers return None and raise nothing,
    and ``lastline.validate`` refuses a traced line that then lacks a
    listed metric, which is why the entries are held and not listed."""
    delta, _ = live["l2"]
    assert read("metric_map_ms", delta) is None
    assert read("slack_fallback_pct", delta) is None
    assert read("rank_members_per_query", delta) is not None  # any metric
    values = {m["name"]: 1.0 for m in lastline.per_layer_of(FULL, CELL)
              if m["name"] != "slack_fallback_pct"}
    units = {m["name"]: m["unit"] for m in FULL["per_layer"]}
    line = lastline.build(
        correct=True, attempted=1, failed=0, values=values,
        units={k: units[k] for k in values},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "window_s": 1.0, "busy_s": 0.5})
    with pytest.raises(lastline.LastLineError, match="slack_fallback_pct"):
        lastline.validate(line, FULL, CELL, True)
    # the committed lists ask for neither, so the same line passes there
    lastline.validate(line, BENCH, CELL, True)


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_with_the_held_entries_merged_in(root, traced):
    out = run(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(FULL, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        assert set(NEW) <= want
        m = {k: v["value"] for k, v in out["metrics"].items()}
        assert m["metric_map_ms"] > 0 and m["rank_members_per_query"] > 0
        assert 0 <= m["slack_fallback_pct"] <= m["fallback_pct"] + 1e-9


# --- broken timed paths ---------------------------------------------------------
def test_a_host_that_ranks_by_the_unit_rows_is_not_correct(root,
                                                           monkeypatch):
    """The parent's path: wherever the host ranks, it ranks by the
    squared distance of the float32 unit rows.  On rows one float32 ulp
    apart (pairs laid into the generator's draw) the answers come back
    in the rounding's order and the comparison says so by the indices."""
    real_draw = datagen_mix.draw

    def draw(spec, n, dim, seed, stream, of=None):
        out = real_draw(spec, n, dim, seed, stream, of=of)
        if stream == datagen.STREAM_ROWS:
            rng = np.random.default_rng(seed)
            src = rng.choice(n, size=n // 3, replace=False)
            twin = out[src].copy()
            cols = rng.integers(0, dim, size=(src.size, 3))
            for j in range(3):
                at = (np.arange(src.size), cols[:, j])
                twin[at] = np.nextafter(twin[at], np.float32(np.inf))
            out[(src + 1) % n] = twin
        return out

    monkeypatch.setattr(datagen_mix, "draw", draw)
    assert run(root)["correct"] is True  # the program itself gets them right
    tiny_cos.rank_by_the_unit_rows(monkeypatch)
    out = run(root)
    assert out["correct"] is False
    assert out["compared"]["mismatched_rows"]["value"] > 0
    assert out["compared"]["dist_err_max"]["value"] <= CONFIG["limits"][
        "dist_err_max"]


def test_distances_that_are_not_cosine_distances_are_not_correct(
        root, monkeypatch):
    from knn_tpu.parallel import ShardedKNN

    real = ShardedKNN.search_certified

    def unhalved(self, queries, **kw):
        d, i, stats = real(self, queries, **kw)
        return 2.0 * d, i, stats

    monkeypatch.setattr(ShardedKNN, "search_certified", unhalved)
    out = run(root)
    assert out["correct"] is False
    assert out["compared"]["mismatched_rows"]["value"] == 0
