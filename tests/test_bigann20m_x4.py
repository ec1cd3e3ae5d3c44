"""The four-chip deployment (``bigann20m-x4``): rows db-sharded over a
(1, 4) mesh, every query sent to all four shards, the certified Pallas
path with its cross-shard merge.  On four of the eight CPU devices, the
kernel interpreted, at sizes a test can hold:

- the system against the plain reference (``benchmark/reference.py``,
  numpy float64, knows nothing of shards) on seeded byte-valued rows;
- what the merge adds to the tracing: the device scope ``knn.merge``,
  the merge-bytes counter, the fields of the ``certified.call`` event;
- the cell ``bigann20m-x4.sweep`` through the whole benchmark harness,
  traced and not, and the control that the comparison has to fail;
- ``benchmark/work/knn_scan_shard.py`` and the cell's data files.
"""

import functools
import json
import os
import re
import sys
import time

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops.pallas_knn import RANK_SLACK
from knn_tpu.parallel import ShardedKNN, crossover, make_mesh
from knn_tpu.parallel import sharded as sh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402  (benchmark/)
import lastline  # noqa: E402
import reference  # noqa: E402
import tinyroot  # noqa: E402  (benchmark/tests/)

CELL = "bigann20m-x4.sweep"
K = 10
SHARDS = 4
N_QUERIES = 48
MERGES = ("ring", "allgather")


def mesh(db_shards: int = SHARDS):
    return make_mesh(1, db_shards, devices=jax.devices()[:db_shards])


# --- the system against the plain reference ---------------------------------
#: name -> (rows, dimensions, values 0...high-1).  Whole numbers in few
#: dimensions and a small range give exact distance ties by the hundred,
#: and rows drawn at random put the tied rows on different shards;
#: 4,097 and 4,099 rows leave three and one of the last shard's rows as
#: padding, which only the mask by global index keeps out of an answer.
CORPORA = {
    "ties_6d_0to3": (4096, 6, 4),
    "ragged_8d_0to7": (4097, 8, 8),
    "bytes_16d": (4099, 16, 256),
}


@functools.lru_cache(maxsize=None)
def corpus(name: str):
    n, dim, high = CORPORA[name]
    rng = np.random.default_rng([27, n, dim])
    db = rng.integers(0, high, size=(n, dim), dtype=np.uint8)
    q = rng.integers(0, high, size=(N_QUERIES, dim), dtype=np.uint8)
    return db.astype(np.float32), q.astype(np.float32)


@functools.lru_cache(maxsize=None)
def answer(name: str, merge: str):
    db, q = corpus(name)
    prog = ShardedKNN(db, mesh=mesh(), k=K, merge=merge)
    return prog.search_certified(q, selector="pallas")


@pytest.mark.parametrize("merge", MERGES)
@pytest.mark.parametrize("name", CORPORA)
def test_the_1x4_certified_path_equals_the_plain_reference(name, merge):
    db, q = corpus(name)
    want_i, want_d = reference.oracle_topk(db, q, K)
    d, i, stats = answer(name, merge)
    # no tolerance on indices: the contract is the float64 brute-force
    # answer in (distance, index) order, ties across shards included
    np.testing.assert_array_equal(i, want_i)
    # distances: the program documents f32 direct-difference values
    # within RANK_SLACK = 2^-18 relative (float64-exact where repaired);
    # the limit bigann20m-x4.json states.  A coarse (expanded-form bf16)
    # distance is off by 2^-9 and would fail it.
    assert RANK_SLACK == 2.0 ** -18
    np.testing.assert_allclose(d, want_d, rtol=RANK_SLACK, atol=0)
    assert stats["certified"] + stats["fallback_queries"] == q.shape[0]
    assert stats["db_shards"] == SHARDS and stats["merge"] == merge
    if CORPORA[name][2] <= 8:
        # the case is what it says: tied neighbours that sit on two shards
        shard_of = want_i // -(-db.shape[0] // SHARDS)
        tied = np.diff(want_d, axis=1) == 0
        assert (tied & (shard_of[:, 1:] != shard_of[:, :-1])).any()
    if db.shape[0] % SHARDS:
        assert i.max() < db.shape[0]  # no padding row in any answer


@pytest.mark.parametrize("name", CORPORA)
def test_ring_and_allgather_give_the_same_arrays(name):
    (d_r, i_r, s_r), (d_a, i_a, s_a) = (answer(name, m) for m in MERGES)
    np.testing.assert_array_equal(i_r, i_a)
    np.testing.assert_array_equal(d_r, d_a)  # bitwise
    for key in ("certified", "fallback_queries", "rank_corrected_queries"):
        assert s_r[key] == s_a[key], key


# --- the device scope knn.merge ----------------------------------------------
COLLECTIVE = re.compile(
    r" (collective-permute|all-gather|all-reduce|all-to-all)"
    r"(-start|-done)?\(")


def collectives(hlo: str):
    """(opcode, op_name) of every cross-device instruction of a compiled
    program's text."""
    out = []
    for line in hlo.splitlines():
        hit = COLLECTIVE.search(line)
        if hit:
            out.append((hit.group(1), re.search(
                r'op_name="([^"]*)"', line).group(1)))
    return out


def certified_hlo(db_shards: int, merge):
    db, q = corpus("ragged_8d_0to7")
    prog = ShardedKNN(db, mesh=mesh(db_shards), k=K, merge=merge)
    qp, _ = prog._place_queries(q)
    one, _, _, _ = prog._pallas_setup(28, None, "bf16x3")
    tail = prog._pallas_operands("bf16x3")  # of the program just set up
    return one.lower(qp, prog._tp, *tail).compile().as_text()


@pytest.mark.parametrize("merge,opcodes", [
    ("ring", {"collective-permute", "all-reduce"}),
    ("allgather", {"all-gather", "all-reduce"}),
])
def test_the_1x4_certified_program_names_its_collectives_knn_merge(
        merge, opcodes):
    found = collectives(certified_hlo(SHARDS, merge))
    assert {op for op, _ in found} == opcodes
    for op, name in found:
        # inside the certify/pack tail, and the innermost scope
        assert f"/{sh.SCOPE_CERTIFY_PACK}/{sh.SCOPE_MERGE}/" in name, (
            op, name)


def test_the_one_shard_certified_program_holds_no_collective():
    hlo = certified_hlo(1, None)
    assert collectives(hlo) == []
    assert sh.SCOPE_MERGE not in hlo  # the scope holds nothing there
    assert sh.SCOPE_CERTIFY_PACK in hlo


@pytest.mark.parametrize("merge", MERGES)
def test_the_repairs_reselect_program_names_its_collectives_knn_merge(merge):
    db, q = corpus("ragged_8d_0to7")
    prog = ShardedKNN(db, mesh=mesh(), k=K, merge=merge)
    qp, _ = prog._place_queries(q)
    exact = sh._knn_program(prog.mesh, 2 * K, "l2", merge, prog.n_train,
                            None, None, "exact")
    found = collectives(exact.lower(qp, prog._tp).compile().as_text())
    assert found
    for op, name in found:
        assert f"/{sh.SCOPE_MERGE}/" in name, (op, name)


# --- the counter and the call's event ---------------------------------------
@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def merge_bytes_counted(strategy: str) -> float:
    series = obs.snapshot().get(mn.MERGE_BYTES, {"series": []})["series"]
    return sum(s["value"] for s in series if s["labels"] == {
        "level": "intra", "strategy": strategy})


@pytest.mark.parametrize("batch_size,batches", [(None, 1), (16, 3)])
@pytest.mark.parametrize("merge", MERGES)
def test_search_certified_counts_its_merge(fresh_registry, merge,
                                           batch_size, batches):
    db, q = corpus("bytes_16d")  # certifies every query: no re-select
    prog = ShardedKNN(db, mesh=mesh(), k=K, merge=merge)
    _, m, _, _ = prog._pallas_setup(28, None, "bf16x3")
    rows = q.shape[0] // batches
    before = merge_bytes_counted(merge)
    _, _, stats = prog.search_certified(q, selector="pallas",
                                        batch_size=batch_size)
    assert stats["fallback_queries"] == 0
    # the certified program keeps m+1 columns a query, not k
    want = batches * crossover.merge_bytes(rows, m + 1, SHARDS, merge)
    assert want > 0
    assert merge_bytes_counted(merge) - before == want
    assert stats["merge_bytes"] == want
    (call,) = [e for e in obs.get_event_log().recent()
               if e.get("span") == "certified.call"]
    assert (call["db_shards"], call["merge"], call["merge_source"],
            call["merge_bytes"]) == (SHARDS, merge, "explicit", want)


def test_the_repairs_reselect_counts_its_merge_by_its_own_k(fresh_registry):
    db, q = corpus("ties_6d_0to3")  # tie runs past the window: fallbacks
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    assert (prog.merge, prog.merge_source) == (
        crossover.choose_merge(K, SHARDS), "measured")
    _, m, _, _ = prog._pallas_setup(28, None, "bf16x3")
    _, _, stats = prog.search_certified(q, selector="pallas")
    assert stats["fallback_queries"] > 0
    spans = [e for e in obs.get_event_log().recent()
             if e.get("type") == "span"]
    reselects = [e for e in spans
                 if e["span"] == "certified.repair.reselect"]
    assert reselects
    want = crossover.merge_bytes(q.shape[0], m + 1, SHARDS, prog.merge) + sum(
        crossover.merge_bytes(e["rows"], e["widen"], SHARDS, prog.merge)
        for e in reselects)
    assert merge_bytes_counted(prog.merge) == want
    (call,) = [e for e in spans if e["span"] == "certified.call"]
    assert (call["merge_source"], call["merge_bytes"]) == ("measured", want)
    assert stats["merge_source"] == "measured"


def test_a_one_shard_call_counts_no_merge(fresh_registry):
    db, q = corpus("bytes_16d")
    prog = ShardedKNN(db, mesh=mesh(1), k=K)
    _, _, stats = prog.search_certified(q, selector="pallas")
    assert (stats["db_shards"], stats["merge_bytes"]) == (1, 0)
    assert mn.MERGE_BYTES not in obs.snapshot()


# --- the cell through the benchmark's harness --------------------------------
BENCH = tinyroot.load_bench()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(str(tmp_path_factory.mktemp("bench_x4")))


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    # the CPU backend reports no memory; the validator refuses 0
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run_cell(root, traced: bool) -> dict:
    lines = []
    parsed = harness.run_cell(root, CELL, 2**31 + 27, 1.5, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_through_the_harness_on_four_devices(
        root, cpu_memory_reading, traced):
    cell = harness.load_cell(root, CELL)
    assert cell.chips == SHARDS == cell.config["db_shards"]
    out = run_cell(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] >= SHARDS
    want = {m["name"] for m in
            lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        # these are there; a later PR may list more for the cell (its
        # first host-side metrics)
        assert {"merge_ms", "pallas_knn_shard_roofline", "kernel_ms",
                "tail_ms", "fallback_pct", "rank_corrected_pct",
                "idle_pct.sweep"} <= want
        assert "pallas_knn_roofline" not in want
    else:
        assert want == {"sweep_qps", "setup_s"}


def test_a_shard_dropped_before_the_merge_comes_out_not_correct(
        root, cpu_memory_reading, monkeypatch):
    """The control: one shard's candidates never reach the merge (the
    certified program's and the repair's alike), so every answer lacks
    the neighbours that shard held, and the comparison has to say so."""
    from jax import lax
    import jax.numpy as jnp

    real = sh._merge_shards

    def lossy(d, gi, keep, hosts, chips, merge, dcn_merge):
        lost = lax.axis_index(sh.DB_AXIS) == 2
        d = jnp.where(lost, jnp.inf, d)
        gi = jnp.where(lost, sh._INT_SENTINEL, gi)
        return real(d, gi, keep, hosts, chips, merge, dcn_merge)

    programs = (sh._pallas_certified_program, sh._knn_program)
    for prog in programs:
        prog.cache_clear()
    monkeypatch.setattr(sh, "_merge_shards", lossy)
    try:
        out = run_cell(root, False)
    finally:
        for prog in programs:  # no later test may find a lossy program
            prog.cache_clear()
    assert out["correct"] is False


# --- the cell's data files ---------------------------------------------------
def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_one_chips_share_of_the_scan():
    scan = harness._module("knn_scan", "work")
    shard = harness._module("knn_scan_shard", "work")
    cfg = _json("benchmark", "configs", "bigann20m-x4.json")
    traffic = _json("benchmark", "traffic", "sweep.json")
    peaks = _json("benchmark", "peaks.json")["kinds"]["TPU v5 lite"]
    ops, nbytes = shard.ops_bytes(cfg, traffic)
    all_ops, all_bytes = scan.ops_bytes(cfg, traffic)
    queries = 4.0 * traffic["batch_rows"] * cfg["dim"]
    assert ops == all_ops / 4  # a quarter of the operations,
    assert nbytes - queries == (all_bytes - queries) / 4  # of the rows,
    assert nbytes > all_bytes / 4  # and every query, whole
    assert shard.least_seconds(cfg, traffic, peaks) == scan.least_seconds(
        cfg, traffic, peaks) / 4  # compute-bound at d=128
    # one chip of it does what bigann5m's one chip does
    five = _json("benchmark", "configs", "bigann5m.json")
    assert shard.least_seconds(cfg, traffic, peaks) == scan.least_seconds(
        five, traffic, peaks)
    for one in (five, {**cfg, "db_shards": 1}):
        assert shard.ops_bytes(one, traffic) == scan.ops_bytes(one, traffic)
        assert shard.least_seconds(one, traffic, peaks) == \
            scan.least_seconds(one, traffic, peaks)


def test_the_configuration_is_the_deployment_bigann5m_is_a_chip_of():
    cfg = _json("benchmark", "configs", "bigann20m-x4.json")
    five = _json("benchmark", "configs", "bigann5m.json")
    for key in ("guarantees", "limits", "require", "dim", "metric", "k",
                "rows", "train_tile"):
        assert cfg[key] == five[key], key  # letter for letter
    assert cfg["limits"]["dist_rel_err_max"] == RANK_SLACK
    assert cfg["rows_n"] == 20_000_000 == cfg["db_shards"] * five["rows_n"]
    assert (cfg["db_shards"], cfg["mesh"]) == (4, "1x4")
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "bigann20m-x4"]
    assert entry["reduced"] == ["rows_n"] == list(cfg["reduced_from_source"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["source"] not in {
        c["source"] for c in bench["configs"] if c is not entry}
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bigann20m-x4", "sweep", cfg["db_shards"])
    four_chip = [w for w in bench["workloads"] if w["chips"] == 4]
    assert four_chip == [cell]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert "pallas_knn_roofline" not in listed  # it counts rows_n for ONE chip
    for name in ("merge_ms", "pallas_knn_shard_roofline"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["moves"] == "sweep_qps"


def test_the_stage_report_says_which_merge_answered(fresh_registry, tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "stage_report_x4",
        os.path.join(ROOT, "scripts", "certified_stage_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    db, q = corpus("bytes_16d")
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    _, m, _, _ = prog._pallas_setup(28, None, "bf16x3")
    log = tmp_path / "events.jsonl"
    obs.reset_event_log(str(log))
    for _ in range(2):
        prog.search_certified(q, selector="pallas", batch_size=16)
    obs.reset_event_log(None)
    table = report.stage_table(report.read_jsonl(str(log)), skip_calls=1)
    assert table["merge"] == {"db_shards": SHARDS, "merge": prog.merge,
                              "merge_source": "measured"}
    assert (table["calls"], table["batches"]) == (1, 3)
    assert table["per_batch"]["merge_bytes"] == crossover.merge_bytes(
        16, m + 1, SHARDS, prog.merge)
