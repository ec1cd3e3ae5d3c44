"""k in the thousands on the certified path (PR 55, the cell
``knnlm1m.sweep_k1024``): ``search_certified(selector="pallas")`` past
the large-keep line (m+2 over 256: both Pallas select stages stand
aside, a survivor depth over 2) against the benchmark's plain reference
``reference_topk``; the survivor depth's rule as a table and its model
against a count; the sub-batch's memory rule without a device at the
cell's shape and unchanged at the nine older cells'; and
``certified_plan`` against what a call reports and records.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.analysis import hbm, subbatch
from knn_tpu.obs import names as mn
from knn_tpu.ops import pallas_knn as pk
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.parallel.sharded import _analysis_window

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import datagen  # noqa: E402  (benchmark/)
import datagen_mix  # noqa: E402
import reference_topk  # noqa: E402

sys.path.remove(os.path.join(ROOT, "benchmark"))

with open(os.path.join(ROOT, "benchmark", "configs", "knnlm1m.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELL = "knnlm1m.sweep_k1024"
MARGIN = 28  # of every k = 100 cell; from k = 232 an eighth of k
V5E = 16909336064     # bytes_limit of one v5e chip, as the chip reads it


@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def counter(name):
    series = obs.snapshot().get(name, {"series": []})["series"]
    return sum(s["value"] for s in series)


# --- the search past the large-keep line ----------------------------------
ROWS, DIM, K, N_Q, TILE = 8192, 64, 300, 64, 1024


def corpus(law: str):
    if law == "uniform":
        spec = {"dist": "uniform", "high": 1.0}
    else:
        spec = {**CONFIG["rows"], "clusters": 16}
    return (datagen_mix.draw(spec, ROWS, DIM, 55, datagen.STREAM_ROWS),
            datagen_mix.draw(spec, N_Q, DIM, 55, datagen.STREAM_QUERIES))


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("law", ["uniform", "zipf_gauss_mix"])
def test_k_300_equals_the_reference(law, shards, fresh_registry):
    db, q = corpus(law)
    prog = ShardedKNN(db, mesh=make_mesh(1, shards,
                                         devices=jax.devices()[:shards]),
                      k=K, metric="l2")
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE)
    want_i, want_d = reference_topk.oracle_topk(db, q, K)
    cmp = reference_topk.compare(i, d, want_i, want_d, db, q)
    assert cmp["mismatched_rows"] == 0
    assert cmp["dist_rel_err_max"] <= CONFIG["limits"]["dist_rel_err_max"]
    # past the line: m+2 over both stages' keep, a depth over the default
    knobs = stats["pallas_knobs"]
    assert K + pk.default_margin(K) + 2 > pk.FINAL_SELECT_MAX_KEEP
    assert knobs["final_select_stage"] == "xla"
    assert knobs["survivors"] is None  # the knob, as the caller left it
    assert knobs["survivor_depth"] == stats["survivor_depth"] > 2
    assert stats["select_width"] == (
        -(-ROWS // shards // TILE) * knobs["survivor_depth"] * 128)
    assert stats["certified"] + stats["fallback_queries"] == N_Q
    assert stats["bin_overflow_queries"] <= stats["fallback_queries"]
    assert counter(mn.CERTIFIED_BIN_OVERFLOW) == stats["bin_overflow_queries"]


def test_the_answer_does_not_depend_on_the_depth():
    db, q = corpus("uniform")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
                      k=K, metric="l2")
    ruled = prog.search_certified(q, selector="pallas", tile_n=TILE)
    two = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                survivors=2)
    assert two[2]["survivor_depth"] == 2 != ruled[2]["survivor_depth"]
    assert two[2]["pallas_knobs"]["survivors"] == 2
    # two survivors of 330 neighbours over 1,024 bins: nearly every
    # query is repaired, and the answer is the same one
    assert two[2]["fallback_queries"] > ruled[2]["fallback_queries"]
    np.testing.assert_array_equal(two[1], ruled[1])


# --- the depth rule -------------------------------------------------------
#: one chip's rows and k of every cell (BENCHMARK.json's order)
CELLS = [("bigann5m", 5_000_000, 128, 100), ("gist1m", 1_000_000, 1024, 100),
         ("bigann20m-x4", 5_000_000, 128, 100),
         ("text2image2m5", 2_500_000, 256, 10),
         ("ssnpp2m5", 2_500_000, 256, 100), ("yfcc2m5", 2_500_000, 256, 10),
         ("openai500k", 500_000, 1536, 100),
         ("imagenet-knn768", 1_281_167, 768, 20),
         ("deep5m-knng", 5_000_000, 128, 10)]


@pytest.mark.parametrize("name,rows,width,k", CELLS)
def test_the_nine_cells_keep_two_survivors(name, rows, width, k):
    depth, tile, share = pk.survivor_depth(rows, pk.TILE_N, None,
                                           k + MARGIN + 2)
    assert (depth, tile) == (pk.DEFAULT_SURVIVORS, pk.TILE_N)
    assert share < pk.SURVIVOR_OVERFLOW_LIMIT
    # and the tile is the one the kernel resolves with no depth named
    assert tile == pk.effective_tile(rows, pk.TILE_N, None, k + MARGIN + 2)


def test_the_highest_of_the_nine_is_openai500k_under_the_limit():
    shares = {name: pk.survivor_depth(rows, pk.TILE_N, None,
                                      k + MARGIN + 2)[2]
              for name, rows, _, k in CELLS}
    assert max(shares, key=shares.get) == "openai500k"
    assert 0.02 < shares["openai500k"] < 0.025 < pk.SURVIVOR_OVERFLOW_LIMIT
    assert shares["bigann5m"] == pytest.approx(2.4e-4, rel=0.05)


def test_the_margin_follows_k():
    assert [pk.default_margin(k) for k in (1, 10, 20, 100, 200, 231)] == [
        MARGIN] * 6
    assert [pk.default_margin(k) for k in (232, 300, 1024, 2048)] == [
        29, 37, 128, 256]


def test_knnlm1m_takes_four():
    rows, k = CONFIG["rows_n"], CONFIG["k"]
    # at the margin of the k = 100 cells (what the issue reckoned with)
    assert [round(pk.bin_overflow_share(k + MARGIN + 2, 62 * 128, depth), 4)
            for depth in (2, 3, 4)] == [0.9395, 0.0884, 0.0024]
    keep = k + pk.default_margin(k) + 2
    assert keep == 1154
    assert [round(pk.bin_overflow_share(keep, 62 * 128, depth), 4)
            for depth in (2, 3, 4)] == [0.974, 0.1233, 0.0038]
    depth, tile, share = pk.survivor_depth(rows, pk.TILE_N, None, keep)
    assert (depth, tile) == (4, pk.TILE_N) and share < 0.004
    # a caller's depth is taken as given, capped like _geometry caps it
    assert pk.survivor_depth(rows, pk.TILE_N, 2, keep)[0] == 2
    assert pk.survivor_depth(rows, pk.TILE_N, 99, keep)[0] == pk.MAX_SURVIVORS


def test_a_corpus_of_a_few_bins_takes_the_least_share():
    # one tile's 128 bins under 330 neighbours: no depth is under the
    # limit, the deepest reads least
    depth, _, share = pk.survivor_depth(8192, 8192, None, 330)
    assert depth == pk.MAX_SURVIVORS and share > pk.SURVIVOR_OVERFLOW_LIMIT
    assert share == min(pk.survivor_depth(8192, 8192, d, 330)[2]
                        for d in range(2, pk.MAX_SURVIVORS + 1))


def test_the_model_against_a_count_on_uniform_rows():
    """Uniform rows fall into the bins independently of their distance
    to a query, which is all the model assumes: the share of queries
    with more than ``depth`` of their ``keep`` nearest in one bin."""
    rng = np.random.default_rng(55)
    rows, tile, keep, n_q = 65_536, 1024, 330, 1024
    db = rng.random((rows, 8), dtype=np.float32)
    q = rng.random((n_q, 8), dtype=np.float32)
    d = ((q[:, None, :].astype(np.float64) - db[None]) ** 2).sum(-1)
    top = np.argpartition(d, keep - 1, axis=1)[:, :keep]
    bins = np.sort((top // tile) * 128 + top % 128, axis=1)
    for depth in (2, 3):
        full = (bins[:, depth:] == bins[:, :-depth]).any(axis=1)
        model = pk.bin_overflow_share(keep, rows // tile * 128, depth)
        sd = (model * (1 - model) / n_q) ** 0.5
        assert abs(full.mean() - model) < 4 * sd, (depth, full.mean(), model)


def test_the_counter_counts_the_queries_a_full_bin_fails(fresh_registry):
    """At two survivors every query whose exact top-k puts three rows in
    one bin MUST fail its certificate (one of the three is no
    candidate), and ``_bin_overflows`` finds exactly those."""
    db, q = corpus("uniform")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
                      k=K, metric="l2")
    _, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        survivors=2)
    bins = np.sort((i // TILE) * 128 + i % 128, axis=1)
    full = (bins[:, 2:] == bins[:, :-2]).any(axis=1)
    assert stats["bin_overflow_queries"] == int(full.sum()) > 0
    assert stats["fallback_queries"] >= int(full.sum())


# --- the sub-batch by memory ----------------------------------------------
def launch_terms(rows, width, k, with_lo=True, in_use=None):
    """(bytes a query, room) of one v5e chip at a cell's shape, by the
    rules' own arithmetic and no device."""
    m = k + pk.default_margin(k)
    depth, tile, _ = pk.survivor_depth(rows, pk.TILE_N, None, m + 2)
    select_width = -(-rows // tile) * depth * 128
    w = _analysis_window(k, m)
    query_bytes = hbm.certified_query_bytes(
        m, width, select_width, w + -(-(w - 1) // 32) + 1 + k)
    placed = rows * width * 4
    form = hbm.row_operand_bytes(-(-rows // tile) * tile, width, with_lo)
    room = hbm.resident_operands_room(
        form, placed, {"bytes_limit": V5E, "bytes_in_use": in_use or placed,
                       "bytes_reserved": 0}, width=width)
    assert room["kept"]
    return query_bytes, hbm.certified_launch_room(room), room


def test_knnlm1m_is_cut_to_what_fits_the_chip():
    query_bytes, left, room = launch_terms(CONFIG["rows_n"], CONFIG["dim"],
                                           CONFIG["k"])
    # 9.4 MB of gathered rows a query and their differences (1,153 rows
    # of 4 KB, twice), 0.5 of candidates: 1,024 queries would hold
    # 10.2 GB beside 8.4
    assert 9.8e6 < query_bytes < 10.1e6
    assert 1024 * query_bytes > left
    rows, why = subbatch.certified_sub_batch(
        4096, batch_size=None, operands="resident", width=CONFIG["dim"],
        block_q=256, query_shards=1, query_bytes=query_bytes,
        room_bytes=left)
    assert (rows, why) == (512, "memory") and why in subbatch.REASONS
    # inside the 14.8 GB the placement rule keeps to, with the operands
    assert room["limit"] == int(hbm.RESIDENT_FILL * V5E)
    assert (room["held"] + room["form_bytes"] + rows * query_bytes
            <= room["limit"])
    # whole query blocks, one compiled shape a call, whatever the call
    for queries in (4096, 4097, 5000, 1024, 600):
        got, why = subbatch.certified_sub_batch(
            queries, batch_size=None, operands="resident",
            width=CONFIG["dim"], block_q=256, query_shards=1,
            query_bytes=query_bytes, room_bytes=left)
        assert (got % 256 == 0 or got == queries) and got * query_bytes <= left
        assert why == ("memory" if queries > 650 else "small")
    # two query shards hold half a launch each
    assert subbatch.certified_sub_batch(
        4096, batch_size=None, operands="resident", width=CONFIG["dim"],
        block_q=256, query_shards=2, query_bytes=query_bytes,
        room_bytes=left) == (1024, "memory")
    # the caller's own batch wins, and no reading is no bound
    assert subbatch.certified_sub_batch(
        4096, batch_size=2048, operands="resident", width=CONFIG["dim"],
        block_q=256, query_shards=1, query_bytes=query_bytes,
        room_bytes=left) == (2048, "explicit")
    assert subbatch.certified_sub_batch(
        4096, batch_size=None, operands="resident", width=CONFIG["dim"],
        block_q=256, query_shards=1, query_bytes=query_bytes,
        room_bytes=0) == (1024, "resident")


@pytest.mark.parametrize("name,rows,width,k", CELLS)
def test_the_nine_cells_are_cut_as_they_were(name, rows, width, k):
    query_bytes, left, _ = launch_terms(
        rows, width, k, with_lo=name not in ("bigann5m", "bigann20m-x4",
                                             "ssnpp2m5", "yfcc2m5"))
    assert 1024 * query_bytes < left / 3
    kw = dict(batch_size=None, operands="resident", width=width,
              block_q=256, query_shards=1)
    for queries in (4096, 8192, 1000):
        assert subbatch.certified_sub_batch(
            queries, query_bytes=query_bytes, room_bytes=left, **kw
        ) == subbatch.certified_sub_batch(queries, **kw)
    assert subbatch.certified_sub_batch(
        4096, query_bytes=query_bytes, room_bytes=left, **kw
    ) == (1024, "resident")


def test_a_device_with_no_room_left_is_cut_as_it_was():
    room = {"limit": 100, "held": 90, "form_bytes": 20}
    assert hbm.certified_launch_room(room) == 0
    assert hbm.certified_launch_room({"limit": 0}) == 0
    assert hbm.certified_launch_room({}) == 0
    assert subbatch.certified_sub_batch(
        4096, batch_size=None, operands="per_call", width=1024, block_q=256,
        query_shards=1, query_bytes=9_000_000, room_bytes=0
    ) == (4096, "per_call_operands")


# --- the plan -------------------------------------------------------------
def test_the_plan_is_what_a_call_reports(fresh_registry):
    db, q = corpus("uniform")
    prog = ShardedKNN(db, mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
                      k=K, metric="l2")
    plan = prog.certified_plan(N_Q, tile_n=TILE)
    margin = pk.default_margin(K)
    assert margin == 37
    assert (plan["k"], plan["m"], plan["row_tile"]) == (K, K + margin, TILE)
    assert plan["survivor_depth"] == 4
    assert plan["overflow_share"] == pytest.approx(
        pk.bin_overflow_share(K + margin + 2, ROWS // TILE * 128, 4))
    assert plan["select_width"] == ROWS // TILE * 4 * 128
    assert (plan["select_merge_stage"], plan["final_select_stage"]) == (
        "none", "xla")
    assert (plan["queries"], plan["sub_batch_rows"], plan["sub_batch"],
            plan["batches"]) == (N_Q, N_Q, "small", 1)
    assert plan["room_bytes"] == 0  # the CPU reports no limit
    assert plan["launch_bytes"] == N_Q * hbm.certified_query_bytes(
        K + margin, 128, plan["select_width"],
        _analysis_window(K, K + margin) + 10 + 1 + K)
    # nothing was launched but the operands' placement
    assert counter(mn.CERTIFIED_LAUNCHES) == 0
    _, _, stats = prog.search_certified(q, selector="pallas", tile_n=TILE)
    knobs = stats["pallas_knobs"]
    shared = plan.keys() & knobs.keys()
    assert {"survivor_depth", "final_select_stage", "operands", "sub_batch",
            "batches", "row_block", "row_steps", "dim_chunk", "dim_chunks",
            "select_merge_short", "interpret"} <= shared
    assert {key: plan[key] for key in shared} == {
        key: knobs[key] for key in shared}
    assert prog.certified_plan(N_Q, tile_n=TILE) == plan
    # one event a placement while the calls resolve alike, the launches
    # counted by depth and stage beside the calls
    events = [e for e in obs.get_event_log().recent()
              if e.get("name") == "certified.plan"]
    assert len(events) == 1
    assert {key: events[0][key] for key in plan} == plan
    series = obs.snapshot()[mn.CERTIFIED_LAUNCHES]["series"]
    assert [(s["labels"], s["value"]) for s in series] == [
        ({"survivor_depth": "4", "final_select_stage": "xla"}, 1)]
    assert counter(mn.CERTIFIED_SUB_BATCH_CALLS) == 1
    # a call of another size resolves otherwise, and says so
    assert prog.certified_plan(4096, tile_n=TILE)["batches"] == 4
    assert len([e for e in obs.get_event_log().recent()
                if e.get("name") == "certified.plan"]) == 2


def test_a_chip_with_little_room_cuts_the_call_and_answers_the_same():
    db, q = corpus("uniform")
    q = np.concatenate([q] * 16)[:1000]
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    free = ShardedKNN(db, mesh=mesh, k=K, metric="l2")
    one = free.search_certified(q, selector="pallas", tile_n=TILE)
    assert (one[2]["batches"], one[2]["sub_batch"]) == (1, "small")
    tight = ShardedKNN(db, mesh=mesh, k=K, metric="l2")
    placed = tight._tp.nbytes
    form = hbm.row_operand_bytes(ROWS, 128, True)
    margin = pk.default_margin(K)
    query_bytes = hbm.certified_query_bytes(
        K + margin, 128, ROWS // TILE * 4 * 128,
        _analysis_window(K, K + margin) + 10 + 1 + K)
    # room for the rows, their operands, the rule's temporaries, and
    # 300 queries of a launch beside them
    limit = placed + form + max(int(1.25 * placed), 300 * query_bytes)
    assert tight._row_operands(TILE, True, memory_stats={
        "bytes_limit": int(limit / hbm.RESIDENT_FILL) + 8,
        "bytes_in_use": placed}) is not None
    plan = tight.certified_plan(1000, tile_n=TILE)
    assert 300 * query_bytes <= plan["room_bytes"] < 512 * query_bytes
    assert (plan["sub_batch_rows"], plan["sub_batch"], plan["batches"]) == (
        256, "memory", 4)
    assert plan["launch_bytes"] == 256 * query_bytes <= plan["room_bytes"]
    cut = tight.search_certified(q, selector="pallas", tile_n=TILE)
    assert (cut[2]["batches"], cut[2]["sub_batch"]) == (4, "memory")
    np.testing.assert_array_equal(cut[1], one[1])
    np.testing.assert_array_equal(cut[0], one[0])


# --- the benchmark's lists ------------------------------------------------
def test_the_cell_is_appended_and_nothing_else_moved():
    # by name: later cells are appended after this one (PR 57's is)
    (cfg,) = [c for c in BENCH["configs"] if c["name"] == "knnlm1m"]
    assert BENCH["configs"].index(cfg) == 9
    assert cfg["reduced"] == ["rows_n"]
    assert cfg["source"] == CONFIG["source"]
    cell = BENCH["workloads"][9]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "knnlm1m", "sweep_k1024", 1)
    assert len(BENCH["workloads"]) >= 10
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) == 1
    first_ten = [c["name"] for c in BENCH["workloads"][:10]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert [w for w in m["workloads"] if w in first_ten][-1] == CELL
    mine = [m for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in mine] == [
        "select_final_ms", "launches_per_call", "survivor_overflow_pct"]
    at = BENCH["per_layer"].index(mine[0])
    assert BENCH["per_layer"][at:at + 3] == mine
