"""The default sub-batch of a certified call (``analysis/subbatch.py``):
the rule alone, deviceless, over the benchmark's seven configurations; a
call the rule cuts against the same call uncut, bit for bit, on the CPU
with the kernel interpreted, at the smallest shape the rule cuts; and
what the call records of it: every stage series once a call, the sum of
its sub-batches' parts, the reason on the event, in ``stats`` and in one
counter.

Nothing here reads a clock for a speed: what is checked is which
launches and records exist, in which order, and that they add up.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import program_digest  # noqa: E402  (tests/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.analysis import subbatch  # noqa: E402
from knn_tpu.analysis.widths import lane_tiled  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.obs import trace as obs_trace  # noqa: E402
from knn_tpu.ops import certified, pallas_knn as pk  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402

N = subbatch.SUB_BATCHES
BLOCK_Q = 256  # tuning.DEFAULT_KNOBS["block_q"], what every cell resolves
#: the fewest queries the rule cuts at the default block on one query
#: shard, and the sub-batch it cuts them into
N_Q = N * subbatch.SUB_BATCH_MIN_ROWS
ROWS = subbatch.SUB_BATCH_MIN_ROWS
K, DIM = 10, 128


def rule(queries=4096, *, batch_size=None, operands="resident", width=128,
         block_q=BLOCK_Q, query_shards=1):
    return subbatch.certified_sub_batch(
        queries, batch_size=batch_size, operands=operands, width=width,
        block_q=block_q, query_shards=query_shards)


# --- the rule, deviceless -----------------------------------------------------
#: cell -> (the rows' columns as the placement is GIVEN them, the
#: operands' source on the chip); ``lane_tiled`` of the first is what
#: the rule reads, the placed width (PR 44)
CELLS = {
    "bigann5m.sweep": (128, "resident"),
    "bigann20m-x4.sweep": (128, "resident"),
    "ssnpp2m5.sweep_range": (256, "resident"),
    # resident since PR 49: the rule reckons a lane-tiled placement's
    # largest program at 1.25 x the rows, not a copied one's 2.7
    "gist1m.sweep": (960, "resident"),
    "text2image2m5.sweep_ip": (201, "resident"),
    "yfcc2m5.sweep_filter": (192, "resident"),
    "openai500k.sweep_cos": (1536, "resident"),
    # 3.94 GB of rows and 3.98 of halves and norms (resident since
    # PR 49, as gist1m)
    "imagenet-knn768.sweep_vote": (768, "resident"),
    # a block of the bulk self-join is a call of 4,096 rows to the rule
    # (2.56 GB of rows, 2.59 of halves and norms)
    "deep5m-knng.build": (96, "resident"),
}


@pytest.mark.parametrize("as_placed", [True, False])
@pytest.mark.parametrize("operands", ["resident", "per_call"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_over_the_seven_configurations(cell, operands, as_placed):
    """4,096 queries, one query shard (the four-chip cell's mesh is
    1x4: its shards are the rows'), the default query block.  At the
    width a placement lays the rows out in (whole lane tiles) every
    cell is cut into SUB_BATCHES equal parts where its operands are
    resident; at the width GIVEN, which a pre-placed array keeps, the
    three cells of 192, 201 and 960 columns are one batch."""
    width, _ = CELLS[cell]
    if as_placed:
        width = lane_tiled(width)
    rows, why = rule(width=width, operands=operands)
    if operands == "per_call":
        assert (rows, why) == (4096, "per_call_operands")
    elif width % 128:
        assert not as_placed
        assert (rows, why) == (4096, "layout_copy")
    else:
        assert (rows, why) == (4096 // N, "resident")
    assert why in subbatch.REASONS


def test_the_cells_as_the_chip_runs_them():
    cut = {cell for cell, (width, operands) in CELLS.items()
           if rule(width=lane_tiled(width), operands=operands)[1]
           == "resident"}
    # since PR 44 every cell whose operands are resident: the rows of
    # text2image2m5 (201 columns) and yfcc2m5 (192) are placed in 256
    # and since PR 49 keeps gist1m's and imagenet-knn768's operands too
    assert cut == set(CELLS)
    # a placement a full device keeps per_call is one launch still
    assert rule(width=768, operands="per_call")[1] == "per_call_operands"
    assert rule(width=lane_tiled(960), operands="per_call")[1] == (
        "per_call_operands")
    assert rule(width=lane_tiled(960))[1] == "resident"
    # and what the placement does not lay out keeps its width's reading
    assert {rule(width=w)[1] for w in (96, 192, 201, 960)} == {
        "layout_copy"}


@pytest.mark.parametrize("queries,want", [
    (1, "small"), (96, "small"), (512, "small"), (N_Q - 1, "small"),
    (N_Q, "resident"), (4096, "resident"), (10_000, "resident"),
])
def test_a_call_under_the_floor_is_one_batch(queries, want):
    rows, why = rule(queries)
    assert why == want
    if want == "small":
        assert rows == queries
    else:
        assert subbatch.SUB_BATCH_MIN_ROWS <= rows < queries


@pytest.mark.parametrize("queries", [N_Q + 1, 4000, 4097, 5000, 12_345])
@pytest.mark.parametrize("block_q", [128, 256])
def test_a_query_count_that_is_no_multiple_pads_the_tail(queries, block_q):
    rows, why = rule(queries, block_q=block_q)
    if why == "small":
        assert queries < N * max(subbatch.SUB_BATCH_MIN_ROWS,
                                 subbatch.SUB_BATCH_MIN_BLOCKS * block_q)
        return
    assert why == "resident" and rows % block_q == 0
    launches = -(-queries // rows)
    assert launches == N
    # padded to one compiled shape, by under a block a launch
    assert 0 <= launches * rows - queries < N * block_q


@pytest.mark.parametrize("mesh_shape,query_shards", [
    ((1, 4), 1), ((2, 2), 2), ((4, 1), 4)])
def test_a_sub_batch_is_whole_query_blocks_on_every_query_shard(
        mesh_shape, query_shards):
    for queries in (4096, 8192, 8200, 20_000):
        rows, why = rule(queries, query_shards=query_shards)
        least = max(subbatch.SUB_BATCH_MIN_ROWS,
                    subbatch.SUB_BATCH_MIN_BLOCKS * BLOCK_Q * query_shards)
        if queries < N * least:
            assert (rows, why) == (queries, "small")
            continue
        assert why == "resident"
        assert rows % (BLOCK_Q * query_shards) == 0
        assert rows // query_shards >= subbatch.SUB_BATCH_MIN_BLOCKS * BLOCK_Q
        assert -(-queries // rows) == N


@pytest.mark.parametrize("batch_size", [1, 32, 1024, 4096, 5000])
@pytest.mark.parametrize("operands,width", [
    ("resident", 128), ("per_call", 960), ("resident", 201)])
def test_an_explicit_batch_size_wins(batch_size, operands, width):
    assert rule(batch_size=batch_size, operands=operands, width=width) == (
        batch_size, "explicit")


# --- the program: the parent's -------------------------------------------------
with open(os.path.join(HERE, "fixtures",
                       "sub_batch_program_digests.json")) as _f:
    SUB_BATCH_DIGESTS = json.load(_f)
with open(os.path.join(HERE, "fixtures",
                       "unfiltered_program_digests.json")) as _f:
    WHOLE_DIGESTS = json.load(_f)


@pytest.mark.parametrize("cell", sorted(program_digest.CELLS))
def test_the_program_of_every_launch_is_the_parents(cell):
    """An uncut cell launches the program at its 4,096 queries, a cut
    one at the rule's rows: the jaxpr, the kernel's body included, is
    the one the parent's tree traces at those rows (there an explicit
    ``batch_size`` of as many)."""
    width, operands = CELLS[cell]
    width = lane_tiled(width)
    assert width == program_digest.CELLS[cell][2]
    rows, why = rule(program_digest.QUERIES, width=width, operands=operands)
    got = program_digest.digest(cell, rows)
    if why == "resident":
        assert rows in program_digest.SUB_BATCH_ROWS
        assert got == SUB_BATCH_DIGESTS[cell][str(rows)]
        assert got != WHOLE_DIGESTS[cell]
    else:
        assert rows == program_digest.QUERIES
        assert got == WHOLE_DIGESTS[cell]


# --- the call ------------------------------------------------------------------
@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


@pytest.fixture(scope="module")
def corpus():
    """Byte rows with repeats: tie runs in half the queries, and rows
    repeated past the analysis window, so that queries in BOTH halves of
    the call fall back."""
    rng = np.random.default_rng(42)
    db = rng.integers(0, 256, size=(1536, DIM)).astype(np.float32)
    db[256:512] = db[:256]              # a twin of each: tie runs
    db[1024:1024 + 80] = db[7]          # 80 copies: over the window
    db[1200:1200 + 80] = db[9]
    q = rng.integers(0, 256, size=(N_Q, DIM)).astype(np.float32)
    q[: N_Q // 2 : 3] = db[rng.integers(0, 256, size=len(q[: N_Q // 2 : 3]))]
    q[5], q[N_Q - 5] = db[7], db[9]     # one flagged query a half at least
    q[ROWS - 1], q[ROWS] = db[7], db[9]  # and on both sides of a seam
    return db, q


@pytest.fixture(scope="module")
def placed(corpus):
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K)
    prog.search_certified(corpus[1][:8], selector="pallas")
    return prog


def same_answer(a, b):
    (da, ia, sa), (db_, ib, sb) = a, b
    np.testing.assert_array_equal(ia, ib)
    np.testing.assert_array_equal(da, db_)  # bitwise: float64 arrays
    for key in ("certified", "fallback_queries", "rank_corrected_queries",
                "host_exact_queries"):
        assert sa.get(key) == sb.get(key), key


def test_a_cut_call_equals_the_uncut_call_bit_for_bit(placed, corpus):
    cut = placed.search_certified(corpus[1], selector="pallas")
    one = placed.search_certified(corpus[1], selector="pallas",
                                  batch_size=N_Q)
    assert (cut[2]["batches"], cut[2]["sub_batch"]) == (N, "resident")
    assert (one[2]["batches"], one[2]["sub_batch"]) == (1, "explicit")
    assert cut[2]["pallas_knobs"]["sub_batch"] == "resident"
    assert cut[2]["pallas_knobs"]["batches"] == N
    assert cut[2]["operands"] == "resident"
    same_answer(cut, one)
    # the corpus does what it was made for: both repairs had work
    assert cut[2]["fallback_queries"] >= 4
    assert cut[2]["rank_corrected_queries"] > N_Q // 8


@pytest.mark.parametrize("queries", [N_Q + 37, N_Q - 1, 300])
def test_a_padded_tail_and_a_small_call(placed, corpus, queries):
    q = np.concatenate([corpus[1], corpus[1][:64]])[:queries]
    got = placed.search_certified(q, selector="pallas")
    one = placed.search_certified(q, selector="pallas", batch_size=queries)
    if queries >= N_Q:
        assert (got[2]["batches"], got[2]["sub_batch"]) == (N, "resident")
    else:
        assert (got[2]["batches"], got[2]["sub_batch"]) == (1, "small")
    same_answer(got, one)


@pytest.mark.parametrize("metric,dim,pre_placed,want", [
    ("l2", 192, False, (N, "resident")),
    ("dot", 200, False, (N, "resident")),   # 201 placed-given columns
    ("l2", 201, True, (1, "layout_copy")),
])
def test_a_width_of_no_whole_tiles(metric, dim, pre_placed, want):
    """Rows the placement lays out itself are placed in whole lane
    tiles, so a default 4,096-query call on them is cut as any other
    (PR 44); a pre-placed array is used at the width it is handed in,
    and there the rule still reads ``layout_copy``."""
    rng = np.random.default_rng(3)
    db = rng.integers(0, 256, size=(600, dim)).astype(np.float32)
    q = rng.integers(0, 256, size=(N_Q, dim)).astype(np.float32)
    mesh = make_mesh(1, 1)
    train = db
    if pre_placed:
        from knn_tpu.parallel.collectives import shard
        from knn_tpu.parallel.mesh import db_axes

        train = shard(db, mesh, db_axes(mesh))
    prog = ShardedKNN(train, mesh=mesh, k=K, metric=metric)
    assert prog._tp.shape[1] == (dim if pre_placed else 256)
    _, i, stats = prog.search_certified(q, selector="pallas")
    assert stats["operands"] == "resident"
    assert (stats["batches"], stats["sub_batch"]) == want
    one = prog.search_certified(q[:64], selector="pallas", batch_size=64)
    np.testing.assert_array_equal(i[:64], one[1])


def test_operands_formed_in_the_program_are_one_batch(corpus):
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K)
    # the device reads full the first time the geometry is resolved
    full = {"bytes_limit": 1 << 20, "bytes_in_use": 1 << 20}
    tile = pk.effective_tile(prog._shard_rows(), pk.TILE_N, None,
                             K + 28 + 2)
    assert prog._row_operands(tile, False, memory_stats=full) is None
    _, _, stats = prog.search_certified(corpus[1], selector="pallas")
    assert stats["operands"] == "per_call"
    assert (stats["batches"], stats["sub_batch"]) == (
        1, "per_call_operands")


def test_a_filtered_call_is_cut_and_equal(corpus):
    """``filter_mask`` is launched once a sub-batch, on that
    sub-batch's tag ids, ahead of its certified program."""
    rng = np.random.default_rng(8)
    db = corpus[0]
    n = db.shape[0]
    bags = [np.unique(rng.integers(0, 40, size=rng.integers(1, 5)))
            for _ in range(n)]
    indptr = np.concatenate([[0], np.cumsum([len(b) for b in bags])]
                            ).astype(np.int64)
    tags = np.concatenate(bags).astype(np.int32)
    ft = rng.integers(0, 40, size=(N_Q, 2)).astype(np.int32)
    ft[::2, 1] = -1
    ft[3] = [-1, -1]
    ft[ROWS + 3] = [77, -1]  # a tag no row holds, in the second sub-batch
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K, row_tags=(indptr, tags))
    cut = prog.search_certified(corpus[1], selector="pallas", filter_tags=ft)
    one = prog.search_certified(corpus[1], selector="pallas", filter_tags=ft,
                                batch_size=N_Q)
    assert (cut[2]["batches"], cut[2]["sub_batch"]) == (N, "resident")
    assert one[2]["batches"] == 1
    same_answer(cut, one)
    assert cut[2]["filter"] == one[2]["filter"]
    assert (cut[1][ROWS + 3] == -1).all() and cut[2]["filter"]["empty"] >= 1
    launches = {s["labels"]["program"]: s["value"] for s in
                obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    assert launches["certified"] == N + 1


def test_a_voted_call_is_cut_and_equal():
    """``predict_certified(vote="softmax")``: the vote program takes the
    resident operands as the certified program does, so a default call
    of 4,096 queries is cut in four, and classes and totals are the
    uncut call's (the re-vote of the flagged queries, once a call over
    every sub-batch's, included)."""
    rng = np.random.default_rng(49)
    classes = 24
    centres = rng.normal(size=(classes, 96)).astype(np.float32)
    labels = rng.integers(0, classes, size=1500).astype(np.int32)
    db = centres[labels] + 0.7 * rng.normal(size=(1500, 96)).astype(np.float32)
    db[300:320] = db[299]      # equal rows under other labels: boundary
    labels[300:320] = np.arange(20) % classes   # ties and close margins
    q = (centres[rng.integers(0, classes, size=N_Q)]
         + 0.9 * rng.normal(size=(N_Q, 96)).astype(np.float32))
    q[7], q[ROWS], q[N_Q - 3] = db[299], db[305], db[310]
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=20, metric="cosine",
                      labels=labels, num_classes=classes)
    ask = dict(vote="softmax", temperature=0.07, classes_out=5,
               selector="pallas")
    cut = prog.predict_certified(q, **ask)
    one = prog.predict_certified(q, batch_size=N_Q, **ask)
    assert (cut[2]["operands"], cut[2]["batches"], cut[2]["sub_batch"]) == (
        "resident", N, "resident")
    assert (one[2]["batches"], one[2]["sub_batch"]) == (1, "explicit")
    np.testing.assert_array_equal(cut[0], one[0])
    np.testing.assert_array_equal(cut[1], one[1])  # float64 totals, bitwise
    for key in ("vote_boundary_queries", "vote_margin_queries",
                "vote_repaired_queries", "fallback_queries"):
        assert cut[2][key] == one[2][key], key
    # the corpus does what it was made for: the re-vote had work on
    # both sides of a seam
    assert cut[2]["vote_repaired_queries"] >= 3
    launches = {s["labels"]["program"]: s["value"] for s in
                obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    assert launches["certified"] == N + 1


def test_a_range_calls_lists_are_equal(placed, corpus, monkeypatch):
    """``range_search_certified`` takes no ``batch_size``: its first pass
    is a default call, cut by the rule; the uncut side is the rule at
    one sub-batch a call."""
    db, q = corpus
    radius_sq = 0.0  # exact copies: the 80-fold rows' lists pass k
    cut = placed.range_search_certified(q, radius_sq=radius_sq)
    assert (cut[3]["batches"], cut[3]["sub_batch"]) == (N, "resident")
    monkeypatch.setattr(subbatch, "SUB_BATCHES", 1)
    one = placed.range_search_certified(q, radius_sq=radius_sq)
    assert one[3]["batches"] == 1
    for a, b in zip(cut[:3], one[:3]):
        np.testing.assert_array_equal(a, b)
    assert cut[3]["range"] == one[3]["range"]
    assert cut[3]["range"]["truncated"] >= 2 and cut[0][-1] > 2 * 81


def test_the_repair_runs_once_over_the_union(placed, corpus, monkeypatch):
    calls, selects = [], []
    real = certified.repair_uncertified

    def recording(d, i, k, m, bad, q_np, db_np, *, select_fn, **kw):
        calls.append(np.array(bad))

        def select(qb, widen):
            selects.append(qb.shape[0])
            return select_fn(qb, widen)

        return real(d, i, k, m, bad, q_np, db_np, select_fn=select, **kw)

    monkeypatch.setattr(certified, "repair_uncertified", recording)
    _, _, stats = placed.search_certified(corpus[1], selector="pallas")
    assert stats["batches"] == N
    (bad,) = calls  # once a call
    assert bad.size == stats["fallback_queries"]
    # flagged queries of the first and of the last sub-batch, together
    assert bad.min() < ROWS and bad.max() >= N_Q - ROWS
    assert {5, ROWS - 1, ROWS, N_Q - 5} <= set(bad.tolist())
    assert selects == [bad.size]  # one re-select, over all of them


class _Recorded:
    """A launch's output that says when the host waits for it and when
    it copies it down."""

    def __init__(self, log, which, out):
        self.log, self.which, self.out = log, which, out

    def block_until_ready(self):
        self.log.append(("wait", self.which))
        self.out.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.which))
        return np.asarray(self.out)


def test_every_launch_precedes_the_first_fetch(placed, corpus, monkeypatch):
    log = []
    real = ShardedKNN._pallas_setup

    def setup(self, *a, **kw):
        prog, m, w, interpret = real(self, *a, **kw)

        def stub(*operands):
            which = sum(1 for what, _ in log if what == "launch")
            log.append(("launch", which))
            return _Recorded(log, which, prog(*operands))

        return stub, m, w, interpret

    monkeypatch.setattr(ShardedKNN, "_pallas_setup", setup)
    got = placed.search_certified(corpus[1], selector="pallas")
    monkeypatch.undo()
    want = [("launch", b) for b in range(N)]
    for b in range(N):
        want += [("wait", b), ("fetch", b)]
    assert log == want
    same_answer(got, placed.search_certified(
        corpus[1], selector="pallas", batch_size=N_Q))


# --- the accounting ------------------------------------------------------------
STAGES = sh._PALLAS_STAGES


def _series():
    return {s["labels"]["span"]: s["value"]
            for s in obs.snapshot()[mn.SPAN_SECONDS]["series"]}


def _spans():
    return [e for e in obs.get_event_log().recent()
            if e.get("type") == "span" and e["span"].startswith("certified.")]


@pytest.mark.parametrize("kw,launches", [
    pytest.param({}, N, id="cut_by_the_rule"),
    pytest.param({"batch_size": N_Q}, 1, id="one_batch"),
    pytest.param({"batch_size": N_Q // 8}, 8, id="eight_explicit"),
])
def test_a_stage_series_counts_calls_and_sums_its_parts(
        placed, corpus, monkeypatch, kw, launches):
    parts = []
    real = obs_trace.CallAccount.add

    def add(self, piece, seconds, **attrs):
        parts.append((piece, seconds, attrs))
        real(self, piece, seconds, **attrs)

    monkeypatch.setattr(obs_trace.CallAccount, "add", add)
    _, _, stats = placed.search_certified(corpus[1], selector="pallas", **kw)
    assert stats["batches"] == launches
    series = _series()
    by = {e["span"]: e for e in _spans()}
    for stage in STAGES:
        mine = [(s, a) for piece, s, a in parts if piece == stage]
        assert len(mine) == launches  # one scope a sub-batch
        assert series[stage]["count"] == 1  # one record a call
        assert series[stage]["sum"] == pytest.approx(
            sum(s for s, _ in mine), rel=1e-9)
        assert by[stage]["parent"] == "certified.call"
        assert "account_of" not in by[stage]
    assert by["certified.dispatch"]["h2d_bytes"] == N_Q * DIM * 4
    assert by["certified.d2h"]["d2h_bytes"] == sum(
        a["d2h_bytes"] for p, _, a in parts if p == "certified.d2h")
    assert by["certified.rank_correct"]["queries_corrected"] == stats[
        "rank_corrected_queries"]
    assert isinstance(by["certified.rank_correct"]["members"], int)
    # the stage's insides, handed up by the code below it, add up too
    assert by["certified.rank_correct"]["score_s"] == pytest.approx(
        by["certified.rank_correct.score"]["dur_s"], abs=2e-6)
    assert series["certified.call"]["count"] == 1
    assert series["certified.repair"]["count"] == 1
    # exposed + the union of the flights is still the call
    exposed = by["certified.exposed"]
    assert exposed["launches"] == launches + 1  # and the one re-select
    assert exposed["dur_s"] + exposed["inflight_union_s"] == pytest.approx(
        exposed["call_s"], abs=2e-6)
    assert by["certified.inflight.certified"]["launches"] == launches
    # the stages are children of the call: their sum is inside it
    children = sum(e["dur_s"] for e in _spans() if e.get("parent")
                   == "certified.call")
    assert children <= by["certified.call"]["dur_s"] + 1e-4


def test_the_reason_on_the_event_in_stats_and_in_one_counter(placed, corpus):
    q = corpus[1]
    for kw, rows, why, batches in (
            ({}, N_Q, "resident", N), ({}, 300, "small", 1),
            ({"batch_size": 512}, N_Q, "explicit", N_Q // 512),
            ({}, N_Q, "resident", N)):
        obs.reset_event_log(None)
        _, _, stats = placed.search_certified(q[:rows], selector="pallas",
                                              **kw)
        (call,) = [e for e in _spans() if e["span"] == "certified.call"]
        assert (call["batches"], call["sub_batch"]) == (batches, why)
        assert (stats["batches"], stats["sub_batch"]) == (batches, why)
    counted = {s["labels"]["why"]: s["value"] for s in
               obs.snapshot()[mn.CERTIFIED_SUB_BATCH_CALLS]["series"]}
    assert counted == {"resident": 2, "small": 1, "explicit": 1}
    assert set(counted) <= set(subbatch.REASONS)
    # the counted selectors are one batch unless the caller cuts, and
    # say nothing of a rule that is not theirs
    _, _, stats = placed.search_certified(q[:64], selector="approx")
    assert stats["batches"] == 1 and "sub_batch" not in stats
