"""Search under a scalar range filter (PR 57): ``ShardedKNN(row_attr=...)``
and ``search_certified(filter_range=...)`` under cosine AND squared L2,
against the float64 filtered oracle the benchmark holds the cell
``openai500k-intfilter.sweep_cos_filter`` to
(``benchmark/reference_cosfilter.py``; for l2 a brute force spelled out
here) and against the plain ``jax.numpy`` statement of the same contract
(``ops.tagfilter.range_topk_reference``), on one and on four CPU devices,
the attribute SHUFFLED (the program compares values, not positions):

- ranges that keep every row (the unfiltered call's answer, bit for
  bit), half, 1 %, fewer than k (padded -1 / +inf), none, ``lo >= hi``;
- a corpus of exact copies at a small margin, where the device flags
  queries by itself: the masked re-select answers some and the host's
  scan over the valid rows the others, and the counters say so;
- no returned id outside its range, ever;
- the range maker's words against a numpy packing at two row tiles;
- ``filter_tags`` under cosine (the same lifted refusal);
- what refuses says so; a call without ``filter_range`` is the program
  it was (the cosine cell's digests, recorded on the parent);
- the counters, the maker's account, the placement event, the spans.
"""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "benchmark"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import reference_cosfilter  # noqa: E402  (benchmark/)
from test_yfcc_filter import random_bags  # noqa: E402  (tests/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import certified, pallas_knn as pk, tagfilter  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402

K, DIM, TILE, ROWS = 10, 24, 1024, 3001
METRICS = ("l2", "cosine")


def mesh(shards: int = 1):
    return make_mesh(1, shards, devices=jax.devices()[:shards])


def oracle(db, attr, q, ranges, k, metric):
    """float64 (indices, distances) of the contract: the benchmark's own
    reference under cosine, a brute force spelled out under l2."""
    if metric == "cosine":
        return reference_cosfilter.oracle_topk(db, attr, q, ranges, k)
    d = ((q.astype(np.float64)[:, None, :]
          - db.astype(np.float64)[None]) ** 2).sum(-1)
    d[~reference_cosfilter.in_range(attr, ranges)] = np.inf
    ids = np.broadcast_to(np.arange(db.shape[0]), d.shape)
    return reference_cosfilter._pad(ids, d, k)


def holds_the_contract(prog, db, attr, q, ranges, metric, k=K, **kw):
    """One filtered call against the float64 oracle: the indices to the
    letter (padding included), no id outside its range, the distances
    within the metric's own bound."""
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        filter_range=ranges, **kw)
    want_i, want_d = oracle(db, attr, q, ranges, k, metric)
    np.testing.assert_array_equal(i, want_i)
    cmp = reference_cosfilter.compare(i, d, want_i, want_d, attr, ranges)
    assert cmp["invalid_returned"] == 0
    there = i >= 0
    assert np.isinf(d[~there]).all() and np.isfinite(d[there]).all()
    if metric == "cosine":
        assert cmp["dist_err_max"] <= 2.0 ** -18
    else:
        np.testing.assert_allclose(d[there], want_d[there], rtol=2.0 ** -18)
    assert stats["filter"]["filter"] == "range"
    found = there.sum(axis=1)
    assert stats["filter"]["short"] == ((found > 0) & (found < k)).sum()
    assert stats["filter"]["empty"] == (found == 0).sum()
    assert stats["filter"]["valid_rows"] == reference_cosfilter.in_range(
        attr, ranges).sum()
    return d, i, stats


# --- random rows, a shuffled attribute, every kind of range -------------------
def drawn(metric: str, seed: int = 57):
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(ROWS, DIM)).astype(np.float32)
    db *= rng.lognormal(0.0, 0.3, size=(ROWS, 1)).astype(np.float32)
    # distinct values in no order, negative ones among them
    attr = rng.permutation(ROWS).astype(np.int64) * 7 - 4000
    q = rng.normal(size=(24, DIM)).astype(np.float32)
    return db, attr, q


@pytest.fixture(scope="module")
def placed():
    made = {}

    def of(metric: str, shards: int):
        if (metric, shards) not in made:
            db, attr, q = drawn(metric)
            made[metric, shards] = (
                ShardedKNN(db, mesh=mesh(shards), k=K, metric=metric,
                           train_tile=1024, row_attr=attr), db, attr, q)
        return made[metric, shards]

    return of


def _span(attr, share):
    """A range that holds the ``share`` smallest values (about)."""
    ordered = np.sort(attr)
    return [int(ordered[0]), int(ordered[int(share * (attr.size - 1))]) + 1]


RANGES = {
    "all": lambda attr: [int(attr.min()), int(attr.max()) + 1],
    "half": lambda attr: _span(attr, 0.5),
    "one_percent": lambda attr: _span(attr, 0.01),
    "fewer_than_k": lambda attr: [int(np.sort(attr)[0]),
                                  int(np.sort(attr)[K - 4]) + 1],
    "none": lambda attr: [int(attr.max()) + 1, int(attr.max()) + 500],
    "lo_not_under_hi": lambda attr: [int(np.median(attr)),
                                     int(np.median(attr)) - 50],
}


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("kind", sorted(RANGES))
def test_a_range_call_equals_both_references(placed, kind, metric, shards):
    prog, db, attr, q = placed(metric, shards)
    ranges = np.tile(np.asarray(RANGES[kind](attr), np.int64), (len(q), 1))
    d, i, stats = holds_the_contract(prog, db, attr, q, ranges, metric)
    plain_d, plain_i = tagfilter.range_topk_reference(
        db, q, ranges, attr, K, metric)
    np.testing.assert_array_equal(i, plain_i)
    there = i >= 0
    np.testing.assert_allclose(d[there], plain_d[there], rtol=2e-5,
                               atol=2e-6)
    valid = reference_cosfilter.in_range(attr, ranges).sum(axis=1)
    if kind == "all":
        # every row valid: the unfiltered call's answer, bit for bit
        d0, i0, s0 = prog.search_certified(q, selector="pallas", tile_n=TILE)
        np.testing.assert_array_equal(i, i0)
        np.testing.assert_array_equal(d, d0)
        assert s0["filter"] == {"filter": "none"}
    elif kind == "fewer_than_k":
        assert (valid == K - 3).all() and (there.sum(axis=1) == K - 3).all()
        assert stats["filter"]["short"] == len(q)
    elif kind in ("none", "lo_not_under_hi"):
        assert not valid.any() and not there.any()
        assert stats["filter"]["empty"] == len(q)
    else:
        assert (valid >= K).all() and there.all()


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", METRICS)
def test_ranges_that_differ_from_query_to_query(placed, metric, shards):
    """Every kind in ONE call, the cell's way: the words differ a query,
    and a batch cut in three launches answers as the whole."""
    prog, db, attr, q = placed(metric, shards)
    kinds = sorted(RANGES)
    ranges = np.asarray([RANGES[kinds[j % len(kinds)]](attr)
                         for j in range(len(q))], np.int64)
    d, i, _ = holds_the_contract(prog, db, attr, q, ranges, metric)
    d3, i3, s3 = prog.search_certified(
        q, selector="pallas", tile_n=TILE, filter_range=ranges, batch_size=8)
    assert s3["batches"] == 3
    np.testing.assert_array_equal(i, i3)
    np.testing.assert_array_equal(d, d3)


# --- queries the device flags by itself ---------------------------------------
def copies(metric: str, seed: int = 3):
    """3,000 rows that are 30 whole-number directions, 100 exact copies
    each in shuffled places (under cosine every copy at its own power of
    two of length, which leaves its cosine the same to the bit): a query
    near a direction finds its copies tied, more of them than the
    analysis window holds, so no boundary can be proved."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, 5, size=(30, DIM)).astype(np.float32)
    base[:, 0] += 1.0  # no zero row
    db = base[rng.permutation(np.repeat(np.arange(30), 100))]
    if metric == "cosine":
        db = db * (2.0 ** rng.integers(-3, 4, size=(3000, 1))).astype(
            np.float32)
    attr = rng.permutation(3000).astype(np.int64)
    q = base[:12] + rng.normal(0, 0.01, size=(12, DIM)).astype(np.float32)
    return db, attr, np.tile(q, (3, 1))


def repair_outcomes() -> dict:
    series = obs.snapshot().get(mn.REPAIR_QUERIES, {"series": []})["series"]
    return {s["labels"]["outcome"]: s["value"] for s in series}


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("metric", METRICS)
def test_flagged_queries_are_repaired_among_their_valid_rows(metric, shards):
    db, attr, q = copies(metric)
    # a third of the queries under each: every row (100 tied copies: past
    # the widened selection's 78, the host scans), 40 % (some 40 copies:
    # the masked re-select holds them all and proves it), 3 % (a few
    # copies, then other directions: nothing tied at the boundary)
    ranges = np.asarray([[0, 3000]] * 12 + [[0, 1200]] * 12 + [[0, 90]] * 12)
    obs.reset(enabled=True)
    try:
        prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric=metric,
                          train_tile=1024, row_attr=attr)
        _, i, stats = holds_the_contract(prog, db, attr, q, ranges, metric,
                                         margin=4)
        outcomes = repair_outcomes()
        launches = {s["labels"]["program"]: s["value"] for s in
                    obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    finally:
        obs.reset()
    assert stats["fallback_queries"] >= 20
    assert stats["fallback_positions"] == sorted(stats["fallback_positions"])
    assert len(stats["fallback_positions"]) == stats["fallback_queries"]
    # every query of the first third went to the host's scan of its
    # valid rows, and queries of the second were proven by the re-select
    assert set(range(12)) <= set(stats["fallback_positions"])
    assert outcomes["host_scan"] == stats["host_exact_queries"] >= 12
    assert outcomes["proven"] >= 6
    assert outcomes["proven"] + outcomes["host_scan"] == stats[
        "fallback_queries"]
    # one launch of the maker for the batch, one for the repair's block
    # (``filter_mask`` is the account's name for the maker, whichever)
    assert launches["filter_mask"] == 2 and launches["reselect"] == 1
    # the tied copies come back in index order
    tied = i[0][i[0] >= 0]
    assert (np.diff(tied) > 0).all()


@pytest.mark.parametrize("metric", METRICS)
def test_a_tolerance_no_gap_clears_sends_every_query_to_the_host_scan(
        metric, monkeypatch):
    """The host's scan alone, a cosine call's kept norms cut to the valid
    rows: every query flagged, no widened selection proven."""
    real = ShardedKNN._certify_pallas

    def flag_all(self, batches, bs, d, i, q_np, *a, **kw):
        _, n_corrected, by_slack = real(self, batches, bs, d, i, q_np, *a,
                                        **kw)
        return np.arange(q_np.shape[0]), n_corrected, by_slack

    monkeypatch.setattr(ShardedKNN, "_certify_pallas", flag_all)
    monkeypatch.setattr(
        certified, "certification_tolerance",
        lambda q_np, db_np, **kw: np.full(q_np.shape[0], 1e30))
    db, attr, q = drawn(metric, seed=8)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric=metric, train_tile=1024,
                      row_attr=attr)
    ranges = np.asarray([RANGES[kind](attr) for kind in sorted(RANGES)] * 4)
    _, _, stats = holds_the_contract(prog, db, attr, q, ranges, metric)
    assert stats["fallback_queries"] == len(q)
    # a selection that ran out holds every valid row: proven; the others
    # (more valid rows than the widened width) are scanned
    widen = max(2 * (K + 28), K + 28 + 64)
    many = (reference_cosfilter.in_range(attr, ranges).sum(axis=1)
            > widen).sum()
    assert stats["host_exact_queries"] == many > 0


# --- the maker ----------------------------------------------------------------
def test_the_bounds_are_inclusive_clipped_and_empty_where_nothing_fits():
    top = tagfilter.ATTR_MAX
    got = tagfilter.range_bounds(np.asarray(
        [[0, 10], [5, 5], [7, 3], [-2 ** 40, 2 ** 40], [top, top + 1],
         [2 ** 33, 2 ** 34], [-2 ** 34, -2 ** 33], [-top - 1, -top + 1]]), 8)
    assert got.dtype == np.int32
    none = [top, -top]
    assert got.tolist() == [[0, 9], none, none, [-top, top], [top, top],
                            none, none, [-top, -top]]
    as_u64 = tagfilter.range_bounds(
        np.asarray([[3, 2 ** 63 + 5]], np.uint64), 1)
    assert as_u64.tolist() == [[3, top]]
    for bad in (np.zeros((3, 2)), np.zeros((2, 2), np.int32),
                np.zeros((3, 3), np.int32)):
        with pytest.raises(ValueError, match="filter_range"):
            tagfilter.range_bounds(bad, 3)
    with pytest.raises(ValueError, match="int32"):
        tagfilter.check_row_attr(np.asarray([0, -2 ** 31]), 2)
    with pytest.raises(ValueError, match="int32"):
        tagfilter.check_row_attr(np.asarray([0, 2 ** 31]), 2)
    with pytest.raises(ValueError, match="one a row"):
        tagfilter.check_row_attr(np.zeros(3), 3)
    with pytest.raises(ValueError, match="one a row"):
        tagfilter.check_row_attr(np.zeros(4, np.int64), 3)


@pytest.mark.parametrize("shards,tile", [(1, 1024), (1, 8192), (4, 1024)])
def test_the_range_maker_equals_a_numpy_packing(shards, tile):
    rng = np.random.default_rng(tile + shards)
    rows = 9000
    attr = rng.integers(-500, 500, size=rows)  # values repeat
    attr[:3] = [tagfilter.ATTR_MAX, -tagfilter.ATTR_MAX, 0]
    ranges = np.stack([rng.integers(-600, 600, size=40),
                       rng.integers(-600, 600, size=40)], axis=1)
    ranges[0], ranges[1] = [-2 ** 40, 2 ** 40], [0, 1]
    ranges[2] = [tagfilter.ATTR_MAX, tagfilter.ATTR_MAX + 1]
    valid = reference_cosfilter.in_range(attr, ranges)
    assert valid[0].all() and valid[2].sum() == 1 and not valid[
        ranges[:, 0] >= ranges[:, 1]].any()
    bounds = tagfilter.range_bounds(ranges, 40)
    for pos in range(40):
        np.testing.assert_array_equal(
            tagfilter.range_valid_rows(tagfilter.check_row_attr(attr, rows),
                                       *bounds[pos]),
            np.flatnonzero(valid[pos]))
    shard_rows = -(-rows // shards)
    placed = tagfilter.place_attr(
        tagfilter.check_row_attr(attr, rows), shards=shards,
        shard_rows=shard_rows, tile_n=tile)
    for s in range(shards):
        words = tagfilter.range_words(jnp.asarray(bounds),
                                      jnp.asarray(placed[s]), interpret=True)
        mine = np.zeros((40, shard_rows), bool)
        part = valid[:, s * shard_rows:(s + 1) * shard_rows]
        mine[:, :part.shape[1]] = part
        want = pk.pack_valid_words(mine, tile)
        np.testing.assert_array_equal(
            np.asarray(words).view(np.uint32), want)
        # and the unpacking the masked re-select lays over its distances
        np.testing.assert_array_equal(np.asarray(tagfilter.words_to_valid(
            words, tile_n=tile, n_rows=shard_rows)), mine)


def test_the_makers_body_binds_few_operations():
    """One traced loop over the queries and one over a word's 32 bits:
    a body unrolled in Python is seconds of every process's first batch
    (root PERF.md section 7, "Since PR 35" (4))."""
    traced = jax.make_jaxpr(lambda b, a: tagfilter.range_words(
        b, a, interpret=True))(
        jax.ShapeDtypeStruct((1024, 2), jnp.int32),
        jax.ShapeDtypeStruct((124 * 32, 128), jnp.int32))
    assert len(re.findall(r"\bpallas_call\b", str(traced))) == 1
    assert len(str(traced).splitlines()) < 120


# --- tags under cosine: the same lifted refusal -------------------------------
@pytest.mark.parametrize("shards", [1, 4])
def test_filter_tags_under_cosine(shards):
    rng = np.random.default_rng(11)
    db, _, q = drawn("cosine", seed=11)
    indptr, tags = random_bags(rng, ROWS, 40, 3)
    ft = rng.integers(0, 12, size=(len(q), 2)).astype(np.int32)
    ft[::3, 1] = -1
    ft[1] = [39, 38]
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="cosine",
                      train_tile=1024, row_tags=(indptr, tags))
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        filter_tags=ft)
    row_of = np.repeat(np.arange(ROWS), np.diff(indptr))
    valid = np.ones((len(q), ROWS), bool)
    for pos, pair in enumerate(ft):
        for tag in pair[pair >= 0]:
            valid[pos] &= np.isin(np.arange(ROWS), row_of[tags == tag])
    # the benchmark's cosine oracle over ids that are 1 on the valid rows
    for pos in range(len(q)):
        want_i, want_d = reference_cosfilter.oracle_topk(
            db, valid[pos].astype(np.int64), q[pos:pos + 1],
            np.asarray([[1, 2]]), K)
        np.testing.assert_array_equal(i[pos], want_i[0])
    assert stats["filter"]["filter"] == "tags"


# --- what refuses, and what did not move --------------------------------------
def test_what_cannot_take_a_range_says_so():
    db, attr, q = drawn("l2", seed=5)
    fr = np.tile([[0, 10]], (len(q), 1))
    bare = ShardedKNN(db, mesh=mesh(), k=K, train_tile=1024)
    with pytest.raises(ValueError, match="row_attr"):
        bare.search_certified(q, selector="pallas", filter_range=fr)
    prog = ShardedKNN(db, mesh=mesh(), k=K, train_tile=1024, row_attr=attr,
                      row_tags=random_bags(np.random.default_rng(1), ROWS,
                                           20, 2))
    with pytest.raises(ValueError, match="together"):
        prog.search_certified(q, selector="pallas", filter_range=fr,
                              filter_tags=np.zeros((len(q), 2), np.int32))
    for selector in ("approx", "exact"):
        with pytest.raises(ValueError, match="selector='pallas'"):
            prog.search_certified(q, selector=selector, filter_range=fr)
    for kernel in ("streaming", "fused"):
        with pytest.raises(ValueError, match="kernel='tiled'"):
            prog.search_certified(q, selector="pallas", filter_range=fr,
                                  kernel=kernel)
    with pytest.raises(ValueError, match="shape"):
        prog.search_certified(q, selector="pallas", filter_range=fr[:3])
    with pytest.raises(ValueError, match="whole numbers"):
        prog.search_certified(q, selector="pallas",
                              filter_range=fr.astype(np.float32))
    with pytest.raises(ValueError, match="one a row"):
        ShardedKNN(db, mesh=mesh(), k=K, row_attr=attr[:-1])
    with pytest.raises(ValueError, match="int32"):
        ShardedKNN(db, mesh=mesh(), k=K, row_attr=attr + 2 ** 40)
    dot = ShardedKNN(db, mesh=mesh(), k=K, metric="dot", row_attr=attr)
    with pytest.raises(ValueError, match="dot"):
        dot.search_certified(q, selector="pallas", filter_range=fr)
    # a pre-placed array under cosine leaves no rows as given
    cos = ShardedKNN(jax.device_put(db, jax.sharding.NamedSharding(
        mesh(), jax.sharding.PartitionSpec(sh.db_axes(mesh())))),
        mesh=mesh(), k=K, metric="cosine", row_attr=attr)
    with pytest.raises(ValueError, match="host array"):
        cos.search_certified(q, selector="pallas", filter_range=fr)
    # what takes no such argument at all
    for call in (lambda: prog.range_search_certified(q, radius_sq=1.0,
                                                     filter_range=fr),
                 lambda: prog.predict_certified(q, filter_range=fr),
                 lambda: prog.self_join_call(0, 10, 10, filter_range=fr)):
        with pytest.raises(TypeError, match="filter_range"):
            call()


with open(os.path.join(HERE, "fixtures", "cos_program_digests.json")) as _f:
    PARENT_COS_DIGESTS = json.load(_f)


def cos_program_text(queries: int) -> str:
    """The UNFILTERED certified program of a cosine placement at the
    cell's shape (500K x 1,536, k = 100, resident operands, the row tile
    in four steps), traced from abstract arguments: its jaxpr, the
    kernel's body included, as ``tests/program_digest.py`` prints one."""
    rows, dim, k, parts = 500_000, 1536, 100, 2
    block, steps = pk.row_blocking(dim, tile_n=pk.TILE_N, block_q=pk.BLOCK_Q,
                                   precision="bf16x3", kernel="tiled",
                                   terms="hh+hl+lh", survivors=None)
    assert steps == 4
    prog = sh._pallas_certified_program(
        mesh(), k + 28, k, "ring", pk.TILE_N, "bf16x3", n_train=rows,
        interpret=True, augmented=True, slack_outcome=True, row_block=block,
        resident_parts=parts)
    rows_p = -(-rows // pk.TILE_N) * pk.TILE_N
    aval = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(prog)(
        aval((queries, dim), jnp.float32), aval((rows, dim), jnp.float32),
        aval((), jnp.float32),
        *[aval((rows_p, dim), jnp.bfloat16)] * parts,
        aval((rows_p,), jnp.float32), aval((), jnp.float32)))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(m.group(1).split(", "))), text)


@pytest.mark.parametrize("queries", [4096, 1024])
def test_without_a_range_the_cosine_program_is_the_parents(queries):
    """Recorded on PR 56's tree (``python tests/test_cos_filter.py`` with
    that tree on the path prints them): the cosine cell's program, whole
    and at the sub-batch a default call is cut into, is what it was."""
    text = cos_program_text(queries)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_COS_DIGESTS[
        str(queries)]


def test_a_placement_with_an_attribute_runs_the_programs_it_ran(tmp_path):
    """Without ``filter_range`` nothing of the filter is built, placed
    or launched, and the certified program is the unmasked one."""
    db, attr, q = drawn("cosine", seed=2)
    log = tmp_path / "obs.jsonl"
    obs.reset(enabled=True)
    obs.reset_event_log(str(log))
    try:
        plain = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
        d0, i0, s0 = plain.search_certified(q, selector="pallas",
                                            tile_n=TILE)
        prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine",
                          row_attr=attr)
        d1, i1, s1 = prog.search_certified(q, selector="pallas", tile_n=TILE)
        launches = {s["labels"]["program"]: s["value"] for s in
                    obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    finally:
        obs.reset()
        obs.reset_event_log(from_env=True)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)
    assert s0["filter"] == s1["filter"] == {"filter": "none"}
    assert prog._attr_rows_cache is None and "filter_mask" not in launches
    events = [json.loads(ln) for ln in log.read_text().splitlines()]
    names = [e.get("span") or e.get("name") for e in events]
    assert "placement.row_attr" not in names
    assert "certified.filter_mask" not in names
    # (a first-call record exists only where this process had not
    # traced the program before)
    built = [e["key"] for e in events
             if e.get("span") == "program.first_call.certified"]
    assert not any("masked" in key for key in built)
    assert "program.first_call.filter_mask" not in names


# --- what a filtered call tells -----------------------------------------------
def test_counters_the_account_the_placement_event_and_the_spans(tmp_path):
    db, attr, q = copies("cosine")
    ranges = np.asarray([[0, 3000]] * 12 + [[0, 1200]] * 12 + [[0, 90]] * 12)
    log = tmp_path / "obs.jsonl"
    obs.reset(enabled=True)
    obs.reset_event_log(str(log))
    try:
        prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine",
                          train_tile=1024, row_attr=attr)
        for _ in range(2):
            _, _, stats = prog.search_certified(
                q, selector="pallas", tile_n=TILE, filter_range=ranges,
                margin=4)
        snap = obs.snapshot()
    finally:
        obs.reset()
        obs.reset_event_log(from_env=True)
    told = stats["filter"]
    by = {s["labels"]["outcome"]: s["value"]
          for s in snap[mn.FILTER_RANGE_QUERIES]["series"]}
    assert by == {"full": 2 * (36 - told["short"] - told["empty"]),
                  "short": 2 * told["short"], "empty": 2 * told["empty"]}
    (rows,) = snap[mn.FILTER_RANGE_VALID_ROWS]["series"]
    assert rows["value"] == 2 * told["valid_rows"] == 2 * 12 * (
        3000 + 1200 + 90)
    assert mn.FILTER_QUERIES not in snap  # the tag maker's, not this one's
    events = [json.loads(ln) for ln in log.read_text().splitlines()]
    names = [e.get("span") or e.get("name") for e in events]
    assert names.count("placement.row_attr") == 1  # placed once
    (placed_event,) = [e for e in events
                       if e.get("name") == "placement.row_attr"]
    assert (placed_event["tile"], placed_event["rows"]) == (TILE, 3000)
    assert names.count("certified.inflight.filter_mask") == 2  # one a call
    # (a first-call record exists only where this process had not
    # traced the maker at this shape before)
    assert all(e["key"].startswith("maker=range") for e in events
               if e.get("span") == "program.first_call.filter_mask")
    makers = [e for e in events if e.get("span") == "certified.filter_mask"]
    assert len(makers) >= 4 and {e["maker"] for e in makers} == {"range"}
    calls = [e for e in events if e.get("span") == "certified.call"]
    assert [c["filter"] for c in calls] == ["range", "range"]
    assert calls[0]["valid_rows"] == told["valid_rows"]
    for span in ("certified.repair", "certified.repair.reselect",
                 "certified.repair.refine"):
        spans = [e for e in events if e.get("span") == span]
        assert spans and all(e["masked"] is True for e in spans), span


if __name__ == "__main__":
    jax.config.update("jax_num_cpu_devices", 8)
    print(json.dumps({str(n): hashlib.sha256(
        cos_program_text(n).encode()).hexdigest() for n in (4096, 1024)},
        indent=1))
