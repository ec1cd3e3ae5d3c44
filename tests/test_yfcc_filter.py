"""Exact filtered search (PR 40): ``ShardedKNN(row_tags=...)`` and
``search_certified(filter_tags=...)`` against the float64 filtered
oracle the benchmark holds the cell ``yfcc2m5.sweep_filter`` to
(``benchmark/reference_filter.py``) and against the plain ``jax.numpy``
statement of the same contract (``ops.tagfilter.filtered_topk_reference``),
on one and on four CPU devices:

- random bags and queries, and built cases: no valid row, 1 to 9,
  exactly k, a valid row farther than a thousand invalid nearer ones,
  equal distances across the validity boundary, two tags where OR is
  not AND, a tag just under and just over the bitmap rule, a query whose
  two tags take the two forms, rows off a multiple of 4,096;
- a fallback forced through the masked re-select and through the host
  scan;
- the validity words' layout against a numpy packing, the mask
  program against it, and the unpacking;
- ``filter_tags=None`` is the parent's program at the five cells'
  shapes (``tests/program_digest.py``);
- counters, the ``filter_mask`` program's account, the placement event;
- what refuses a filter says so.

The masked kernel and the mask program compiled for a described v5e at
the cell's shape are two cases of tests/test_text2image.py, the one
file that holds the described chip.
"""

import hashlib
import json
import os
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

import program_digest  # noqa: E402  (tests/)
import reference_filter  # noqa: E402  (benchmark/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import certified, pallas_knn as pk, tagfilter  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402

K, DIM, TILE = 10, 24, 1024


def mesh(shards: int):
    return make_mesh(1, shards, devices=jax.devices()[:shards])


def random_bags(rng, n: int, vocabulary: int, per_row: float):
    """CSR bags under a Zipf(0.8) law: a few frequent tags, many rare."""
    p = np.arange(1, vocabulary + 1) ** -0.8
    p /= p.sum()
    bags = [np.unique(rng.choice(vocabulary, size=rng.poisson(per_row) + 1,
                                 p=p)) for _ in range(n)]
    return csr(bags)


def csr(bags):
    indptr = np.concatenate([[0], np.cumsum([len(b) for b in bags])])
    tags = (np.concatenate(bags) if len(bags) else np.empty(0)
            ).astype(np.int32)
    return indptr.astype(np.int64), tags


def both_references(db, q, ft, indptr, tags, k=K):
    want_i, want_d = reference_filter.oracle_topk(db, indptr, tags, q, ft, k)
    plain_d, plain_i = tagfilter.filtered_topk_reference(
        db, q, ft, indptr, tags, k)
    return want_i, want_d, plain_i, plain_d


def holds_the_contract(placed, db, q, ft, indptr, tags, k=K, **kw):
    d, i, stats = placed.search_certified(q, selector="pallas",
                                          filter_tags=ft, **kw)
    want_i, want_d, plain_i, plain_d = both_references(
        db, q, ft, indptr, tags, k)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_array_equal(np.isfinite(d), np.isfinite(want_d))
    fin = np.isfinite(want_d)
    np.testing.assert_allclose(d[fin], want_d[fin], rtol=2.0 ** -18, atol=0)
    cmp = reference_filter.compare(i, d, want_i, want_d, indptr, tags, ft)
    assert (cmp["mismatched_rows"], cmp["invalid_returned"]) == (0, 0)
    assert cmp["dist_rel_err_max"] <= 2.0 ** -18
    # whole numbers: float32 at highest precision is exact too, so the
    # plain jax.numpy statement agrees index for index
    np.testing.assert_array_equal(i, plain_i)
    np.testing.assert_allclose(d[fin], plain_d[fin], rtol=1e-6)
    return d, i, stats


# --- random bags, one and four devices ---------------------------------------
@pytest.mark.parametrize("shards,rows", [(1, 3001), (4, 3001), (1, 5000)])
def test_a_filtered_call_equals_both_references(shards, rows):
    rng = np.random.default_rng(40 + shards)
    db = rng.integers(0, 256, size=(rows, DIM)).astype(np.float32)
    q = rng.integers(0, 256, size=(48, DIM)).astype(np.float32)
    indptr, tags = random_bags(rng, rows, 2500, 6)
    ft = rng.integers(0, 2500, size=(48, 2)).astype(np.int32)
    ft[::2, 1] = -1            # one tag
    ft[0] = [-1, -1]           # no tag: every row
    ft[1] = [2600, -1]         # a tag no row holds
    ft[2] = [0, 1]             # the two most frequent
    ft[3] = [-1, 0]            # the first slot empty
    placed = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=1024,
                        row_tags=(indptr, tags))
    _, i, stats = holds_the_contract(placed, db, q, ft, indptr, tags)
    told = stats["filter"]
    assert told["filter"] == "tags"
    assert told["empty"] == int((i[:, 0] < 0).sum()) > 0
    assert told["short"] == int(((i[:, 0] >= 0) & (i[:, -1] < 0)).sum()) > 0
    # every tag named is a lookup of one form, but the one past the
    # vocabulary, which is neither
    assert told["bitmap_lookups"] + told["list_lookups"] == int(
        (ft >= 0).sum()) - 1
    # without a filter the same placement answers as it always did
    d0, i0, stats0 = placed.search_certified(q, selector="pallas")
    assert stats0["filter"] == {"filter": "none"} and (i0 >= 0).all()
    want_i, _ = reference_filter.oracle_topk(
        db, indptr, tags, q, np.full_like(ft, -1), K)
    np.testing.assert_array_equal(i0, want_i)


# --- built cases --------------------------------------------------------------
ROWS = 5 * 4096 + 777  # off every multiple of 4,096 and of the tile
#: tag -> what it is for
EMPTY, ONE, NINE, TEN, FAR, TIES, LEFT, RIGHT, UNDER, OVER = range(10)


@pytest.fixture(scope="module")
def built():
    """One corpus whose tags are placed by hand, answered once on one
    device and once on four."""
    rng = np.random.default_rng(4040)
    db = rng.integers(40, 216, size=(ROWS, DIM)).astype(np.float32)
    q = np.full((1, DIM), 128, np.float32)
    bags = [set() for _ in range(ROWS)]
    order = np.argsort(((db - q) ** 2).sum(1), kind="stable")
    for r in order[:1]:
        bags[r].add(ONE)
    for r in rng.choice(ROWS, 9, replace=False):
        bags[r].add(NINE)
    for r in rng.choice(ROWS, 10, replace=False):
        bags[r].add(TEN)
    # FAR: held by the 1,001st nearest row alone
    bags[order[1000]].add(FAR)
    # TIES: twelve copies of one row, every other one tagged: the answer
    # is the tagged copies in index order, then the next tagged rows
    copies = np.sort(rng.choice(ROWS, 12, replace=False))
    db[copies] = db[copies[0]]
    for r in copies[::2]:
        bags[r].add(TIES)
    for r in rng.choice(ROWS, 6, replace=False):
        bags[r].add(TIES)
    # LEFT and RIGHT: 40 rows each, 5 shared
    left = rng.choice(ROWS, 40, replace=False)
    right = np.concatenate([left[:5], rng.choice(
        np.setdiff1d(np.arange(ROWS), left), 35, replace=False)])
    for r in left:
        bags[r].add(LEFT)
    for r in right:
        bags[r].add(RIGHT)
    # UNDER and OVER: one row under the bitmap rule, and exactly at it,
    # on the one-device placement (21 tiles of 1,024 rows: 5 rows)
    rule = tagfilter.bitmap_min_rows(-(-ROWS // TILE) * TILE)
    assert rule == 5
    for r in np.concatenate([left[10:12], right[10:12]]):  # rule - 1 rows
        bags[r].add(UNDER)
    for r in rng.choice(ROWS, rule, replace=False):
        bags[r].add(OVER)
    indptr, tags = csr([sorted(b) for b in bags])
    cases = {
        "no valid row": [EMPTY, -1],
        "a tag past the vocabulary": [10_000, -1],
        "one valid row": [ONE, -1],
        "nine valid rows": [NINE, -1],
        "exactly k valid rows": [TEN, -1],
        "a valid row behind 1,000 nearer invalid ones": [FAR, -1],
        "equal distances across the validity boundary": [TIES, -1],
        "two tags (AND)": [LEFT, RIGHT],
        "a list under the rule": [UNDER, -1],
        "a bitmap at the rule": [OVER, -1],
        "a listed and a mapped tag together": [UNDER, LEFT],
    }
    ft = np.asarray(list(cases.values()), np.int32)
    qs = np.repeat(q, len(cases), axis=0)
    qs[list(cases).index("equal distances across the validity boundary")] \
        = db[copies[0]]
    out = {}
    for shards in (1, 4):
        placed = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=1024,
                            row_tags=(indptr, tags))
        out[shards] = placed.search_certified(qs, selector="pallas",
                                              filter_tags=ft, tile_n=TILE)
        out[shards, "index"] = placed._tag_index_cache
    want = reference_filter.oracle_topk(db, indptr, tags, qs, ft, K)
    return dict(cases=cases, ft=ft, q=qs, db=db, indptr=indptr, tags=tags,
                out=out, want=want, copies=copies, left=left, right=right,
                order=order, rule=rule)


CASES = ["no valid row", "a tag past the vocabulary", "one valid row",
         "nine valid rows", "exactly k valid rows",
         "a valid row behind 1,000 nearer invalid ones",
         "equal distances across the validity boundary", "two tags (AND)",
         "a list under the rule", "a bitmap at the rule",
         "a listed and a mapped tag together"]


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("case", CASES)
def test_a_built_case(built, case, shards):
    at = list(built["cases"]).index(case)
    d, i, _ = built["out"][shards]
    want_i, want_d = built["want"]
    np.testing.assert_array_equal(i[at], want_i[at])
    np.testing.assert_array_equal(d[at], want_d[at])
    found = int((want_i[at] >= 0).sum())
    assert found == {
        "no valid row": 0, "a tag past the vocabulary": 0,
        "one valid row": 1, "nine valid rows": 9,
        "exactly k valid rows": 10,
        "a valid row behind 1,000 nearer invalid ones": 1,
        "two tags (AND)": 5, "a list under the rule": built["rule"] - 1,
        "a bitmap at the rule": built["rule"],
        "a listed and a mapped tag together": 2}.get(case, found)
    assert (i[at][found:] == -1).all() and np.isinf(d[at][found:]).all()
    if case == "a valid row behind 1,000 nearer invalid ones":
        assert i[at][0] == built["order"][1000]
    if case == "equal distances across the validity boundary":
        tagged = built["copies"][::2]
        np.testing.assert_array_equal(i[at][:6], tagged)
        assert (d[at][:6] == 0).all()
    if case == "two tags (AND)":
        # OR would have given 75 rows and a full answer
        assert set(i[at][:5]) == set(built["left"][:5])
        assert np.union1d(built["left"], built["right"]).size == 75


def test_the_rule_puts_the_built_tags_on_both_sides(built):
    """On one device (the rule reads the padded shard rows) ``UNDER`` is
    a list and ``OVER`` a bitmap; the stats count each lookup by its
    form, and the ids the listed ones name."""
    index = built["out"][1, "index"]
    assert index["slots"][UNDER] < 0 <= index["slots"][OVER]
    assert index["slots"][LEFT] >= 0
    told = built["out"][1][2]["filter"]
    want = tagfilter.lookup_forms(index["slots"], index["counts"],
                                  built["ft"])
    assert {k: told[k] for k in want} == want
    assert told["list_ids"] >= 2 * (built["rule"] - 1)
    assert told["empty"] == 2 and told["short"] >= 5


# --- the fallbacks ------------------------------------------------------------
@pytest.fixture
def every_query_flagged(monkeypatch):
    """The certified pass answers, and then says it proved nothing: the
    repair has to reproduce every answer."""
    real = ShardedKNN._certify_pallas

    def flag_all(self, batches, bs, d, i, q_np, *a, **kw):
        _, n_corrected, by_slack = real(self, batches, bs, d, i, q_np, *a,
                                        **kw)
        return np.arange(q_np.shape[0]), n_corrected, by_slack

    monkeypatch.setattr(ShardedKNN, "_certify_pallas", flag_all)


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("path", ["reselect", "host_scan"])
def test_a_fallback_is_held_to_the_same_rows(every_query_flagged,
                                             monkeypatch, shards, path):
    rng = np.random.default_rng(7 + shards)
    rows = 3001
    db = rng.integers(0, 256, size=(rows, DIM)).astype(np.float32)
    q = rng.integers(0, 256, size=(70, DIM)).astype(np.float32)
    indptr, tags = random_bags(rng, rows, 600, 6)
    ft = rng.integers(0, 40, size=(70, 2)).astype(np.int32)
    ft[::3, 1] = -1
    ft[1] = [599, 598]  # (most likely) no row
    if path == "host_scan":
        # a tolerance no gap clears: whatever the widened selection did
        # not exhaust goes to the host's exact scan of the valid rows
        monkeypatch.setattr(
            certified, "certification_tolerance",
            lambda q_np, db_np, **kw: np.full(q_np.shape[0], 1e30))
    placed = ShardedKNN(db, mesh=mesh(shards), k=K, train_tile=1024,
                        row_tags=(indptr, tags))
    _, _, stats = holds_the_contract(placed, db, q, ft, indptr, tags)
    assert stats["fallback_queries"] == 70
    scanned = stats.get("host_exact_queries", 0)
    if path == "host_scan":
        # every query with more valid rows than the widened width
        widen = max(2 * 38, 38 + 64)
        many = sum(reference_filter.valid_rows(indptr, tags, t).size
                   > widen for t in ft)
        assert scanned == many > 0
    else:
        assert scanned == 0


# --- the words ----------------------------------------------------------------
@pytest.mark.parametrize("tile", [1024, 3072, 4096, 16384])
def test_the_words_layout(tile):
    """Bit ``g % 32`` of word ``(g // 32) * 128 + lane`` of a tile is its
    row ``g * 128 + lane``; wherever the tile is a multiple of 4,096 the
    word of a row does not depend on the tile."""
    rows = np.arange(2 * tile + 300)
    col, bit = pk.valid_word_position(rows, tile)
    t, r = rows // tile, rows % tile
    g, lane = r // 128, r % 128
    np.testing.assert_array_equal(
        col, t * pk.valid_words_per_tile(tile) + (g // 32) * 128 + lane)
    np.testing.assert_array_equal(bit, g % 32)
    if tile % 4096 == 0:
        big = rows // 128
        np.testing.assert_array_equal(col, (big // 32) * 128 + lane)
        np.testing.assert_array_equal(bit, big % 32)
    rng = np.random.default_rng(tile)
    valid = rng.random((5, rows.size)) < 0.3
    words = pk.pack_valid_words(valid, tile)
    back = np.asarray(tagfilter.words_to_valid(
        jnp.asarray(words), tile_n=tile, n_rows=rows.size))
    np.testing.assert_array_equal(back, valid)


@pytest.mark.parametrize("shards,tile", [(1, 1024), (1, 4096), (4, 1024)])
def test_the_mask_program_equals_a_numpy_packing(shards, tile):
    rng = np.random.default_rng(3)
    rows, vocabulary = 5000, 4000
    indptr, tags = random_bags(rng, rows, vocabulary, 6)
    inv = tagfilter.invert_bags(indptr, tags)
    shard_rows = -(-rows // shards)
    arrays = tagfilter.place_arrays(*inv, n_train=rows, shards=shards,
                                    shard_rows=shard_rows, tile_n=tile)
    if (shards, tile) == (1, 4096):  # 8,192 padded rows: lists of one id
        assert 0 < arrays["bitmap_tags"] < arrays["tags"]
        assert arrays["list_ids"] > 0
    ft = rng.integers(0, vocabulary, size=(24, 2)).astype(np.int32)
    ft[0], ft[1, 1], ft[2], ft[3, 0] = [-1, -1], -1, [vocabulary + 5, -1], -1
    valid = np.stack([np.isin(np.arange(rows), reference_filter.valid_rows(
        indptr, tags, t)) for t in ft])
    for pos, t in enumerate(ft):
        np.testing.assert_array_equal(
            tagfilter.valid_rows(*inv, rows, *t), np.flatnonzero(valid[pos]))
    for s in range(shards):
        words = tagfilter.mask_words(
            jnp.asarray(ft), jnp.asarray(arrays["slots"]),
            *(jnp.asarray(arrays[key][s])
              for key in ("bitmaps", "list_ptr", "list_rows")),
            tile_n=tile, list_cap=arrays["list_cap"], interpret=True)
        mine = np.zeros((24, shard_rows), bool)
        part = valid[:, s * shard_rows:(s + 1) * shard_rows]
        mine[:, :part.shape[1]] = part
        want = pk.pack_valid_words(mine, tile)
        got = np.asarray(words).view(np.uint32)
        # the bitmaps' word rows are padded to whole 8s: zeros past the
        # layout's own words
        np.testing.assert_array_equal(got[:, :want.shape[1]], want)
        assert not got[:, want.shape[1]:].any()


def test_the_kernel_never_emits_a_masked_row():
    rng = np.random.default_rng(9)
    n, tile = 5000, 4096
    db = rng.integers(0, 256, size=(n, 40)).astype(np.float32)
    q = rng.integers(0, 256, size=(16, 40)).astype(np.float32)
    valid = rng.random((16, n)) < 0.3
    valid[0] = False
    valid[1] = False
    valid[1, 7] = True
    d32, idx, lb = map(np.asarray, pk.local_certified_candidates(
        jnp.asarray(q), jnp.asarray(db), 12, tile_n=tile, block_q=8,
        valid_words=jnp.asarray(pk.pack_valid_words(valid, tile)),
        interpret=True))
    for qi in range(16):
        got = idx[qi][idx[qi] < n]
        assert valid[qi][got].all()
    assert (idx[0] >= n).all() and np.isinf(d32[0]).all() and np.isinf(lb[0])
    assert idx[1][0] == 7 and (idx[1][1:] >= n).all() and np.isinf(lb[1])
    with pytest.raises(ValueError, match="validity words"):
        pk._bin_candidates(
            jnp.asarray(q), jnp.asarray(db), block_q=8, tile_n=tile,
            survivors=None, precision="bf16x3", interpret=True,
            kernel="streaming",
            valid_words=jnp.asarray(pk.pack_valid_words(valid, tile)))


# --- no filter: the parent's program ------------------------------------------
with open(os.path.join(HERE, "fixtures",
                       "unfiltered_program_digests.json")) as _f:
    PARENT_DIGESTS = json.load(_f)


@pytest.mark.parametrize("cell", sorted(program_digest.CELLS))
def test_without_a_filter_the_program_is_the_parents(cell):
    """The jaxpr of the certified program at each accepted cell's shape,
    the kernel's body included, against the digest recorded on the tree
    before per-query validity: no cell can have moved."""
    assert set(PARENT_DIGESTS) == set(program_digest.CELLS)
    text = program_digest.jaxpr_text(cell)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DIGESTS[cell]


def test_a_masked_program_is_another_program():
    m = mesh(1)
    plain = sh._pallas_certified_program(m, 38, K, "ring", TILE, "bf16x3",
                                         n_train=3000, interpret=True)
    masked = sh._pallas_certified_program(m, 38, K, "ring", TILE, "bf16x3",
                                          n_train=3000, interpret=True,
                                          masked=True)
    assert plain is not masked
    assert plain is sh._pallas_certified_program(
        m, 38, K, "ring", TILE, "bf16x3", n_train=3000, interpret=True)


# --- counters, the account, the event -----------------------------------------
def counter(name, **labels):
    want = {k: str(v) for k, v in labels.items()}
    for s in obs.snapshot().get(name, {"series": []})["series"]:
        if s["labels"] == want:
            return s["value"]
    return None


def test_counters_the_account_and_the_placement_event(tmp_path):
    rng = np.random.default_rng(11)
    rows = 3001
    db = rng.integers(0, 256, size=(rows, DIM)).astype(np.float32)
    q = rng.integers(0, 256, size=(32, DIM)).astype(np.float32)
    indptr, tags = random_bags(rng, rows, 2500, 6)
    ft = rng.integers(0, 2500, size=(32, 2)).astype(np.int32)
    ft[::2, 1] = -1
    log = tmp_path / "obs.jsonl"
    obs.reset(enabled=True)
    obs.reset_event_log(str(log))
    before = {o: counter(mn.FILTER_QUERIES, outcome=o) or 0
              for o in ("full", "short", "empty")}
    ids_before = counter(mn.FILTER_LIST_IDS) or 0
    launches = counter(mn.PROGRAM_LAUNCHES, program="filter_mask") or 0
    placed = ShardedKNN(db, mesh=mesh(1), k=K, train_tile=1024,
                        row_tags=(indptr, tags))
    _, i, stats = placed.search_certified(q, selector="pallas",
                                          filter_tags=ft)
    _, _, again = placed.search_certified(q, selector="pallas",
                                          filter_tags=ft)
    told = stats["filter"]
    assert again["filter"] == told
    found = (i >= 0).sum(axis=1)
    for outcome, n in (("full", int((found == K).sum())),
                       ("short", told["short"]), ("empty", told["empty"])):
        assert counter(mn.FILTER_QUERIES, outcome=outcome) \
            == before[outcome] + 2 * n
    assert counter(mn.FILTER_LIST_IDS) == ids_before + 2 * told["list_ids"]
    # one launch a batch (no fallback here), counted when a call closes
    assert counter(mn.PROGRAM_LAUNCHES, program="filter_mask") \
        == launches + 2 + stats["fallback_queries"] \
        + again["fallback_queries"]
    obs.reset_event_log(None)
    obs.reset()
    events = [json.loads(ln) for ln in log.read_text().splitlines()]
    names = [e.get("span") or e.get("name") for e in events]
    assert names.count("placement.tag_index") == 1  # built once
    (placed_event,) = [e for e in events
                       if e.get("name") == "placement.tag_index"]
    for key in ("tags", "pairs", "bitmap_tags", "bytes", "seconds"):
        assert key in placed_event
    assert placed_event["pairs"] == tags.size
    assert names.count("certified.inflight.filter_mask") == 2
    assert names.count("certified.filter_mask") >= 2
    assert "program.first_call.filter_mask" in names
    calls = [e for e in events if e.get("span") == "certified.call"]
    assert [c["filter"] for c in calls] == ["tags", "tags"]
    assert calls[0]["list_ids"] == told["list_ids"]


# --- what refuses a filter ----------------------------------------------------
def test_what_cannot_take_a_filter_says_so():
    rng = np.random.default_rng(5)
    db = rng.integers(0, 256, size=(2000, DIM)).astype(np.float32)
    q = db[:4]
    indptr, tags = random_bags(rng, 2000, 50, 3)
    ft = np.zeros((4, 2), np.int32)
    bare = ShardedKNN(db, mesh=mesh(1), k=K, train_tile=1024)
    with pytest.raises(ValueError, match="row_tags"):
        bare.search_certified(q, selector="pallas", filter_tags=ft)
    placed = ShardedKNN(db, mesh=mesh(1), k=K, train_tile=1024,
                        row_tags=(indptr, tags))
    for selector in ("approx", "exact"):
        with pytest.raises(ValueError, match="selector='pallas'"):
            placed.search_certified(q, selector=selector, filter_tags=ft)
    for kernel in ("streaming", "fused"):
        with pytest.raises(ValueError, match="kernel='tiled'"):
            placed.search_certified(q, selector="pallas", filter_tags=ft,
                                    kernel=kernel)
    with pytest.raises(ValueError, match="shape"):
        placed.search_certified(q, selector="pallas",
                                filter_tags=np.zeros((3, 2), np.int32))
    with pytest.raises(ValueError, match="indptr"):
        ShardedKNN(db, mesh=mesh(1), k=K, row_tags=(indptr[:-1], tags))
    dot = ShardedKNN(db, mesh=mesh(1), k=K, metric="dot",
                     row_tags=(indptr, tags))
    with pytest.raises(ValueError, match="l2 placement"):
        dot.search_certified(q, selector="pallas", filter_tags=ft)
