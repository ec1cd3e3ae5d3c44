"""The rows placed in whole 128-column lane tiles (PR 44): where the
rows a ``ShardedKNN`` is given are no whole number of lane tiles wide,
the placed array is the rows with zero columns after them up to the next
multiple of 128, written once on the device (``_lane_tile_program``);
every batch is widened to match where it is placed, the host's copies
keep the width given, and no answer moves.  On the CPU, the kernel
interpreted, at sizes a test can hold:

- 192 columns (``yfcc2m5``'s), 200 under inner product (201 with the
  augmentation column: ``text2image2m5``'s), 960 (``gist1m``'s), and 70
  under cosine, on one device and db-sharded over four: the placed
  width, the zero columns, what ``_host_train`` / ``_placed_host`` hand
  back, the placement's event; ``search``, ``search_certified`` under
  every selector, ``range_search_certified``, a filtered call and
  ``predict`` against float64 oracles;
- 128, 256 and 1,536 columns: the placement is the parent's, no program
  runs;
- a pre-placed array stays as handed in; a device batch is widened as a
  host batch is; the quantized certificate reads the width given;
- the program itself where the rows are no whole number of its blocks.

The compiled form (no ``copy`` of the placed rows in the certified
program or the re-select, for a described v5e) is two cases of
tests/test_text2image.py, the one file that holds the described chip;
the sub-batch rule's reading of these placements is
tests/test_sub_batch.py's.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "benchmark"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import oracles  # noqa: E402  (tests/)
import reference_filter  # noqa: E402  (benchmark/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.analysis.widths import lane_tiled  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402
from knn_tpu.parallel.collectives import shard  # noqa: E402
from knn_tpu.parallel.mesh import db_axes  # noqa: E402

K, N_Q, TILE, CLASSES = 10, 12, 512, 5
#: label -> (metric, the caller's columns, columns placed-given, placed)
SHAPES = {
    "l2-192": ("l2", 192, 192, 256),
    "dot-200": ("dot", 200, 201, 256),
    "l2-960": ("l2", 960, 960, 1024),
    "cosine-70": ("cosine", 70, 70, 128),
}
L2 = [s for s in SHAPES if s.startswith("l2")]


def mesh(shards: int):
    return make_mesh(1, shards, devices=jax.devices()[:shards])


def test_the_rule_of_the_width():
    assert [lane_tiled(w) for w in (1, 127, 128, 129, 192, 201, 256, 960,
                                    1024, 1536)] == [
        128, 128, 128, 256, 256, 256, 256, 1024, 1024, 1536]


def _cases(shapes):
    return dict(params=[(shape, shards) for shape in shapes
                        for shards in (1, 4)],
                ids=lambda p: f"{p[0]}-1x{p[1]}")


@pytest.fixture(scope="module", **_cases(SHAPES))
def placed(request):
    return build(*request.param)


@pytest.fixture(scope="module", **_cases(L2))
def placed_l2(request):
    """The l2 placements alone: range search and ``filter_tags`` are the
    l2 family's."""
    return build(*request.param)


@functools.lru_cache(maxsize=None)
def build(shape, shards):
    """One placement a (shape, mesh), with its data, its labels, its tag
    bags (l2 only) and the float64 order of every row for every query."""
    metric, dim, _, _ = SHAPES[shape]
    rng = np.random.default_rng(44 + dim + shards)
    n = 1201 if shards == 1 else 1303  # 1,303: pad rows on four shards
    db = rng.normal(size=(n, dim)).astype(np.float32)
    db *= rng.lognormal(0.0, 0.3, size=(n, 1)).astype(np.float32)
    q = rng.normal(size=(N_Q, dim)).astype(np.float32)
    labels = rng.integers(0, CLASSES, size=n).astype(np.int32)
    bags = [np.unique(rng.integers(0, 12, size=rng.integers(1, 4)))
            for _ in range(n)]
    indptr = np.concatenate([[0], np.cumsum([len(b) for b in bags])]
                            ).astype(np.int64)
    tags = np.concatenate(bags).astype(np.int32)
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    prog = ShardedKNN(
        db, mesh=mesh(shards), k=K, metric=metric, train_tile=TILE,
        labels=labels, num_classes=CLASSES,
        **({"row_tags": (indptr, tags)} if metric == "l2" else {}))
    events = [e for e in obs.get_event_log().recent()
              if e.get("name") == "placement.device_put"]
    q64, t64 = q.astype(np.float64), db.astype(np.float64)
    if metric == "l2":
        scores = oracles.sq_l2(q, db)
    elif metric == "dot":
        scores = -(q64 @ t64.T)
    else:
        scores = 1.0 - (q64 @ t64.T) / (
            np.linalg.norm(q64, axis=1)[:, None]
            * np.linalg.norm(t64, axis=1)[None])
    want_d, want_i = oracles.topk_lowindex(scores, K)
    return dict(shape=shape, shards=shards, prog=prog, db=db, q=q,
                labels=labels, bags=(indptr, tags), scores=scores,
                want_d=want_d, want_i=want_i, events=events)


def test_the_rows_lie_in_whole_lane_tiles(placed):
    metric, dim, given, width = SHAPES[placed["shape"]]
    prog, db = placed["prog"], placed["db"]
    assert (prog.dim_in, prog._placed_width) == (dim, width)
    rows = -(-db.shape[0] // placed["shards"]) * placed["shards"]
    assert prog._tp.shape == (rows, width)
    on_device = np.asarray(prog._tp)
    assert not on_device[:, given:].any()
    if metric == "l2":
        np.testing.assert_array_equal(on_device[:db.shape[0], :dim], db)
    # the host's copies keep the width they were given at
    host, as_placed = prog._host_train(), prog._placed_host()
    assert host.shape == (db.shape[0], given)
    assert as_placed.shape == (db.shape[0], given)
    assert as_placed.flags["C_CONTIGUOUS"]
    np.testing.assert_array_equal(as_placed,
                                  on_device[:db.shape[0], :given])
    (event,) = placed["events"]
    assert (event["width"], event["placed_width"]) == (given, width)
    assert event["rows"] == rows and event["bytes"] == rows * given * 4


def test_search_equals_the_oracle(placed):
    d, i = placed["prog"].search(placed["q"])
    np.testing.assert_array_equal(np.asarray(i), placed["want_i"])
    np.testing.assert_allclose(np.asarray(d), placed["want_d"],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("selector", sh.SELECTORS)
def test_search_certified_equals_the_oracle(placed, selector):
    d, i, stats = placed["prog"].search_certified(
        placed["q"], selector=selector, tile_n=TILE)
    np.testing.assert_array_equal(i, placed["want_i"])
    # float64 scores of the rows as given (a dot placement's: -q.t)
    np.testing.assert_allclose(d, placed["want_d"], rtol=2.0 ** -18,
                               atol=2.0 ** -20)
    assert stats["certified"] + stats["fallback_queries"] == N_Q
    if selector == "pallas":
        assert (stats["sub_batch"], stats["operands"]) == (
            "small", "resident")


def test_predict_equals_the_oracle(placed):
    got = np.asarray(placed["prog"].predict(placed["q"]))
    want = oracles.running_argmax_vote(
        placed["labels"][placed["want_i"]], CLASSES)
    np.testing.assert_array_equal(got, want)


def test_range_search_equals_the_oracle(placed_l2):
    placed = placed_l2
    scores = placed["scores"]
    # a radius that 0 to about 40 rows a query lie within: under and
    # over k, so both the first pass alone and the completion answer
    radius_sq = float(np.sort(scores, axis=1)[:, 20].mean())
    lims, idx, dist, stats = placed["prog"].range_search_certified(
        placed["q"], radius_sq=radius_sq)
    sizes = np.diff(lims)
    assert sizes.min() < K < sizes.max()
    for r in range(N_Q):
        inside = np.flatnonzero(scores[r] <= radius_sq)
        inside = inside[np.lexsort((inside, scores[r][inside]))]
        np.testing.assert_array_equal(idx[lims[r]:lims[r + 1]], inside)
        np.testing.assert_allclose(dist[lims[r]:lims[r + 1]],
                                   scores[r][inside], rtol=2.0 ** -40)
    assert stats["range"]["truncated"] >= 1


def test_a_filtered_call_equals_the_oracle(placed_l2):
    placed = placed_l2
    indptr, tags = placed["bags"]
    rng = np.random.default_rng(7)
    ft = np.stack([rng.integers(0, 12, size=N_Q),
                   np.where(rng.random(N_Q) < 0.5,
                            rng.integers(0, 12, size=N_Q), -1)],
                  axis=1).astype(np.int32)
    d, i, stats = placed["prog"].search_certified(
        placed["q"], selector="pallas", filter_tags=ft, tile_n=TILE)
    want_i, want_d = reference_filter.oracle_topk(
        placed["db"], indptr, tags, placed["q"], ft, K)
    np.testing.assert_array_equal(i, want_i)
    fin = np.isfinite(want_d)
    np.testing.assert_array_equal(np.isfinite(d), fin)
    np.testing.assert_allclose(d[fin], want_d[fin], rtol=2.0 ** -18)
    assert stats["filter"]["filter"] == "tags"


# --- whole widths, pre-placed arrays, device batches ---------------------------
@pytest.mark.parametrize("dim", [128, 256, 1536])
def test_a_whole_width_is_placed_as_it_is(monkeypatch, dim):
    """No program more, no copy, the same array: the parent's
    placement."""
    def no_program(*a, **kw):
        raise AssertionError("a whole width needs no lane-tile program")

    monkeypatch.setattr(sh, "_lane_tile_program", no_program)
    rng = np.random.default_rng(dim)
    db = rng.normal(size=(300, dim)).astype(np.float32)
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    prog = ShardedKNN(db, mesh=mesh(1), k=K)
    assert prog._placed_width == dim == prog.dim_in
    np.testing.assert_array_equal(np.asarray(prog._tp), db)
    assert prog._host_train() is db
    (event,) = [e for e in obs.get_event_log().recent()
                if e.get("name") == "placement.device_put"]
    assert event["width"] == event["placed_width"] == dim
    _, i = prog.search(db[:5])
    np.testing.assert_array_equal(np.asarray(i)[:, 0], np.arange(5))


def test_a_pre_placed_array_stays_as_handed_in(monkeypatch):
    monkeypatch.setattr(sh, "_lane_tile_program", None)  # never asked
    rng = np.random.default_rng(5)
    db = rng.normal(size=(400, 201)).astype(np.float32)
    q = rng.normal(size=(7, 201)).astype(np.float32)
    m = mesh(4)
    handed = shard(db, m, db_axes(m))
    prog = ShardedKNN(handed, mesh=m, k=K)
    assert prog._tp is handed and prog._placed_width == 201
    assert prog._host_train().shape == (400, 201)
    _, want = oracles.topk_lowindex(oracles.sq_l2(q, db), K)
    np.testing.assert_array_equal(np.asarray(prog.search(q)[1]), want)
    _, i, _ = prog.search_certified(q, selector="pallas", tile_n=TILE)
    np.testing.assert_array_equal(i, want)


@pytest.mark.parametrize("metric,dim", [("l2", 192), ("dot", 200)])
def test_a_device_batch_is_widened_as_a_host_batch_is(metric, dim):
    rng = np.random.default_rng(9)
    db = rng.normal(size=(500, dim)).astype(np.float32)
    q = rng.normal(size=(6, dim)).astype(np.float32)
    prog = ShardedKNN(db, mesh=mesh(4), k=K, metric=metric)
    host, n_host = prog._place_queries(q)
    dev, n_dev = prog._place_queries(jnp.asarray(q))
    assert host.shape == dev.shape == (6, 256) and n_host == n_dev == 6
    np.testing.assert_array_equal(np.asarray(host), np.asarray(dev))
    assert not np.asarray(host)[:, dim:].any()
    d_h, i_h = prog.search(q)
    d_d, i_d = prog.search(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(i_h), np.asarray(i_d))
    np.testing.assert_array_equal(np.asarray(d_h), np.asarray(d_d))
    # a threshold vector (one number a query) is no batch of rows
    thr, _ = prog._place_queries(np.ones(6, np.float32))
    assert thr.shape == (6,)


@pytest.mark.parametrize("shards", [1, 4])
def test_the_quantized_certificate_reads_the_width_given(shards):
    """Byte rows ride the int8 kernel in a space shifted by 128; a
    placed batch's lane-tile columns are 0 there, not 128, so the
    certificate takes its query norm over the columns the quantized rows
    have.  Read over all 256 it would come out 64 x 128^2 too large and
    certify a query whose nearest rows overflow one kernel bin."""
    rng = np.random.default_rng(11)
    rows = 900 * shards
    db = rng.integers(0, 256, size=(rows, 192)).astype(np.uint8)
    q = rng.integers(0, 256, size=(24, 192)).astype(np.float32)
    # four rows of one shard's first tile on one lane (a bin keeps two),
    # each nearer query 1 than any other row: the bin's bound is under
    # the query's k-th score, which only an exact re-select repairs
    near = rng.integers(1, 200, size=192).astype(np.uint8)
    for j, row in enumerate((5, 5 + 128, 5 + 256, 5 + 384)):
        db[row] = near
        db[row, j] += 1 + j
    q[1] = near
    prog = ShardedKNN(db, mesh=mesh(shards), k=K)
    assert prog._uint8_train is db and prog._tp.shape[1] == 256
    _, want = oracles.topk_lowindex(oracles.sq_l2(q, db), K)
    assert want[1, :4].tolist() == [5, 133, 261, 389]
    _, i, stats = prog.search_certified(q, selector="pallas",
                                        precision="int8", tile_n=TILE)
    np.testing.assert_array_equal(i, want)
    assert prog._int8_cache["values"].shape[1] == 192
    assert stats["fallback_queries"] >= 1
    # the program's verdicts at the width given (a pre-placed array of
    # the same rows, the same quantized placement): the same counts
    ref = ShardedKNN(shard(db.astype(np.float32), prog.mesh,
                           db_axes(prog.mesh)), mesh=prog.mesh, k=K)
    assert ref._tp.shape[1] == 192
    ref._int8_cache = prog._int8_cache
    _, i_ref, stats_ref = ref.search_certified(
        q, selector="pallas", precision="int8", tile_n=TILE)
    np.testing.assert_array_equal(i_ref, want)
    for key in ("fallback_queries", "certified", "rank_corrected_queries"):
        assert stats_ref[key] == stats[key], key


# --- the program ----------------------------------------------------------------
@pytest.mark.parametrize("n,block", [(10, 4), (12, 4), (3, 4), (7, 7)])
def test_the_program_writes_the_rows_a_block_at_a_time(monkeypatch, n,
                                                        block):
    monkeypatch.setattr(sh, "_LANE_TILE_BLOCK_ROWS", block)
    rows = np.arange(n * 5, dtype=np.float32).reshape(n, 5) + 1
    out = np.asarray(jax.jit(
        lambda x: sh._lane_tiled_rows(x, 128))(rows))
    assert out.shape == (n, 128)
    np.testing.assert_array_equal(out[:, :5], rows)
    assert not out[:, 5:].any()


def test_the_program_is_one_a_mesh_and_width():
    m = mesh(4)
    assert sh._lane_tile_program(m, 256) is sh._lane_tile_program(m, 256)
    assert sh._lane_tile_program(m, 256) is not sh._lane_tile_program(
        m, 1024)
    rows = np.random.default_rng(2).normal(size=(40, 130)).astype(
        np.float32)
    out = sh._lane_tile_program(m, 256)(shard(rows, m, db_axes(m)))
    assert out.sharding.is_equivalent_to(
        shard(rows, m, db_axes(m)).sharding, 2)
    np.testing.assert_array_equal(np.asarray(out)[:, :130], rows)
    assert not np.asarray(out)[:, 130:].any()
