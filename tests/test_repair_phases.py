"""The host time under three spans that had no children, timed where the
work happens (docs/OBSERVABILITY.md "The certified call"):

- ``ops.certified.repair_uncertified`` records ``certified.repair.refine``
  and ``certified.repair.host_scan`` once where ``certified.repair`` is
  recorded once (a call; a block of the bulk self-join), children of it,
  and ``knn_tpu_repair_queries_total{outcome}``;
- ``ShardedKNN._range_complete`` sums five phases over the completion's
  sub-batches through the call's account, one record a call each,
  children of ``certified.range_complete``;
- both sides of ``certified.metric_map`` are series and annotations of
  their own beside the sum.

And what the refine gathers since PR 54: of the widened re-select's
candidates only those whose float32 score can still reach the top-k
(``ops.certified._within_reach``), the answers the arrays they were.

CPU, Pallas interpreted, tiny corpora: what is checked is which records
exist, whose children they are and that they close on their parents,
never how long one took.
"""

import os
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from test_certified_spans import _CountingAnnotation  # noqa: E402  (tests/)
from test_yfcc_filter import csr, every_query_flagged  # noqa: E402,F401  (tests/)

from knn_tpu import obs, tuning  # noqa: E402
from knn_tpu.join import engine, knn_self_join  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.obs import trace as obs_trace  # noqa: E402
from knn_tpu.ops import certified  # noqa: E402
from knn_tpu.ops.radius import RANGE_SUB_BATCH  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402

K = 10
REFINE, HOST_SCAN = certified.PHASE_REFINE, certified.PHASE_HOST_SCAN
RANGE_PHASES = tuple(f"certified.range_complete.{p}" for p in (
    "wait", "decode", "score", "host_scan", "order"))


@pytest.fixture(autouse=True)
def _fresh_registry():
    _fresh()
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def mesh(shards=1):
    return make_mesh(1, shards, devices=jax.devices()[:shards])


def _fresh():
    obs.reset(enabled=True)
    obs.reset_event_log(None)


def spans(name=None, prefix="certified."):
    return [e for e in obs.get_event_log().recent()
            if e.get("type") == "span" and (
                e["span"] == name if name else e["span"].startswith(prefix))]


def outcomes(name) -> dict:
    series = obs.snapshot().get(name, {"series": []})["series"]
    return {s["labels"]["outcome"]: s["value"] for s in series}


def repair_outcomes() -> dict:
    return outcomes(mn.REPAIR_QUERIES)


#: which of tied_rows' eight families a call's queries lie near: two of
#: either kind, or the four whose copies the widened re-select holds
#: whole (a gap after the 40th candidate: the refine has rows to leave)
MIXED, GAPPED = (0, 1, 4, 5), (4, 5, 6, 7)


def tied_rows(seed=6, dim=16, near=MIXED):
    """Eight rows, four of them repeated 128 times (more copies than the
    widened re-select holds: its own bound proves nothing and the host
    scans) and four 40 times (past the analysis window and inside the
    widened selection: flagged, then proven), and a query near each of
    the families ``near``."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(8, dim)).astype(np.float32)
    base *= rng.uniform(0.5, 2.0, size=(8, 1)).astype(np.float32)
    db = np.concatenate([np.repeat(base[:4], 128, axis=0),
                         np.repeat(base[4:], 40, axis=0)])
    q = base[list(near)] + np.float32(0.01)
    return db, q


def _search(metric, near=MIXED, shards=1):
    db, q = tied_rows(near=near)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric=metric)
    return lambda: prog.search_certified(q, selector="pallas")


def _filtered(near=MIXED):
    db, q = tied_rows(near=near)
    # every row holds tag 0, a row in three tag 1 too: a filter on tag 0
    # keeps every row valid
    prog = ShardedKNN(db, mesh=mesh(), k=K, row_tags=csr(
        [[0, 1] if r % 3 == 0 else [0] for r in range(db.shape[0])]))
    ft = np.array([[0, -1]] * q.shape[0], np.int32)
    return lambda: prog.search_certified(q, selector="pallas",
                                         filter_tags=ft)


def _self(monkeypatch, near=MIXED):
    # one block: the call's rows are fewer than a block
    monkeypatch.setitem(tuning.DEFAULT_KNOBS, "tile_n", 256)
    db, _ = tied_rows()
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    # rows of the last 128-copy family and the first 40-copy one, or of
    # the 40-copy one alone
    rows = (500, 532) if near == MIXED else (512, 544)
    return lambda: knn_self_join(prog, rows=rows)


#: every form ``repair_uncertified`` is called in: (monkeypatch, near)
#: -> a call that returns (distances, indices, stats)
FORMS = {
    "l2-plain": lambda mp, near: _search("l2", near),
    "dot-plain": lambda mp, near: _search("dot", near),
    "cosine-plain": lambda mp, near: _search("cosine", near),
    "l2-valid_rows_fn": lambda mp, near: _filtered(near),
    "l2-exclude": _self,
}


# --- the repair's host half ---------------------------------------------------
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_call_with_fallbacks_records_the_repairs_two_phases(form,
                                                              monkeypatch):
    call = FORMS[form](monkeypatch, MIXED)
    call()  # the placement's one-time passes and compiles
    _fresh()
    stats = call()[-1]
    scanned = stats.get("host_exact_queries", 0)
    assert 0 < scanned < stats["fallback_queries"], "both outcomes are taken"
    by = {}
    for name in (REFINE, HOST_SCAN):
        (by[name],) = spans(name)  # once where certified.repair is once
        assert by[name]["parent"] == "certified.repair"
        assert by[name]["dur_s"] > 0
    (repair,) = spans("certified.repair")
    owner = (repair if form != "l2-exclude" else spans("join.block")[0])
    assert {e["trace_id"] for e in by.values()} == {owner["trace_id"]}
    assert by[REFINE]["rows"] >= stats["fallback_queries"] * K
    # dense ties (more copies of one row than the re-select is wide):
    # every score is the k-th, nothing is past reach, and step 3 scans
    assert by[REFINE]["rows"] == by[REFINE]["selected"]
    assert outcomes(mn.REPAIR_REFINE_ROWS) == {
        "refined": by[REFINE]["rows"], "thinned": 0}
    # host_exact_queries stays on the repair's event and is the counter's
    assert (by[HOST_SCAN]["queries"] == scanned
            == repair["host_exact_queries"])
    assert repair_outcomes() == {
        "proven": stats["fallback_queries"] - scanned, "host_scan": scanned}
    # the phases lie inside the repair, beside the re-select
    reselect = sum(e["dur_s"] for e in spans("certified.repair.reselect"))
    assert (by[REFINE]["dur_s"] + by[HOST_SCAN]["dur_s"]
            <= repair["dur_s"] - reselect + 1e-4)
    series = {s["labels"]["span"]: s["value"]["count"]
              for s in obs.snapshot()[mn.SPAN_SECONDS]["series"]}
    assert (series[REFINE], series[HOST_SCAN]) == (1, 1)


def test_a_call_without_fallbacks_records_both_at_zero():
    rng = np.random.default_rng(5)
    db = rng.normal(size=(3000, 32)).astype(np.float32)
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    q = rng.normal(size=(48, 32)).astype(np.float32)
    _, _, stats = prog.search_certified(q, selector="pallas")
    assert stats["fallback_queries"] == 0
    for name in (REFINE, HOST_SCAN):
        (e,) = spans(name)
        assert (e["parent"], e["dur_s"]) == ("certified.repair", 0.0)
    # both outcomes exist from the placement's first call
    assert repair_outcomes() == {"proven": 0, "host_scan": 0}
    assert outcomes(mn.REPAIR_REFINE_ROWS) == {"refined": 0, "thinned": 0}


def test_the_self_join_records_the_repairs_phases_once_a_block(monkeypatch):
    monkeypatch.setitem(tuning.DEFAULT_KNOBS, "tile_n", 256)
    monkeypatch.setattr(engine, "DEFAULT_SUPERBLOCK_ROWS", 192)
    db, _ = tied_rows()
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    knn_self_join(prog, rows=(0, 192))
    _fresh()
    # three blocks: the first two hold rows of the 128-copy families
    # alone, the third of the 40-copy ones too
    _, _, stats = knn_self_join(prog, rows=(0, 3 * 192))
    blocks = spans("join.block", prefix="")
    assert len(blocks) == 3
    for name in (REFINE, HOST_SCAN, "certified.repair"):
        assert len(spans(name)) == len(blocks), name
    assert {e["parent"] for e in spans(REFINE) + spans(HOST_SCAN)} == {
        "certified.repair"}
    assert {e["trace_id"] for e in spans(REFINE)} == {
        blocks[0]["trace_id"]}
    assert sum(repair_outcomes().values()) == stats["fallback_queries"] > 0
    assert repair_outcomes()["host_scan"] == stats["host_exact_queries"]


# --- what the refine gathers ---------------------------------------------------
@pytest.mark.parametrize("form", sorted(FORMS) + ["l2-plain-x4"])
def test_the_thinned_refine_returns_the_whole_refines_arrays(form,
                                                             monkeypatch):
    """Queries near the 40-copy families: the re-select's first 40
    candidates tie and the 41st lies a real gap away, so the refine
    leaves the rest where they are, and the answers are bit for bit
    those of a refine over every candidate (the band at +inf)."""
    call = (_search("l2", GAPPED, shards=4) if form == "l2-plain-x4"
            else FORMS[form](monkeypatch, GAPPED))
    call()  # the placement's one-time passes and compiles
    _fresh()
    d, i, stats = call()
    (thin,) = spans(REFINE)
    assert stats["fallback_queries"] > 0
    assert not stats.get("host_exact_queries")  # step 2 proved them all
    assert (stats["fallback_queries"] * K <= thin["rows"]
            < thin["selected"])
    assert outcomes(mn.REPAIR_REFINE_ROWS) == {
        "refined": thin["rows"], "thinned": thin["selected"] - thin["rows"]}
    monkeypatch.setattr(certified, "_REACH_TOLS", np.inf)
    _fresh()
    d_all, i_all, stats_all = call()
    (whole,) = spans(REFINE)
    assert whole["rows"] == whole["selected"] == thin["selected"]
    assert outcomes(mn.REPAIR_REFINE_ROWS) == {
        "refined": whole["rows"], "thinned": 0}
    np.testing.assert_array_equal(i, i_all)
    np.testing.assert_array_equal(d, d_all)  # bitwise
    assert {k: stats[k] for k in stats if k.startswith("fallback")} == {
        k: stats_all[k] for k in stats_all if k.startswith("fallback")}


@pytest.mark.parametrize("metric, spread", [
    ("l2", 1.0), ("dot", 0.003), ("cosine", 1.0)])
def test_a_band_some_candidates_wide_changes_no_answer(
        metric, spread, every_query_flagged, monkeypatch):  # noqa: F811
    """Rows far from the origin: the tolerance spans a few neighbours'
    spacing (``spread``: what gives each metric's scores that spacing),
    so the kept width lies strictly between k and the re-select's
    (neither the first k alone nor everything), every query through the
    repair; bit for bit the whole refine's answer."""
    rng = np.random.default_rng(540)
    db = (rng.normal(size=(3000, 16)) * spread + 100.0).astype(np.float32)
    q = (rng.normal(size=(24, 16)) * spread + 100.0).astype(np.float32)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric=metric)
    d, i, stats = prog.search_certified(q, selector="pallas")
    assert stats["fallback_queries"] == 24
    (thin,) = spans(REFINE)
    assert 24 * (K + 1) <= thin["rows"] < thin["selected"] - 24
    monkeypatch.setattr(certified, "_REACH_TOLS", np.inf)
    d_all, i_all, _ = prog.search_certified(q, selector="pallas")
    np.testing.assert_array_equal(i, i_all)
    np.testing.assert_array_equal(d, d_all)  # bitwise
    if metric == "l2":
        np.testing.assert_array_equal(
            i, certified.host_exact_knn(db, q, K)[1])


def swapped_pair(seed=54, rows=400, dim=8, m=16):
    """A corpus whose k-th and (k + 1)-th neighbours of the one query
    lie a float32 ulp of one coordinate apart, and a re-select whose
    float32 scores (each within the certificate's tolerance of the exact
    value, as the kernel's are) order that pair the wrong way round: the
    true k-th neighbour comes back as the (k + 1)-th candidate."""
    rng = np.random.default_rng(seed)
    db = rng.normal(size=(rows, dim)).astype(np.float32)
    q = rng.normal(size=(1, dim)).astype(np.float32)
    exact = ((db.astype(np.float64) - q.astype(np.float64)) ** 2).sum(-1)
    order = np.argsort(exact, kind="stable")
    a, b = order[K - 1], order[K]
    db[b] = db[a]
    far = int(np.argmax(np.abs(db[a] - q[0])))
    db[b, far] = np.nextafter(db[a, far], np.float32(np.sign(
        db[a, far] - q[0, far]) * np.inf))  # one ulp further from q
    exact = ((db.astype(np.float64) - q.astype(np.float64)) ** 2).sum(-1)
    tol = certified.certification_tolerance(q, db)[0]
    assert 0 < exact[b] - exact[a] < 0.1 * tol
    scores = exact.copy()
    scores[a] += 0.4 * tol
    scores[b] -= 0.4 * tol

    def select_fn(qb, widen):
        idx = np.argsort(scores, kind="stable")[:widen]
        return scores[idx][None].astype(np.float32), idx[None]

    fs, fi = select_fn(q, certified.repair_widen(m, rows))
    assert (fi[0, K - 1], fi[0, K]) == (b, a)  # the wrong way round
    assert np.abs(fs[0] - exact[fi[0]]).max() <= tol  # the premise
    assert fs[0, K + 1] - fs[0, K] > 4 * tol  # and a real gap after them
    return db, q, m, select_fn


@pytest.mark.parametrize("tols, exact", [(2.0, True), (0.0, False)])
def test_a_true_neighbour_past_the_kth_score_is_still_refined(
        tols, exact, monkeypatch):
    """The band is the proof's: with it the pair is refined and the
    answer is the float64 scan's; with none the float32 order is
    trusted, the true k-th neighbour is never gathered, step 2 still
    proves the repair, and the answer is wrong."""
    db, q, m, select_fn = swapped_pair()
    monkeypatch.setattr(certified, "_REACH_TOLS", tols)
    d = np.zeros((1, K))
    i = np.zeros((1, K), np.int64)
    got = certified.repair_uncertified(
        d, i, K, m, np.arange(1), q, db, select_fn=select_fn,
        max_widen=db.shape[0])
    assert "host_exact_queries" not in got  # proven, either way
    want_d, want_i = certified.host_exact_knn(db, q, K)
    assert np.array_equal(i, want_i) == exact
    # (the scan sums a difference's squares in another order)
    np.testing.assert_allclose(d[:, : K - 1], want_d[:, : K - 1], rtol=1e-14)
    assert (abs(d[0, K - 1] - want_d[0, K - 1]) <= 1e-14) == exact
    (e,) = spans(REFINE)
    # the pair and nothing after it; or the first k alone
    assert (e["rows"], e["selected"]) == (
        K + 1 if exact else K, certified.repair_widen(m, db.shape[0]))


def test_a_zero_row_among_the_first_k_does_not_set_the_band():
    """Cosine: a row of zero norm is selected at D' = 1, half its cosine
    distance of 1.  Six rows nearer than that, the zero row, 24 rows at
    cosine 0.05 to 0.45 and the rest behind the query: by float32 score
    the zero row is the 7th candidate, in truth the 31st, and the
    widened selection's last score is over 2, so step 2 proves whatever
    step 1 refined.  The k-th score is taken among the candidates of
    nonzero norm, so the four rows that replace the zero row and the
    three before it are within reach."""
    rng = np.random.default_rng(54)
    n, dim = 400, 16
    axis = np.zeros(dim)
    axis[0] = 1.0

    def at(cos):
        side = rng.normal(size=(cos.size, dim))
        side[:, 0] = 0.0
        side /= np.linalg.norm(side, axis=1, keepdims=True)
        return ((cos[:, None] * axis + np.sqrt(1 - cos ** 2)[:, None] * side)
                * rng.uniform(0.5, 2.0, size=(cos.size, 1)))

    db = np.concatenate([
        at(rng.uniform(0.6, 0.9, 6)), np.zeros((1, dim)),
        at(rng.uniform(0.05, 0.45, 24)),
        at(rng.uniform(-0.9, -0.1, n - 31))]).astype(np.float32)
    db = db[rng.permutation(n)]
    q = (3.0 * axis)[None].astype(np.float32)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine")
    d, i, stats = prog.search_certified(q, selector="pallas")
    # a zero row among the candidates: flagged; proven without a scan
    assert stats["fallback_queries"] == 1
    assert "host_exact_queries" not in stats
    want_d, want_i = certified.host_exact_knn(db, q, K, metric="cosine")
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_allclose(d, want_d, rtol=1e-14)
    assert not (db[i[0]] == 0).all(axis=1).any()
    (e,) = spans(REFINE)
    assert K < e["rows"] < e["selected"]
    with pytest.raises(ValueError, match="norms"):
        certified.repair_uncertified(
            d, i, K, K + 4, np.arange(1), q, db, select_fn=None,
            max_widen=n, metric="cosine")


def test_a_filtered_query_out_of_valid_rows_keeps_every_candidate(
        every_query_flagged):  # noqa: F811
    """A query with fewer than k valid rows: its k-th float32 score is
    +inf, nothing lies past it, and the refine takes the call's whole
    width; the answer is the valid rows in float64 order, padded."""
    rng = np.random.default_rng(54)
    db = rng.integers(0, 256, size=(600, 16)).astype(np.float32)
    q = rng.integers(0, 256, size=(2, 16)).astype(np.float32)
    few = np.sort(rng.choice(600, K - 4, replace=False))
    prog = ShardedKNN(db, mesh=mesh(), k=K, row_tags=csr(
        [[0, 1] if r in few else [0] for r in range(600)]))
    every = np.array([[0, -1]] * 2, np.int32)
    prog.search_certified(q, selector="pallas", filter_tags=every)
    (plenty,) = spans(REFINE)
    assert 2 * K <= plenty["rows"] < plenty["selected"]
    _fresh()
    d, i, stats = prog.search_certified(
        q, selector="pallas", filter_tags=np.array([[0, -1], [1, -1]],
                                                   np.int32))
    assert stats["fallback_queries"] == 2
    (e,) = spans(REFINE)
    assert e["rows"] == e["selected"] == plenty["selected"]
    d64 = ((db[few].astype(np.float64) - q[1]) ** 2).sum(-1)
    order = np.lexsort((few, d64))
    np.testing.assert_array_equal(i[1, : K - 4], few[order])
    np.testing.assert_array_equal(d[1, : K - 4], d64[order])
    assert (i[1, K - 4:] == -1).all() and np.isinf(d[1, K - 4:]).all()
    want_d, want_i = certified.host_exact_knn(db, q[:1], K)
    np.testing.assert_array_equal(i[:1], want_i)
    np.testing.assert_array_equal(d[:1], want_d)


_SIX = np.arange(6)[None]
#: case -> (fs ascending, fi, exclude, norms, the candidates kept); the
#: tolerance is 1 and k is 2
WITHIN_REACH = {
    # the band is two tolerances, and closed
    "a gap": ([[0.0, 1.0, 2.5, 3.0, 3.5, 9.0]], _SIX, None, None,
              _SIX[:, :4]),
    "ties past the width": ([[4.0] * 6], _SIX, None, None, _SIX),
    "no finite k-th": ([[0.0] + [np.inf] * 5], _SIX, None, None, _SIX),
    "no wider than k": ([[0.0, 50.0]], _SIX[:, :2], None, None,
                        _SIX[:, :2]),
    # one rectangular slice a call
    "the widest query": ([[0.0, 1.0, 9.0, 9.0, 9.0, 9.0],
                          [0.0, 1.0, 2.0, 3.0, 9.0, 9.0]],
                         np.stack([_SIX[0], _SIX[0] + 10]), None, None,
                         np.stack([_SIX[0, :4], _SIX[0, :4] + 10])),
    # the own row is the nearest: the k-th LEFT is 3.0, not 1.0
    "an own row": ([[0.0, 1.0, 3.0, 4.5, 5.5, 9.0]], _SIX, np.array([0]),
                   None, [[1, 2, 3]]),
    # cosine: row 1 has no norm, so the k-th that counts is 3.0
    "a zero row": ([[0.0, 1.0, 3.0, 4.5, 5.5, 9.0]], _SIX, None,
                   (np.ones(1), np.array([2.0, 0.0, 1.0, 1.0, 1.0, 1.0])),
                   _SIX[:, :4]),
}


@pytest.mark.parametrize("case", sorted(WITHIN_REACH))
def test_within_reach(case):
    """The helper alone."""
    fs, fi, exclude, norms, want = WITHIN_REACH[case]
    fs = np.asarray(fs)
    got = certified._within_reach(fs, fi, 2, np.ones(fs.shape[0]), exclude,
                                  norms)
    np.testing.assert_array_equal(got, want)


# --- the range completion -------------------------------------------------------
@pytest.fixture(scope="module")
def ranged():
    """96 queries whose lists are all longer than k: two completion
    sub-batches of 64, and some lists longer than the collect width
    (512 at k = 10), which the host scan finishes."""
    rng = np.random.default_rng(34)
    db = rng.random((2500, 24), dtype=np.float32)
    q = rng.random((96, 24), dtype=np.float32)
    prog = ShardedKNN(db, mesh=mesh(), k=K, train_tile=1024)
    d2 = ((db.astype(np.float64)[None] - q.astype(np.float64)[:, None])
          ** 2).sum(-1)
    radius_sq = float(np.sort(d2, axis=1)[:, 39].max())
    prog.range_search_certified(q, radius_sq=radius_sq)
    return prog, q, radius_sq


def test_a_range_call_records_the_five_phases_once(ranged):
    prog, q, radius_sq = ranged
    *_, stats = prog.range_search_certified(q, radius_sq=radius_sq)
    done = stats["range"]
    assert done["truncated"] + done["host_scan"] == 96 > RANGE_SUB_BATCH
    assert done["sub_batches"] == 2 and done["host_scan"] > 0
    (whole,) = spans("certified.range_complete")
    by = {}
    for name in RANGE_PHASES:
        (by[name],) = spans(name)  # one record, not one a sub-batch
        assert by[name]["parent"] == "certified.range_complete"
        assert by[name]["trace_id"] == whole["trace_id"]
    ran = [e["dur_s"] for e in by.values()]
    assert all(v > 0 for v in ran)
    assert sum(ran) <= whole["dur_s"] + 1e-4
    # the account's own records name the call, the phases their stage
    assert not any("account_of" in e for e in by.values())


def test_a_range_call_with_nothing_truncated_records_them_at_zero(ranged):
    prog, q, _ = ranged
    *_, stats = prog.range_search_certified(q, radius_sq=1e-6)
    assert stats["range"]["truncated"] == 0
    assert {name: [e["dur_s"] for e in spans(name)]
            for name in RANGE_PHASES} == dict.fromkeys(RANGE_PHASES, [0.0])


# --- both sides of metric_map ---------------------------------------------------
def _mapped(kind):
    rng = np.random.default_rng(31)
    db = rng.normal(size=(3000, 48)).astype(np.float32)
    db *= rng.uniform(0.5, 2.0, size=(3000, 1)).astype(np.float32)
    q = rng.normal(size=(48, 48)).astype(np.float32)
    if kind == "voted":
        prog = ShardedKNN(db, mesh=mesh(), k=K, metric="cosine",
                          labels=rng.integers(0, 30, 3000).astype(np.int32),
                          num_classes=30)
        return lambda: prog.predict_certified(
            q, vote="softmax", temperature=0.07, classes_out=5,
            selector="pallas")
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric=kind)
    return lambda: prog.search_certified(q, selector="pallas")


@pytest.mark.parametrize("kind", ["dot", "cosine", "voted"])
def test_metric_maps_two_sides_are_series_of_their_own(kind, monkeypatch):
    call = _mapped(kind)
    call()
    obs.reset_event_log(None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.names = []
    call()
    (whole,) = spans("certified.metric_map")
    (before,) = spans("certified.metric_map.before")
    (after,) = spans("certified.metric_map.after")
    assert whole["parent"] == "certified.call"
    assert {before["parent"], after["parent"]} == {"certified.metric_map"}
    assert len({e["trace_id"] for e in (whole, before, after)}) == 1
    assert before["dur_s"] + after["dur_s"] == pytest.approx(
        whole["dur_s"], abs=2e-6)  # each rounded to the microsecond
    assert (before["dur_s"], after["dur_s"]) == pytest.approx(
        (whole["before_s"], whole["after_s"]), abs=1e-6)
    assert before["dur_s"] > 0
    # only the inner product scores its answers afterwards
    assert (after["dur_s"] > 0) == (kind == "dot")
    named = [n for n in _CountingAnnotation.names if "metric_map" in n]
    assert set(named) == {"knn.certified.metric_map.before"} | (
        {"knn.certified.metric_map.after"} if kind == "dot" else set())


def test_an_l2_call_maps_nothing():
    _search("l2")()
    assert spans(prefix="certified.metric_map") == []


# --- one switch -----------------------------------------------------------------
def test_obs_off_records_none_of_it_and_changes_no_answer(ranged,
                                                          monkeypatch):
    prog, q, radius_sq = ranged
    db, tq = tied_rows()
    tied = ShardedKNN(db, mesh=mesh(), k=K, metric="dot")
    on = (tied.search_certified(tq, selector="pallas")[:2]
          + prog.range_search_certified(q, radius_sq=radius_sq)[:3])
    assert spans(REFINE) and spans(RANGE_PHASES[0])
    monkeypatch.setenv("KNN_TPU_OBS", "0")
    obs.reset()
    obs.reset_event_log(None)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.names = []
    off = (tied.search_certified(tq, selector="pallas")[:2]
           + prog.range_search_certified(q, radius_sq=radius_sq)[:3])
    assert _CountingAnnotation.names == []
    assert obs.get_event_log().recent() == [] and obs.snapshot() == {}
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)  # bitwise


def test_a_stage_hands_its_trace_id_to_the_code_below():
    """``repair_uncertified`` learns its trace id from the innermost
    span, and in the self-join that is a stage of the block's account."""
    acct = obs_trace.block_account(obs_trace.NOOP_ACCOUNT, ("s",))
    with obs_trace.stage(acct, "s", "abc", rows=1) as sp:
        assert obs.current_span() is sp
        assert (sp.trace_id, sp.attrs) == ("abc", {"rows": 1})
    with obs_trace.stage(acct, "s") as sp:
        assert sp.trace_id is None
