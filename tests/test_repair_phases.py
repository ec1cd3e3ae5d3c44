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
from test_yfcc_filter import csr  # noqa: E402  (tests/)

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
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def mesh():
    return make_mesh(1, 1, devices=jax.devices()[:1])


def spans(name=None, prefix="certified."):
    return [e for e in obs.get_event_log().recent()
            if e.get("type") == "span" and (
                e["span"] == name if name else e["span"].startswith(prefix))]


def repair_outcomes() -> dict:
    series = obs.snapshot().get(mn.REPAIR_QUERIES, {"series": []})["series"]
    return {s["labels"]["outcome"]: s["value"] for s in series}


def tied_rows(seed=6, dim=16):
    """Eight rows, four of them repeated 128 times (more copies than the
    widened re-select holds: its own bound proves nothing and the host
    scans) and four 40 times (past the analysis window and inside the
    widened selection: flagged, then proven), and a query near each of
    two of either kind."""
    rng = np.random.default_rng(seed)
    base = rng.normal(size=(8, dim)).astype(np.float32)
    base *= rng.uniform(0.5, 2.0, size=(8, 1)).astype(np.float32)
    db = np.concatenate([np.repeat(base[:4], 128, axis=0),
                         np.repeat(base[4:], 40, axis=0)])
    q = base[[0, 1, 4, 5]] + np.float32(0.01)
    return db, q


def _search(metric):
    db, q = tied_rows()
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric=metric)
    return lambda: prog.search_certified(q, selector="pallas")[-1]


def _filtered():
    db, q = tied_rows()
    # every row holds tag 0, a row in three tag 1 too: a filter on tag 0
    # keeps every row valid
    prog = ShardedKNN(db, mesh=mesh(), k=K, row_tags=csr(
        [[0, 1] if r % 3 == 0 else [0] for r in range(db.shape[0])]))
    ft = np.array([[0, -1]] * q.shape[0], np.int32)
    return lambda: prog.search_certified(q, selector="pallas",
                                         filter_tags=ft)[-1]


def _self(monkeypatch):
    # one block: the call's rows are fewer than a block
    monkeypatch.setitem(tuning.DEFAULT_KNOBS, "tile_n", 256)
    db, _ = tied_rows()
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    return lambda: knn_self_join(prog, rows=(500, 532))[-1]


FORMS = {
    "l2-plain": lambda mp: _search("l2"),
    "dot-plain": lambda mp: _search("dot"),
    "cosine-plain": lambda mp: _search("cosine"),
    "l2-valid_rows_fn": lambda mp: _filtered(),
    "l2-exclude": _self,
}


# --- the repair's host half ---------------------------------------------------
@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_call_with_fallbacks_records_the_repairs_two_phases(form,
                                                              monkeypatch):
    call = FORMS[form](monkeypatch)
    call()  # the placement's one-time passes and compiles
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    stats = call()
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


def test_the_self_join_records_the_repairs_phases_once_a_block(monkeypatch):
    monkeypatch.setitem(tuning.DEFAULT_KNOBS, "tile_n", 256)
    monkeypatch.setattr(engine, "DEFAULT_SUPERBLOCK_ROWS", 192)
    db, _ = tied_rows()
    prog = ShardedKNN(db, mesh=mesh(), k=K)
    knn_self_join(prog, rows=(0, 192))
    obs.reset(enabled=True)
    obs.reset_event_log(None)
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
