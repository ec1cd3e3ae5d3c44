"""The certified sweep path's own tracing: one ``search_certified`` call
is a tree of stage spans under ``certified.call`` (one trace id, each
child naming its parent), every span is also a ``knn.<name>`` profiler
annotation on the clock of the device trace, and the device program
carries five ``jax.named_scope`` names.  One switch (``KNN_TPU_OBS``)
turns all of the host side off and changes no answer.

CPU, Pallas interpreted, tiny corpus: what is checked is which spans
exist and how they nest, never how long one took.
"""

import glob
import os
import subprocess
import sys
from collections import Counter

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.obs import trace as obs_trace
from knn_tpu.ops import pallas_knn, refine
from knn_tpu.parallel import sharded as sh
from knn_tpu.parallel.mesh import make_mesh
from knn_tpu.parallel.sharded import ShardedKNN

K = 10
N_QUERIES = 96


@pytest.fixture(autouse=True)
def _fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(5)
    db = rng.normal(size=(3000, 32)).astype(np.float32)
    q = rng.normal(size=(N_QUERIES, 32)).astype(np.float32)
    return db, q


@pytest.fixture(scope="module")
def placed(corpus):
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K)
    # the float64 pass over all rows is the first call's: make it here,
    # so that every test sees a later call
    prog.search_certified(corpus[1], selector="pallas")
    return prog


@pytest.fixture(scope="module")
def tied():
    """Rows repeated far past the analysis window: every query's tie run
    crosses it, so every query falls back and the repair re-selects."""
    rng = np.random.default_rng(6)
    base = rng.normal(size=(8, 16)).astype(np.float32)
    db = np.repeat(base, 64, axis=0)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    q = base[:4] + np.float32(0.01)
    prog.search_certified(q, selector="pallas")
    return prog, q


def _spans(prefix="certified."):
    """The call's own spans; a program's first-call record (prefix
    ``program.``) comes whenever a test is the first to run a shape."""
    return [e for e in obs.get_event_log().recent()
            if e.get("type") == "span" and e["span"].startswith(prefix)]


#: the stages a call closes once a SUB-BATCH: a profiler annotation an
#: occurrence, ONE record a call (the sum, through the call's account)
PER_BATCH = ("certified.dispatch", "certified.device_wait", "certified.d2h",
             "certified.unpack", "certified.rank_correct")
#: the account's records: one a call whatever the sub-batches, every one
#: in every call (0.0 where its piece did not run)
ONCE_A_CALL = ("certified.exposed", "certified.inflight.certified",
               "certified.inflight.reselect",
               "certified.rank_correct.buffers",
               "certified.rank_correct.score",
               "certified.rank_correct.order", "certified.unpack.copies")
#: the repair's host half (ops.certified.repair_uncertified): one record
#: each a call, children of ``certified.repair``, 0.0 and no profiler
#: annotation where no query fell back
REPAIR_PHASES = ("certified.repair.refine", "certified.repair.host_scan")
PHASES = {"knn.certified.rank_correct.buffers",
          "knn.certified.rank_correct.score",
          "knn.certified.rank_correct.order"}


def _expected(batches: int = 1, reselects: int = 0) -> Counter:
    """The records of one call, whatever its sub-batches; with
    ``batches`` the scopes it opens (its profiler annotations)."""
    want = Counter({"certified.call": 1, "certified.prepare": 1,
                    "certified.repair": 1})
    for name in PER_BATCH:
        want[name] = batches
    for name in ONCE_A_CALL + REPAIR_PHASES:
        want[name] = 1
    if reselects:
        want["certified.repair.reselect"] = reselects
    return want


# --- the span tree ------------------------------------------------------
@pytest.mark.parametrize("kw,batches", [
    pytest.param({}, 1, id="one_batch"),
    pytest.param({"batch_size": 32}, 3, id="three_batches"),
])
def test_a_certified_call_emits_exactly_its_stage_spans(placed, corpus, kw,
                                                        batches):
    d, i, stats = placed.search_certified(corpus[1], selector="pallas", **kw)
    assert stats["fallback_queries"] == 0  # so no re-select is expected
    spans = _spans()
    assert Counter(e["span"] for e in spans) == _expected()

    tids = {e.get("trace_id") for e in spans}
    assert len(tids) == 1 and None not in tids
    by = {e["span"]: e for e in spans}
    call = by["certified.call"]
    assert "parent" not in call
    assert (call["selector"], call["queries"], call["batches"]) == (
        "pallas", N_QUERIES, batches)
    children = [e for e in spans if e["span"] != "certified.call"
                and e["span"] not in ONCE_A_CALL + REPAIR_PHASES]
    assert {e["parent"] for e in children} == {"certified.call"}
    # the repair's phases are its own children, at 0.0 with no fallback
    assert {(by[name]["parent"], by[name]["dur_s"])
            for name in REPAIR_PHASES} == {("certified.repair", 0.0)}
    # the account's records are sums over the call, not children of it
    account = [e for e in spans if e["span"] in ONCE_A_CALL]
    assert {e["account_of"] for e in account} == {"certified.call"}
    assert not any("parent" in e for e in account)
    # self time = length less the children's: never negative
    assert sum(e["dur_s"] for e in children) <= call["dur_s"] + 1e-4

    assert by["certified.prepare"]["first_call"] is False
    # a stage's record is the call's: the sum over its sub-batches
    assert by["certified.dispatch"]["h2d_bytes"] == N_QUERIES * 32 * 4
    assert by["certified.d2h"]["d2h_bytes"] > 0
    (corrected,) = [e for e in spans
                    if e["span"] == "certified.rank_correct"]
    assert corrected["queries_corrected"] == stats["rank_corrected_queries"]
    # a tight pair involves two positions; a batch here has far too few
    # of them to be cut into ranges
    assert corrected["members"] >= 2 * corrected["queries_corrected"]
    assert corrected["parts"] == batches
    assert corrected["threads"] == batches * refine._POOL_THREADS
    assert by["certified.repair"]["fallback_queries"] == 0
    assert by["certified.repair"]["host_exact_queries"] == 0
    # the same spans feed the histogram an operator scrapes
    series = {s["labels"]["span"]: s["value"]["count"]
              for s in obs.snapshot()[mn.SPAN_SECONDS]["series"]}
    assert {series[name] for name in PER_BATCH} == {1}
    assert series["certified.call"] == 1
    assert {series[name] for name in ONCE_A_CALL} == {1}


def test_first_call_is_marked_on_prepare(corpus):
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K)
    for want in (True, False):
        obs.reset_event_log(None)
        prog.search_certified(corpus[1][:8], selector="pallas")
        (prep,) = [e for e in _spans() if e["span"] == "certified.prepare"]
        assert prep["first_call"] is want


def test_a_fallback_adds_one_reselect_under_repair(tied):
    prog, q = tied
    d, i, stats = prog.search_certified(q, selector="pallas")
    assert stats["fallback_queries"] == q.shape[0]
    spans = _spans()
    assert Counter(e["span"] for e in spans) == _expected(1, reselects=1)
    by = {e["span"]: e for e in spans}
    re = by["certified.repair.reselect"]
    assert re["parent"] == "certified.repair"
    assert re["trace_id"] == by["certified.call"]["trace_id"]
    assert re["rows"] == q.shape[0] and re["widen"] > K
    assert re["scan_rows_copied"] == 0  # the rows are read where they lie
    assert re["dur_s"] <= by["certified.repair"]["dur_s"] + 1e-4
    assert by["certified.repair"]["fallback_queries"] == q.shape[0]
    assert by["certified.repair"]["host_exact_queries"] == stats.get(
        "host_exact_queries", 0)


# --- one switch ---------------------------------------------------------
class _CountingAnnotation(jax.profiler.TraceAnnotation):
    names = []

    def __init__(self, name, **kw):
        _CountingAnnotation.names.append(name)
        super().__init__(name, **kw)


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="one_batch"),
    pytest.param({"batch_size": 32}, id="three_batches"),
])
def test_obs_off_makes_nothing_and_changes_no_answer(placed, corpus,
                                                     monkeypatch, kw):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.names = []
    d1, i1, _ = placed.search_certified(corpus[1], selector="pallas", **kw)
    on = list(_CountingAnnotation.names)
    assert "knn.certified.call" in on and "knn.certified.d2h" in on
    assert all(n.startswith(obs_trace.ANNOTATION_PREFIX) for n in on)

    monkeypatch.setenv("KNN_TPU_OBS", "0")
    obs.reset()
    obs.reset_event_log(None)
    _CountingAnnotation.names = []
    d0, i0, _ = placed.search_certified(corpus[1], selector="pallas", **kw)
    assert _CountingAnnotation.names == []
    assert obs.get_event_log().recent() == []
    assert obs.snapshot() == {}
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)  # bitwise: same float64 arrays


def test_importing_obs_and_opening_a_span_imports_no_jax():
    # the suite's conftest imports JAX, so prove it in a clean interpreter
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; import knn_tpu.obs as obs\n"
         "with obs.span('serving.dispatch') as sp: sp.set('k', 1)\n"
         "assert obs.get_event_log().recent()[0]['span'] "
         "== 'serving.dispatch'\n"
         "assert 'jax' not in sys.modules, 'obs imported jax'"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


# --- the profiler's clock -----------------------------------------------
def test_a_live_profile_holds_the_stage_annotations_nested(placed, corpus,
                                                           tmp_path):
    path = str(tmp_path / "spans")
    with jax.profiler.trace(path):
        with jax.profiler.TraceAnnotation("test.outer"):
            placed.search_certified(corpus[1], selector="pallas",
                                    batch_size=32)
    (pb,) = glob.glob(f"{path}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(pb)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.name == "test.outer" or e.name.startswith(
                       obs_trace.ANNOTATION_PREFIX)]
            if any(n == "test.outer" for n, _, _ in evs):
                found = evs
    assert found, "no line holds the outer annotation"
    (outer,) = [e for e in found if e[0] == "test.outer"]
    inner = [e for e in found if e[0] != "test.outer"]
    # every scoped span is an annotation; the account's records are sums
    # measured after the fact and make none, but rank_correct's three
    # phases each do, inside their stage, so that an idle gap under
    # knn.certified.rank_correct splits by itself
    scoped = _expected(3) - Counter(ONCE_A_CALL + REPAIR_PHASES)
    assert Counter(n for n, _, _ in inner if n not in PHASES) == Counter(
        {f"knn.{name}": c for name, c in scoped.items()})
    (call,) = [e for e in inner if e[0] == "knn.certified.call"]
    assert outer[1] <= call[1] and call[2] <= outer[2]
    for name, start, end in inner:
        assert call[1] <= start and end <= call[2], name
    stages = [e for e in inner if e[0] == "knn.certified.rank_correct"]
    phases = [e for e in inner if e[0] in PHASES]
    assert {"knn.certified.rank_correct.buffers",
            "knn.certified.rank_correct.order"} <= {n for n, _, _ in phases}
    for name, start, end in phases:
        assert any(lo <= start and end <= hi for _, lo, hi in stages), name


# --- device scopes ------------------------------------------------------
SCOPES = (pallas_knn.SCOPE_OPERAND_PREP, pallas_knn.SCOPE_KERNEL,
          pallas_knn.SCOPE_FINAL_SELECT, pallas_knn.SCOPE_RESCORE,
          sh.SCOPE_CERTIFY_PACK)


@pytest.fixture(scope="module")
def lowered(placed, corpus):
    """The certified program's lowered text, by db-streaming kernel:
    the grid-tiled one the cells run and the one-launch one whose chip
    time is still to be taken (a trace splits either by these scopes)."""
    qp, _ = placed._place_queries(corpus[1])
    out = {}
    for kernel in ("tiled", "streaming"):
        prog, _, _, _ = placed._pallas_setup(28, None, "bf16x3",
                                             kernel=kernel)
        tail = placed._pallas_operands("bf16x3")  # of that program
        out[kernel] = prog.lower(qp, placed._tp, *tail).as_text(
            debug_info=True)
    return out


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("kernel", ["tiled", "streaming"])
def test_the_lowered_program_names_each_device_scope(lowered, kernel,
                                                     scope):
    assert scope.startswith("knn.")
    assert f"{scope}/" in lowered[kernel] or f"{scope}\"" in lowered[
        kernel]


# --- scripts/certified_stage_report.py ----------------------------------
@pytest.fixture(scope="module")
def report():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "certified_stage_report.py")
    spec = importlib.util.spec_from_file_location("stage_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_stage_report_reads_the_jsonl_log(placed, corpus, report,
                                              tmp_path):
    log = tmp_path / "events.jsonl"
    obs.reset_event_log(str(log))
    for _ in range(3):
        placed.search_certified(corpus[1], selector="pallas", batch_size=32)
    obs.reset_event_log(None)
    table = report.stage_table(report.read_jsonl(str(log)), skip_calls=1)
    assert (table["calls"], table["batches"]) == (2, 6)
    assert set(table["stages_ms"]) == set(_expected()) - set(ONCE_A_CALL)
    # the account's records beside the stages, each a mean per CALL
    assert set(table["account_ms"]) == set(ONCE_A_CALL)
    exposed = table["account_ms"]["certified.exposed"]
    assert exposed["launches"] == 3
    assert exposed["ms"] + exposed["inflight_union_ms"] == pytest.approx(
        exposed["call_ms"], abs=1e-3)
    assert exposed["call_ms"] <= table["stages_ms"]["certified.call"][
        "per_call"]
    # a stage is one record a call, its per_batch mean over the launches
    d2h = table["stages_ms"]["certified.d2h"]
    assert d2h["spans"] == 2
    assert d2h["per_batch"] == pytest.approx(d2h["per_call"] / 3, abs=1e-4)
    assert table["stages_ms"]["certified.call"]["spans"] == 2
    # a phase is a row right after its parent's, and names it
    names = list(table["stages_ms"])
    for phase in REPAIR_PHASES:
        row = table["stages_ms"][phase]
        assert (row["parent"], row["spans"]) == ("certified.repair", 2)
        assert names.index(phase) > names.index("certified.repair")
    assert "parent" not in table["stages_ms"]["certified.call"]
    assert 0 < table["children_share_of_call"] <= 1
    assert table["call_self_ms_per_call"] >= 0
    assert table["per_batch"]["h2d_bytes"] == 32 * 32 * 4
    assert table["per_batch"]["members"] >= 2 * table["per_batch"][
        "queries_corrected"]
    assert table["per_batch"]["parts"] == 1
    assert table["per_batch"]["threads"] == refine._POOL_THREADS
    # a narrow shard: the final select ran over the kernel's candidates
    assert (table["select"]["select_width"]
            == table["select"]["select_merged_width"] > 0)


def test_the_stage_report_lays_idle_time_on_the_innermost_span(report):
    busy = [(0, 10), (5, 12), (30, 40), (70, 80)]
    gaps = report.gaps_of(busy, (0, 100))
    assert gaps == [(12, 30), (40, 70), (80, 100)]
    spans = [("knn.certified.call", 10, 90),
             ("knn.certified.device_wait", 10, 41),
             ("knn.certified.rank_correct", 45, 65)]
    by = report.attribute(gaps, spans)
    assert by == {
        "knn.certified.device_wait": 18 + 1,
        "knn.certified.rank_correct": 20,
        "knn.certified.call": 4 + 5 + 10,  # its own time only
        "outside": 10,
    }
    assert sum(by.values()) == sum(hi - lo for lo, hi in gaps)


def test_the_stage_report_lays_gaps_as_the_benchmark_does(report):
    """One arithmetic: the script's ``attribute`` is the benchmark's
    (``tracereduce.attribute``), so two spans of EQUAL length go to the
    later start in both, and a device scope's time is each op's own (a
    ``%while`` does not count its body a second time)."""
    import tracereduce

    spans = [("knn.certified.unpack", 0, 10), ("knn.certified.d2h", 5, 15)]
    assert report.attribute([(6, 9)], spans) == {"knn.certified.d2h": 3}
    assert tracereduce.attribute(
        [(6, 9)], [(n, lo, hi - lo) for n, lo, hi in spans]) == {
            "knn.certified.d2h": 3}
    loop = [("knn.merge", 0.0, 10e9), ("unscoped", 1e9, 4e9),
            ("unscoped", 5e9, 9e9)]
    assert tracereduce._self_times(loop) == {"knn.merge": 3.0,
                                             "unscoped": 7.0}


def _msg(*fields):
    """A protobuf message from (field number, int | bytes) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    buf = b""
    for num, value in fields:
        if isinstance(value, int):
            buf += varint(num << 3) + varint(value)
        else:
            buf += varint(num << 3 | 2) + varint(len(value)) + value
    return buf


def test_the_stage_report_finds_the_scope_in_the_event_metadata(report):
    """The layout the v5e's profiler writes (PERF.md, PR 25): the HLO
    ``op_name`` is the ``tf_op`` stat of an op's event METADATA in the
    device plane, not a stat of its events."""
    def entry(key, value):
        return _msg((1, key), (2, value))

    def op(meta_id, name, tf_op):
        stats = [(5, _msg((1, 7), (5, b"loop fusion")))]
        if tf_op is not None:
            stats.append((5, _msg((1, 300), (5, tf_op))))
        return (4, entry(meta_id, _msg((1, meta_id), (2, name), *stats)))

    plane = _msg(
        (1, 3), (2, b"/device:TPU:0"),
        (5, entry(7, _msg((1, 7), (2, b"hlo_category")))),
        (5, entry(300, _msg((1, 300), (2, b"tf_op")))),
        op(1, b"%fusion.2 = f32[8] fusion()",
           b"jit(spmd)/jit(f)/knn.final_select/top_k:"),
        op(2, b"%pad.0 = f32[8] pad()",
           b"jit(spmd)/knn.kernel/jit(_bin_candidates)/knn.operand_prep/"
           b"jit(_pad)/pad:"),
        op(3, b"%copy.1 = f32[8] copy()", None),
        op(4, b"%select_merge.1 = (f32[8], s32[8], f32[8]) custom-call()",
           b"jit(spmd)/jit(f)/knn.final_select/knn.select_merge/"
           b"pallas_call:"))
    other = _msg((1, 4), (2, b"/host:CPU"),
                 (5, entry(300, _msg((1, 300), (2, b"tf_op")))),
                 op(1, b"%fusion.2 = f32[8] fusion()", b"knn.rescore/x:"))
    scopes = report.op_scopes(_msg((1, other), (1, plane)))
    assert scopes == {
        "%fusion.2 = f32[8] fusion()":
            "jit(spmd)/jit(f)/knn.final_select/top_k:",
        "%pad.0 = f32[8] pad()":
            "jit(spmd)/knn.kernel/jit(_bin_candidates)/knn.operand_prep/"
            "jit(_pad)/pad:",
        "%select_merge.1 = (f32[8], s32[8], f32[8]) custom-call()":
            "jit(spmd)/jit(f)/knn.final_select/knn.select_merge/"
            "pallas_call:"}
    # the bin-merge inside the final select is a row of its own (the
    # innermost scope wins), so merge pass and top-k read apart
    assert [report.innermost_scope(scopes.get(n, "")) for n in (
        "%fusion.2 = f32[8] fusion()", "%pad.0 = f32[8] pad()",
        "%copy.1 = f32[8] copy()",
        "%select_merge.1 = (f32[8], s32[8], f32[8]) custom-call()")] == [
            "knn.final_select", "knn.operand_prep", "unscoped",
            "knn.select_merge"]
    assert report.clipped([(0, 4), (2, 6), (9, 12)], (1, 10)) == [
        (1, 6), (9, 10)]


# --- the call's account -------------------------------------------------
def _account(spans):
    (exposed,) = [e for e in spans if e["span"] == "certified.exposed"]
    inflight = {e["span"].rsplit(".", 1)[1]: e for e in spans
                if e["span"].startswith("certified.inflight.")}
    return exposed, inflight


def _holds_the_identity(exposed, inflight, call):
    """exposed + the union of the in-flight intervals = the call's
    length, as the account measured it, to a microsecond; that length
    is the call span's but for the few lines around the account."""
    assert exposed["dur_s"] + exposed["inflight_union_s"] == pytest.approx(
        exposed["call_s"], abs=1e-6)
    assert exposed["before_first_launch_s"] + exposed["between_s"] + exposed[
        "after_last_ready_s"] == pytest.approx(exposed["dur_s"], abs=2e-6)
    # (a few lines of Python; on a loaded machine a descheduled thread
    # makes them milliseconds)
    assert 0 <= call["dur_s"] - exposed["call_s"] < 5e-2
    # programs in flight one after another: the union is their sum
    assert sum(e["dur_s"] for e in inflight.values()) == pytest.approx(
        exposed["inflight_union_s"], abs=1e-5)
    assert exposed["launches"] == sum(
        e["launches"] for e in inflight.values())


def test_exposed_and_in_flight_close_the_call_without_fallbacks(placed,
                                                                corpus):
    _, _, stats = placed.search_certified(corpus[1], selector="pallas")
    assert stats["fallback_queries"] == 0
    spans = _spans()
    exposed, inflight = _account(spans)
    (call,) = [e for e in spans if e["span"] == "certified.call"]
    _holds_the_identity(exposed, inflight, call)
    assert set(inflight) == {"certified", "reselect"}
    assert inflight["certified"]["launches"] == 1
    # recorded at 0.0, not left out, where the program did not run
    assert (inflight["reselect"]["dur_s"],
            inflight["reselect"]["launches"]) == (0.0, 0)
    assert exposed["between_s"] == 0.0
    # the program is in flight from its launch to the end of the wait
    (wait,) = [e for e in spans if e["span"] == "certified.device_wait"]
    assert inflight["certified"]["dur_s"] >= wait["dur_s"] - 1e-4
    launches = {s["labels"]["program"]: s["value"]
                for s in obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    assert launches == {"certified": 1, "reselect": 0}


def test_exposed_and_in_flight_close_the_call_with_fallbacks(tied):
    prog, q = tied
    _, _, stats = prog.search_certified(q, selector="pallas")
    assert stats["fallback_queries"] == q.shape[0]
    spans = _spans()
    exposed, inflight = _account(spans)
    by = {e["span"]: e for e in spans}
    _holds_the_identity(exposed, inflight, by["certified.call"])
    assert inflight["reselect"]["launches"] == 1
    # the device program inside the repair, apart from the host's refine
    assert 0 < inflight["reselect"]["dur_s"] <= by[
        "certified.repair.reselect"]["dur_s"]
    assert inflight["reselect"]["dur_s"] <= by["certified.repair"]["dur_s"]
    # between the first pass's answer and the re-select's launch the
    # host worked with nothing in flight
    assert exposed["between_s"] > 0


def test_a_range_calls_account_is_the_outermost_calls(placed, corpus):
    """One account a range call: the first pass adds to it, the
    completion's program is named, and the pack, which runs while the
    completion's first sub-batch is in flight, is no part of exposed."""
    lims, idx, dist, stats = placed.range_search_certified(
        corpus[1], radius_sq=40.0)
    assert stats["range"]["truncated"] > 0
    spans = _spans()
    assert Counter(e["span"] for e in spans if e["span"] in ONCE_A_CALL
                   or e["span"].startswith("certified.inflight.")) == Counter(
        {**dict.fromkeys(ONCE_A_CALL, 1), "certified.inflight.range": 1})
    exposed, inflight = _account(spans)
    by = {e["span"]: e for e in spans}
    call = by["certified.range_call"]
    _holds_the_identity(exposed, inflight, call)
    assert {e["account_of"] for e in spans if "account_of" in e} == {
        "certified.range_call"}
    assert set(inflight) == {"certified", "reselect", "range"}
    assert inflight["range"]["launches"] == stats["range"]["sub_batches"]
    assert inflight["certified"]["launches"] == 1
    # the pack lies inside the completion's first flight
    pack = by["certified.range_pack"]["dur_s"]
    assert inflight["range"]["dur_s"] >= pack - 1e-4
    assert exposed["dur_s"] <= (call["dur_s"] - pack
                                - inflight["certified"]["dur_s"] + 1e-4)
    launches = {s["labels"]["program"]: s["value"]
                for s in obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    assert launches == {"certified": 1, "reselect": 0,
                        "range": stats["range"]["sub_batches"]}


def test_obs_off_leaves_a_range_call_its_answers_and_no_record(
        placed, corpus, monkeypatch):
    on = placed.range_search_certified(corpus[1], radius_sq=40.0)
    monkeypatch.setenv("KNN_TPU_OBS", "0")
    obs.reset()
    obs.reset_event_log(None)
    off = placed.range_search_certified(corpus[1], radius_sq=40.0)
    assert obs.get_event_log().recent() == [] and obs.snapshot() == {}
    for a, b in zip(on[:3], off[:3]):
        np.testing.assert_array_equal(a, b)  # bitwise


@pytest.mark.parametrize("batch_size,batches", [(96, 1), (48, 2), (24, 4)])
def test_the_accounts_records_count_calls_and_the_stages_batches(
        placed, corpus, batch_size, batches):
    """Why the account: cut a call into sub-batches and every stage
    scope closes that many times (a series of scopes would read 1/n
    with no work saved); every record of the account, the stages' sums
    among them, stays one a call."""
    for _ in range(2):
        placed.search_certified(corpus[1], selector="pallas",
                                batch_size=batch_size)
    series = {s["labels"]["span"]: s["value"]
              for s in obs.snapshot()[mn.SPAN_SECONDS]["series"]}
    for name in ONCE_A_CALL:
        assert series[name]["count"] == 2, name
    for name in PER_BATCH:
        assert series[name]["count"] == 2, name
    spans = _spans()
    for name, of in (("certified.rank_correct", "certified.rank_correct."),
                     ("certified.unpack", "certified.unpack.")):
        parts = sum(e["dur_s"] for e in spans
                    if e["span"].startswith(of))
        whole = sum(e["dur_s"] for e in spans if e["span"] == name)
        # the pieces are inside their stage (its own overhead is left)
        assert 0 < parts <= whole + 1e-4
    exposed, inflight = _account(spans[-sum(_expected().values()):])
    assert inflight["certified"]["launches"] == batches


def test_rank_corrects_three_phases_make_up_the_stage():
    """Every row placed twice: each query's neighbours come in tied
    pairs, so members are re-scored and all three phases run; what they
    sum to is the stage less its own overhead."""
    rng = np.random.default_rng(8)
    db = np.repeat(rng.normal(size=(1500, 32)).astype(np.float32), 2, axis=0)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K)
    q = rng.normal(size=(64, 32)).astype(np.float32)
    prog.search_certified(q, selector="pallas")
    obs.reset_event_log(None)
    prog.search_certified(q, selector="pallas")
    by = {e["span"]: e for e in _spans()}
    stage = by["certified.rank_correct"]
    assert stage["members"] > 0
    score = by["certified.rank_correct.score"]
    parts = (by["certified.rank_correct.buffers"]["dur_s"]
             + score["dur_s"] + by["certified.rank_correct.order"]["dur_s"])
    assert 0.5 * stage["dur_s"] < parts <= stage["dur_s"] + 1e-4
    # inside the re-score: the gather with its widening, the arithmetic
    assert score["gather_s"] > 0 and score["arith_s"] > 0
    assert score["gather_s"] + score["arith_s"] <= score["dur_s"] + 1e-4
    for key in ("buffers_s", "score_s", "order_s", "gather_s", "arith_s"):
        assert stage[key] >= 0  # the stage's own event carries them too


def test_two_threads_calls_keep_separate_accounts(placed, corpus):
    import threading

    def call(rows, batch_size):
        placed.search_certified(corpus[1][:rows], selector="pallas",
                                batch_size=batch_size)

    threads = [threading.Thread(target=call, args=(96, 24)),
               threading.Thread(target=call, args=(48, 48))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = _spans()
    calls = {e["trace_id"]: e for e in spans if e["span"] == "certified.call"}
    assert len(calls) == 2
    for tid, call_span in calls.items():
        mine = [e for e in spans if e["trace_id"] == tid]
        assert Counter(e["span"] for e in mine) == _expected()
        exposed, inflight = _account(mine)
        assert inflight["certified"]["launches"] == call_span["batches"]
        assert exposed["dur_s"] + exposed[
            "inflight_union_s"] == pytest.approx(exposed["call_s"], abs=1e-6)
        assert exposed["call_s"] <= call_span["dur_s"]


def test_the_counted_selectors_name_their_two_passes(corpus):
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K)
    prog.search_certified(corpus[1][:16], selector="exact", batch_size=8)
    spans = _spans()
    exposed, inflight = _account(spans)
    assert set(inflight) == {"counted", "count", "reselect"}
    assert (inflight["counted"]["launches"],
            inflight["count"]["launches"]) == (2, 2)
    assert inflight["counted"]["dur_s"] > 0 and inflight["count"]["dur_s"] > 0
    assert exposed["dur_s"] + exposed["inflight_union_s"] == pytest.approx(
        exposed["call_s"], abs=1e-6)
    # no stage of the pallas path ran, so none of its pieces is recorded
    assert not any(e["span"].startswith(("certified.rank_correct",
                                         "certified.unpack"))
                   for e in spans)


# --- a program's first call ---------------------------------------------
def _first_calls(program=None):
    return [e for e in _spans("program.first_call.")
            if program in (None, e["program"])]


def test_a_programs_first_call_is_recorded_once(corpus):
    # a k and a row count no other test of this process places: both
    # builders' caches miss, so this process has called neither the
    # certified program nor the one that builds the resident row operands
    db = corpus[0][:2900]
    prog = ShardedKNN(db, mesh=make_mesh(1, 1), k=K + 3)
    prog.search_certified(corpus[1], selector="pallas")
    (first,) = _first_calls("certified")
    (built,) = _first_calls("operands")
    assert first["span"] == "program.first_call.certified"
    assert built["span"] == "program.first_call.operands"
    for rec in (first, built):
        assert rec["traces"] >= 1 and rec["backend_compiles"] >= 1
        # each event's OWN seconds, the change over the bracket: none is
        # longer than the bracket, and lowering and the backend's compile,
        # which follow one another, are together at most the bracket.
        # Tracing is not added to them: the interpreted kernels are traced
        # WHILE the program is lowered, JAX counts those seconds under
        # both events, and on a loaded machine the overlap outgrows what
        # the bracket holds besides (the launch itself)
        for own in ("trace_s", "lower_s", "compile_s"):
            assert 0 < rec[own] <= rec["dur_s"] + 1e-3
        assert rec["lower_s"] + rec["compile_s"] <= rec["dur_s"] + 1e-3
        # the suite runs with the persistent cache off
        assert (rec["cache_hits"], rec["cache_misses"]) == (0, 0)
    assert f"k={K + 3}," in first["key"] and "terms=" in first["key"]
    assert "operands=resident" in first["key"]
    assert first["key"].endswith(f",rows={N_QUERIES}")
    tile = prog._operands_cache["key"][0]
    assert built["key"] == f"tile={tile},parts=th+tl,rows=2900"
    # each launch has its own bracket, the build's inside the prepare
    # stage and the program's inside the dispatch: the program's record
    # holds none of the build's compile
    by = {e["span"]: e for e in _spans()}
    assert built["dur_s"] <= by["certified.prepare"]["dur_s"]
    assert first["dur_s"] <= by["certified.dispatch"]["dur_s"]
    assert built["dur_s"] + first["dur_s"] <= by["certified.call"]["dur_s"]
    assert built["trace_id"] == first["trace_id"] == by[
        "certified.dispatch"]["trace_id"]
    # in the registry as any span is
    series = {s["labels"]["span"] for s in
              obs.snapshot()[mn.SPAN_SECONDS]["series"]}
    assert {"program.first_call.certified",
            "program.first_call.operands"} <= series
    obs.reset_event_log(None)
    prog.search_certified(corpus[1], selector="pallas")
    assert _first_calls() == []
    # another placement of the same shape is handed the same programs:
    # it builds its own operands (a launch) with a program already called
    again = ShardedKNN(db, mesh=make_mesh(1, 1), k=K + 3)
    again.search_certified(corpus[1], selector="pallas")
    assert _first_calls() == []
    assert [e["launches"] for e in _spans("certified.inflight.operands")
            ] == [1]


def test_a_new_widen_or_a_new_row_count_is_a_new_reselect_program(tied):
    """The repair's exact re-select is built per widened k, and a jitted
    program is one executable a shape: each width, and each number of
    fallback rows at a width, is first called once (which is what a
    seed with a fallback count no earlier batch had pays for)."""
    prog, q = tied
    seen = []
    for margin, rows in ((17, 4), (17, 4), (19, 4), (19, 3)):
        obs.reset_event_log(None)
        prog.search_certified(q[:rows], selector="pallas", margin=margin)
        seen.append([e["key"] for e in _first_calls("reselect")])
    assert [len(keys) for keys in seen] == [1, 0, 1, 1]
    (a,), _, (b,), (c,) = seen
    assert a != b and a.endswith(",rows=4") and b.endswith(",rows=4")
    assert c == b.replace("rows=4", "rows=3")
    assert a.startswith("k=") and "selector=exact" in a


def test_the_compile_hook_tells_a_cache_hit_from_a_miss(tmp_path):
    """The hook keeps every /jax/compilation_cache/ key and the plain
    occurrences too: with the persistent cache pointed at a directory, a
    program compiled is a miss and the same program after the
    executables were dropped is a hit."""
    code = f"""
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from knn_tpu import obs
from knn_tpu.obs import jax_hooks, names
assert obs.install_compile_hook()
f = lambda v: jnp.sin(v) * 3.0 + 1.0
x = jnp.arange(677.0).block_until_ready()
def since(before):
    return {{k: v - before[k] for k, v in jax_hooks.tallies().items()}}
start = jax_hooks.tallies()
jax.jit(f)(x).block_until_ready()
miss = since(start)
jax.clear_caches()
middle = jax_hooks.tallies()
jax.jit(f)(x).block_until_ready()
hit = since(middle)
assert (miss["cache_misses"], miss["cache_hits"]) == (1, 0), miss
assert (hit["cache_misses"], hit["cache_hits"]) == (0, 1), hit
assert hit["cache_load_s"] > 0 == miss["cache_load_s"]
# JAX times a load under the backend-compile key too: the hits and
# misses beside it are what tells them apart
assert miss["backend_compiles"] == hit["backend_compiles"] == 1
assert miss["traces"] >= 1 and hit["traces"] >= 1
by = {{s["labels"]["event"]: s["value"] for s in
      obs.snapshot()[names.JAX_COMPILES]["series"]}}
assert by["jax_compilation_cache_cache_hits"] == 1, by
assert by["jax_compilation_cache_cache_misses"] >= 1, by
assert by["jax_core_compile_backend_compile_duration"] >= 2, by
secs = {{s["labels"]["event"] for s in
        obs.snapshot()[names.JAX_COMPILE_SECONDS]["series"]}}
assert "jax_compilation_cache_cache_retrieval_time_sec" in secs, secs
assert "jax_compilation_cache_cache_hits" not in secs  # no duration
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ,
                            "JAX_PLATFORMS": "cpu",
                            # the suite's conftest turns the cache off
                            "JAX_ENABLE_COMPILATION_CACHE": "true"})
    assert r.returncode == 0, r.stderr[-2000:]


def test_the_placements_first_call_passes_are_events(corpus):
    """The host passes of a placement's first certified call: the norm
    walk, and the host copy where the caller kept no host array."""
    on_device = jax.device_put(corpus[0])
    from knn_tpu.parallel.collectives import shard
    from knn_tpu.parallel.mesh import db_axes

    mesh = make_mesh(1, 1)
    prog = ShardedKNN(shard(on_device, mesh, db_axes(mesh)), mesh=mesh, k=K)
    prog.search_certified(corpus[1][:8], selector="pallas")
    events = {e["name"]: e for e in obs.get_event_log().recent()
              if e.get("type") == "event"}
    for name in ("placement.device_put", "placement.host_copy",
                 "placement.norm_walk"):
        assert events[name]["rows"] == corpus[0].shape[0], name
        assert events[name]["seconds"] >= 0
    assert events["placement.norm_walk"]["rows_lo_zero"] is False
    obs.reset_event_log(None)
    prog.search_certified(corpus[1][:8], selector="pallas")
    assert not [e for e in obs.get_event_log().recent()
                if e.get("type") == "event"]


def test_the_stage_report_tables_the_start_up(corpus, report, tmp_path):
    log = tmp_path / "events.jsonl"
    obs.reset_event_log(str(log))
    prog = ShardedKNN(corpus[0], mesh=make_mesh(1, 1), k=K + 5)
    for _ in range(2):
        prog.search_certified(corpus[1], selector="pallas")
    obs.reset_event_log(None)
    run = report.run_log_setup(
        "[bench 12:00:00] set-up: drew 3,000 x 32 rows and 2 batches of 96 "
        "queries from seed 7: 0.5 s\n"
        "[bench 12:00:01] set-up: placed: 0.2 s\n"
        "[bench 12:00:03] set-up: first batch (compiles or loads): 2.5 s; "
        "knobs {'tile_n': None}\n"
        "[bench 12:00:04] set-up: warmed 2 batches: 2.6 s\n"
        '{"correct": true, "metrics": {"setup_s": {"value": 3.5, '
        '"unit": "s"}}}\n')
    assert run == {"drew_s": 0.5, "placed_s": 0.2, "first_batch_s": 2.5,
                   "warmed_s": 2.6, "setup_s": 3.5}
    table = report.startup_table(report.read_jsonl(str(log)), run)
    (program,) = [p for p in table["programs"]
                  if p["program"] == "certified"]
    assert (program["program"], program["cache"], program["in_first_call"]
            ) == ("certified", "off", True)
    assert program["traces"] >= 1 and f"k={K + 5}," in program["key"]
    assert [p["event"] for p in table["placement"]] == [
        "placement.device_put", "placement.norm_walk", "placement.operands"]
    assert [p["in_first_call"] for p in table["placement"]] == [True] * 3
    assert (table["placement"][2]["tile"], table["placement"][2]["parts"]
            ) == (3072, "th+tl")
    setup = table["setup"]
    assert sum(setup["rows"].values()) + setup[
        "unaccounted_s"] == pytest.approx(3.5, abs=1e-3)
    assert setup["unaccounted_s"] == pytest.approx(3.5 - 0.5 - 0.2 - 2.6,
                                                   abs=1e-3)
    assert setup["accounted_share"] == pytest.approx(1 - 0.2 / 3.5,
                                                     abs=1e-3)
