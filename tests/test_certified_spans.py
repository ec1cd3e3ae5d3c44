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
import subprocess
import sys
from collections import Counter

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.obs import trace as obs_trace
from knn_tpu.obs.profiler import device_trace
from knn_tpu.ops import pallas_knn
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


def _spans():
    return [e for e in obs.get_event_log().recent()
            if e.get("type") == "span"]


PER_BATCH = ("certified.dispatch", "certified.device_wait", "certified.d2h",
             "certified.unpack", "certified.rank_correct")


def _expected(batches: int, reselects: int = 0) -> Counter:
    want = Counter({"certified.call": 1, "certified.prepare": 1,
                    "certified.repair": 1})
    for name in PER_BATCH:
        want[name] = batches
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
    assert Counter(e["span"] for e in spans) == _expected(batches)

    tids = {e.get("trace_id") for e in spans}
    assert len(tids) == 1 and None not in tids
    by = {e["span"]: e for e in spans}
    call = by["certified.call"]
    assert "parent" not in call
    assert (call["selector"], call["queries"], call["batches"]) == (
        "pallas", N_QUERIES, batches)
    children = [e for e in spans if e["span"] != "certified.call"]
    assert {e["parent"] for e in children} == {"certified.call"}
    # self time = length less the children's: never negative
    assert sum(e["dur_s"] for e in children) <= call["dur_s"] + 1e-4

    assert by["certified.prepare"]["first_call"] is False
    assert by["certified.dispatch"]["h2d_bytes"] == (
        N_QUERIES // batches * 32 * 4)
    assert by["certified.d2h"]["d2h_bytes"] > 0
    corrections = [e for e in spans if e["span"] == "certified.rank_correct"]
    assert sum(e["queries_corrected"] for e in corrections) == stats[
        "rank_corrected_queries"]
    for e in corrections:
        # a tight pair involves two positions; at 32 columns one block
        # holds 32,768 members, so a batch here is one block or none
        assert e["members"] >= 2 * e["queries_corrected"]
        assert e["blocks"] == (1 if e["members"] else 0)
    assert by["certified.repair"]["fallback_queries"] == 0
    assert by["certified.repair"]["host_exact_queries"] == 0
    # the same spans feed the histogram an operator scrapes
    series = {s["labels"]["span"]: s["value"]["count"]
              for s in obs.snapshot()[mn.SPAN_SECONDS]["series"]}
    assert series["certified.device_wait"] == batches
    assert series["certified.call"] == 1


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
    with device_trace("spans", base_dir=str(tmp_path)) as path:
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
    assert Counter(n for n, _, _ in inner) == Counter(
        {f"knn.{name}": c for name, c in _expected(3).items()})
    (call,) = [e for e in inner if e[0] == "knn.certified.call"]
    assert outer[1] <= call[1] and call[2] <= outer[2]
    for name, start, end in inner:
        assert call[1] <= start and end <= call[2], name


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
    tail = placed._pallas_operands("bf16x3")
    out = {}
    for kernel in ("tiled", "streaming"):
        prog, _, _, _ = placed._pallas_setup(28, None, "bf16x3",
                                             kernel=kernel)
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
    assert set(table["stages_ms"]) == set(_expected(3))
    assert table["stages_ms"]["certified.d2h"]["spans"] == 6
    assert table["stages_ms"]["certified.call"]["spans"] == 2
    assert 0 < table["children_share_of_call"] <= 1
    assert table["call_self_ms_per_call"] >= 0
    assert table["per_batch"]["h2d_bytes"] == 32 * 32 * 4
    assert table["per_batch"]["members"] >= 2 * table["per_batch"][
        "queries_corrected"]
    assert 0 <= table["per_batch"]["blocks"] <= 1
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
