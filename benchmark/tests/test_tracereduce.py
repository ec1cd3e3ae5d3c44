"""The reduction from a trace to busy time, operation time, top
operations and idle gaps: on events written out by hand, on a trace the
CPU backend writes here, and on the recordings cut from real v5e traces
(``data/*.json.gz``)."""

import glob
import gzip
import json
import os

import pytest

import tracereduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
S = 1e9  # ns


def extracted(events, spans, line="XLA Ops", extra_lines=()):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "Steps", "events": [["step", 0, 10 * S]]},
        {"name": line, "events": [list(e) for e in events]},
        *extra_lines]}],
        "host_spans": [list(s) for s in spans], "seen": {}}


WINDOW = ("bench.trace_window", 1 * S, 4 * S)  # [1 s, 5 s]


def test_busy_is_the_union_clipped_to_the_window():
    ev = [("a", 0.5 * S, 1.0 * S),   # [0.5, 1.5] -> 0.5 inside
          ("b", 2.0 * S, 1.0 * S),   # [2, 3]
          ("c", 2.5 * S, 1.0 * S),   # [2.5, 3.5] overlaps b
          ("d", 4.8 * S, 1.0 * S),   # [4.8, 5.8] -> 0.2 inside
          ("e", 7.0 * S, 1.0 * S)]   # outside
    red = tr.reduce(extracted(ev, [WINDOW]), "^XLA Ops$")
    assert red.window_s == pytest.approx(4.0)
    assert red.busy_s == pytest.approx(0.5 + 1.5 + 0.2)
    assert red.busy_s <= red.window_s


def test_only_the_named_line_counts():
    other = {"name": "XLA Modules", "events": [["module", 1 * S, 4 * S]]}
    red = tr.reduce(extracted([("a", 2 * S, 1 * S)], [WINDOW],
                              extra_lines=[other]), "^XLA Ops$")
    assert red.busy_s == pytest.approx(1.0)


def test_op_seconds_by_pattern_and_none_where_nothing_matches():
    ev = [("kernel.1", 1.0 * S, 0.5 * S), ("fusion.2", 1.5 * S, 0.25 * S),
          ("kernel.1", 3.0 * S, 0.5 * S)]
    red = tr.reduce(extracted(ev, [WINDOW]), "^XLA Ops$")
    assert red.op_seconds("^kernel") == pytest.approx(1.0)
    assert red.op_seconds("fusion") == pytest.approx(0.25)
    assert red.op_seconds("no-such-op") is None


def test_top_ops_give_a_loop_its_own_time_only():
    ev = [("while.1", 1.0 * S, 2.0 * S),          # holds body.1 twice
          ("body.1", 1.1 * S, 0.8 * S), ("body.1", 2.0 * S, 0.9 * S),
          ("copy.3", 3.5 * S, 0.4 * S)]
    red = tr.reduce(extracted(ev, [WINDOW]), "^XLA Ops$")
    top = dict(red.top_ops())
    assert top["body.1"] == pytest.approx(1.7)
    assert top["while.1"] == pytest.approx(0.3)
    assert top["copy.3"] == pytest.approx(0.4)
    assert [name for name, _ in red.top_ops(2)] == ["body.1", "copy.3"]
    assert red.busy_s == pytest.approx(2.4)


def test_idle_gaps_are_cut_at_the_host_spans_edges():
    ev = [("a", 1.0 * S, 1.0 * S), ("b", 3.0 * S, 0.5 * S)]
    spans = [WINDOW, ("bench.call", 1.0 * S, 2.6 * S),
             ("bench.host-after-batch", 3.6 * S, 0.3 * S)]
    red = tr.reduce(extracted(ev, spans), "^XLA Ops$")
    gaps = dict(red.idle_gaps())
    # [2, 3], and of the gap [3.5, 5] the 0.1 s before bench.call ends
    assert gaps["call"] == pytest.approx(1.0 + 0.1)
    assert gaps["host-after-batch"] == pytest.approx(0.3)
    # a moment under no span but the window's own
    assert gaps["outside-spans"] == pytest.approx(1.5 - 0.1 - 0.3)
    assert tr.WINDOW_SPAN not in gaps and "trace_window" not in gaps
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


#: one certified call as the program annotates it, inside the benchmark's
#: own span: three spans deep over the gap [2, 3]
NESTED = [("bench.call", 1.0 * S, 2.6 * S),
          ("knn.certified.call", 1.1 * S, 2.4 * S),
          ("knn.certified.device_wait", 1.2 * S, 1.0 * S),     # to 2.2
          ("knn.certified.unpack", 2.2 * S, 0.3 * S),          # to 2.5
          ("knn.certified.repair", 2.6 * S, 0.6 * S),          # to 3.2
          ("knn.certified.repair.reselect", 2.7 * S, 0.2 * S)]  # to 2.9


def test_a_gap_across_nested_spans_is_split_at_their_edges():
    ev = [("a", 1.0 * S, 1.0 * S), ("b", 3.0 * S, 2.0 * S)]
    red = tr.reduce(extracted(ev, [WINDOW, *NESTED]), "^XLA Ops$")
    gaps = dict(red.idle_gaps())
    assert gaps == pytest.approx({
        "knn.certified.device_wait": 0.2, "knn.certified.unpack": 0.3,
        # [2.5, 2.6] lies under the call alone: a knn. span inside
        # bench.call wins, and the bench. span holds nothing here
        "knn.certified.call": 0.1,
        "knn.certified.repair": 0.1 + 0.1,
        "knn.certified.repair.reselect": 0.2})
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_equal_lengths_go_to_the_later_start():
    spans = [("knn.first", 1.0, 2.0), ("knn.second", 2.0, 2.0)]
    assert tr.attribute([(0.5, 4.5)], spans) == pytest.approx({
        tr.OUTSIDE: 0.5 + 0.5, "knn.first": 1.0, "knn.second": 2.0})
    assert tr.attribute([(2.0, 3.0)], spans[::-1]) == {"knn.second": 1.0}


def test_the_rows_are_the_ten_longest_and_sum_to_the_first_chips_idle_time():
    ev = [("a", 1.0 * S, 0.5 * S)]
    spans = [WINDOW] + [(f"knn.certified.s{j}", (2.0 + 0.2 * j) * S,
                         (0.01 + 0.01 * j) * S) for j in range(12)]
    ex = extracted(ev, spans)
    second = json.loads(json.dumps(ex["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["a", 1.0 * S, 3.5 * S]]
    ex["planes"].append(second)
    red = tr.reduce(ex, "^XLA Ops$")
    assert len(red.idle_gaps()) == 10
    gaps = dict(red.idle_gaps(20))
    assert len(gaps) == 13 and [n for n, _ in red.idle_gaps(2)] == [
        "outside-spans", "knn.certified.s11"]
    # of the first chip's plane, not the mean's
    assert sum(gaps.values()) == pytest.approx(4.0 - 0.5)
    assert red.busy_s == pytest.approx((0.5 + 3.5) / 2)


def _stage_report():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(HERE)), "scripts",
                        "certified_stage_report.py")
    spec = importlib.util.spec_from_file_location("stage_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("gaps", [
    [(2.0 * S, 3.0 * S)], [(0.5 * S, 1.05 * S), (2.45 * S, 2.65 * S)],
    [(0.0, 5.0 * S)], [(3.3 * S, 3.4 * S), (3.7 * S, 4.0 * S)]],
    ids=["nested", "edges", "everything", "outside"])
def test_the_programs_own_report_lays_the_same_gaps_alike(gaps):
    """``scripts/certified_stage_report.py idle`` is the arithmetic this
    was taken from; until it calls this one, the two agree."""
    theirs = _stage_report().attribute(
        gaps, [(name, lo, lo + dur) for name, lo, dur in NESTED])
    theirs[tr.OUTSIDE] = theirs.pop("outside", 0.0)
    mine = tr.attribute(gaps, NESTED)
    assert {k: v for k, v in theirs.items() if v} == pytest.approx(mine)
    assert sum(mine.values()) == pytest.approx(
        sum(hi - lo for lo, hi in gaps))


@pytest.mark.parametrize("change,why", [
    (lambda ex: ex.update(host_spans=[]), "no bench.trace_window"),
    (lambda ex: ex.update(planes=[]), "no device plane"),
    (lambda ex: ex["planes"][0]["lines"].pop(1), "no line matching"),
    (lambda ex: ex["planes"][0]["lines"][1].update(
        events=[["late", 9 * S, 1 * S]]), "falls inside"),
])
def test_a_trace_that_cannot_answer_is_an_error(change, why):
    ex = extracted([("a", 2 * S, 1 * S)], [WINDOW])
    change(ex)
    with pytest.raises(tr.TraceError, match=why):
        tr.reduce(ex, "^XLA Ops$")


def test_mean_over_chips():
    ex = extracted([("a", 2 * S, 1 * S)], [WINDOW])
    second = json.loads(json.dumps(ex["planes"][0]))
    second["name"] = "/device:TPU:1"
    second["lines"][1]["events"] = [["a", 2 * S, 2 * S]]
    ex["planes"].append(second)
    red = tr.reduce(ex, "^XLA Ops$")
    assert red.busy_s == pytest.approx(1.5)
    assert red.op_seconds("^a$") == pytest.approx(1.5)


def test_read_xplane_on_a_trace_written_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                with jax.profiler.TraceAnnotation("knn.certified.call"):
                    with jax.profiler.TraceAnnotation("other.span"):
                        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ex = tr.read_xplane(tr.find_xplane(str(tmp_path)), "^/host:CPU$")
    assert {s[0] for s in ex["host_spans"]} == {
        tr.WINDOW_SPAN, "bench.call", "knn.certified.call"}
    assert tr.describe(ex)["host_spans"] == sorted({
        tr.WINDOW_SPAN, "bench.call", "knn.certified.call"})
    red = tr.reduce(ex, "^tf_XLAPjRtCpuClient")
    assert 0 < red.busy_s <= red.window_s
    assert red.op_seconds("dot") is not None
    assert tr.describe(ex)["planes"]


RECORDINGS = sorted(glob.glob(os.path.join(HERE, "data", "*.json.gz")))


def test_there_is_a_recording_of_a_real_trace():
    assert RECORDINGS


@pytest.mark.parametrize("path", RECORDINGS,
                         ids=[os.path.basename(p) for p in RECORDINGS])
def test_recorded_v5e_trace(path):
    with gzip.open(path, "rt") as f:
        ex = json.load(f)
    with open(path[:-len(".json.gz")] + ".expect.json") as f:
        want = json.load(f)
    red = tr.reduce(ex, want["line_re"])
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red.busy_s <= red.window_s
    for pattern, secs in want["op_seconds"].items():
        assert red.op_seconds(pattern) == pytest.approx(secs, rel=1e-9)
    assert [n for n, _ in red.top_ops(3)] == want["top3"]
    gaps = red.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    # a window the device was busy all through has no gap to name
    assert (gaps[0][0] if gaps else None) == want["longest_gap"]
