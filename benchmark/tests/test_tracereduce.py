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


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ev = [("a", 1.0 * S, 1.0 * S), ("b", 3.0 * S, 0.5 * S)]
    spans = [WINDOW, ("bench.call", 1.0 * S, 2.6 * S),
             ("bench.host-after-batch", 3.6 * S, 0.3 * S)]
    red = tr.reduce(extracted(ev, spans), "^XLA Ops$")
    gaps = dict(red.idle_gaps())
    assert gaps["call"] == pytest.approx(1.0)           # [2, 3]
    # [3.5, 5]: one gap, its middle (4.25) under no span
    assert gaps["outside-spans"] == pytest.approx(1.5)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


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
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    ex = tr.read_xplane(tr.find_xplane(str(tmp_path)), "^/host:CPU$")
    assert {s[0] for s in ex["host_spans"]} == {tr.WINDOW_SPAN, "bench.call"}
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
