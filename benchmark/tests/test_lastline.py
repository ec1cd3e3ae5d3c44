"""lastline.validate accepts a good line and refuses each way a line
can be bad, in both modes, for every cell BENCHMARK.json holds (and the
open-loop cell the tests add: ``tinyroot.load_bench``)."""

import copy
import json

import pytest

import lastline
import tinyroot

BENCH = tinyroot.load_bench()
CELLS = [c["name"] for c in BENCH["workloads"]]


def good(workload: str, traced: bool) -> dict:
    metrics = {m["name"]: {"value": 12.5, "unit": m["unit"]}
               for m in lastline.required_metrics(BENCH, workload, traced)}
    device = {"platform": "tpu", "kind": "TPU v5 lite",
              "count": lastline.cell_of(BENCH, workload)["chips"],
              "memory_peak_bytes": 8_500_000_000}
    line = {"correct": True, "attempted": 400, "failed": 0,
            "metrics": metrics, "device": device}
    if traced:
        device.update(window_s=4.0, busy_s=2.5)
        line["breakdown"] = {"device_ops": [["fusion.1", 1.5]],
                             "idle_gaps": [["knn.certified.unpack", 0.7]]}
    line["compared"] = {"mismatched_rows": {
        "value": 0.0, "limit": 0.0, "rule": "<="}}
    return line


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("workload", CELLS)
def test_a_good_line_is_accepted(workload, traced):
    line = good(workload, traced)
    assert lastline.validate(json.dumps(line), BENCH, workload, traced) == line
    assert line["metrics"], "the cell reports nothing in this mode"


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_reports_setup_and_more(workload):
    e2e = [m["name"] for m in lastline.end_to_end_of(BENCH, workload)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert lastline.per_layer_of(BENCH, workload)


def _drop(key):
    return lambda line: line.pop(key)


def _set(path, value):
    def change(line):
        obj = line
        for key in path[:-1]:
            obj = obj[key]
        obj[path[-1]] = value
    return change


def _first_metric(line):
    return next(iter(line["metrics"]))


BAD_ANY_MODE = {
    "missing correct": _drop("correct"),
    "missing attempted": _drop("attempted"),
    "missing failed": _drop("failed"),
    "missing metrics": _drop("metrics"),
    "missing device": _drop("device"),
    "correct not a boolean": _set(["correct"], "true"),
    "attempted negative": _set(["attempted"], -1),
    "attempted a float": _set(["attempted"], 4.5),
    "attempted zero": _set(["attempted"], 0),
    "failed over attempted": _set(["failed"], 401),
    "a listed metric absent":
        lambda line: line["metrics"].pop(_first_metric(line)),
    "a metric not value-and-unit":
        lambda line: line["metrics"].update({_first_metric(line): 3.0}),
    "a metric value null": lambda line: line["metrics"][
        _first_metric(line)].update(value=None),
    "a metric value a string": lambda line: line["metrics"][
        _first_metric(line)].update(value="12.5"),
    "a metric value a boolean": lambda line: line["metrics"][
        _first_metric(line)].update(value=True),
    "a metric with another unit": lambda line: line["metrics"][
        _first_metric(line)].update(unit="furlongs"),
    "an extra metric that is null": lambda line: line["metrics"].update(
        extra={"value": None, "unit": "s"}),
    "device.platform missing": lambda line: line["device"].pop("platform"),
    "device.kind empty": _set(["device", "kind"], ""),
    "device.count zero": _set(["device", "count"], 0),
    "device.memory_peak_bytes missing":
        lambda line: line["device"].pop("memory_peak_bytes"),
    "device.memory_peak_bytes zero": _set(["device", "memory_peak_bytes"], 0),
    "device.memory_peak_bytes a float":
        _set(["device", "memory_peak_bytes"], 8.5e9),
    "compared not the last key":
        lambda line: line.update(correct=line.pop("correct")),
    "compared empty": _set(["compared"], {}),
    "a compared number without its limit":
        lambda line: line["compared"]["mismatched_rows"].pop("limit"),
    "a compared limit that is no number":
        _set(["compared", "mismatched_rows", "limit"], "0"),
}
BAD_TRACED = {
    "busy_s zero": _set(["device", "busy_s"], 0.0),
    "busy_s negative": _set(["device", "busy_s"], -0.1),
    "busy_s over window_s": _set(["device", "busy_s"], 4.000001),
    "busy_s missing": lambda line: line["device"].pop("busy_s"),
    "window_s missing": lambda line: line["device"].pop("window_s"),
    "window_s null": _set(["device", "window_s"], None),
    "breakdown with another key": _set(["breakdown", "host_ops"], []),
    "breakdown with 11 rows":
        _set(["breakdown", "device_ops"], [["op", 0.1]] * 11),
    "breakdown row not a pair": _set(["breakdown", "idle_gaps"], [["call"]]),
    "breakdown seconds a string":
        _set(["breakdown", "idle_gaps"], [["call", "0.7"]]),
}
BAD_UNTRACED = {
    "breakdown in an untraced run":
        _set(["breakdown"], {"device_ops": [], "idle_gaps": []}),
    "an end-to-end metric of 0": lambda line: line["metrics"][
        _first_metric(line)].update(value=0.0),
}


def _cases():
    for workload in CELLS:
        for traced in (False, True):
            table = {**BAD_ANY_MODE,
                     **(BAD_TRACED if traced else BAD_UNTRACED)}
            for why, change in table.items():
                yield pytest.param(workload, traced, change,
                                   id=f"{workload}-trace{int(traced)}-{why}")


@pytest.mark.parametrize("workload,traced,change", list(_cases()))
def test_a_bad_line_is_refused(workload, traced, change):
    line = copy.deepcopy(good(workload, traced))
    change(line)
    with pytest.raises(lastline.LastLineError):
        lastline.validate(json.dumps(line), BENCH, workload, traced)


@pytest.mark.parametrize("text", [
    "", "not json", "[1, 2]", '"a string"', "{\"correct\": true}\n{}",
    '{"correct": true, "attempted": 4, "failed": 0, "metrics": NaN, '
    '"device": {}}',
])
def test_what_is_not_one_json_object_is_refused(text):
    with pytest.raises(lastline.LastLineError):
        lastline.validate(text, BENCH, CELLS[0], False)


@pytest.mark.parametrize("value", [float("nan"), float("inf"),
                                   -float("inf")])
@pytest.mark.parametrize("traced", [False, True])
def test_a_value_that_is_not_finite_cannot_be_built_or_read(value, traced):
    workload = CELLS[0]
    line = good(workload, traced)
    name = _first_metric(line)
    units = {k: v["unit"] for k, v in line["metrics"].items()}
    values = {k: v["value"] for k, v in line["metrics"].items()}
    values[name] = value
    with pytest.raises(lastline.LastLineError):
        lastline.build(correct=True, attempted=4, failed=0, values=values,
                       units=units, device=line["device"])
    line["metrics"][name]["value"] = value
    with pytest.raises(lastline.LastLineError):  # json.dumps writes NaN
        lastline.validate(json.dumps(line), BENCH, workload, traced)


def test_build_then_validate_round_trips():
    workload, traced = CELLS[0], True
    line = good(workload, traced)
    text = lastline.build(
        correct=True, attempted=400, failed=0,
        values={k: v["value"] for k, v in line["metrics"].items()},
        units={k: v["unit"] for k, v in line["metrics"].items()},
        device=line["device"], breakdown=line["breakdown"],
        compared=[{"check": "mismatched_rows", "value": 0.0, "limit": 0.0,
                   "rule": "<=", "ok": True}])
    assert lastline.validate(text, BENCH, workload, traced) == line
    assert list(json.loads(text))[-1] == "compared"


def test_a_compared_number_that_is_not_finite_is_written_as_text():
    line = good(CELLS[0], False)
    text = lastline.build(
        correct=False, attempted=400, failed=0,
        values={k: v["value"] for k, v in line["metrics"].items()},
        units={k: v["unit"] for k, v in line["metrics"].items()},
        device=line["device"],
        compared=[{"check": "dist_rel_err_max", "value": float("nan"),
                   "limit": 3.8e-6, "rule": "<=", "ok": False}])
    out = lastline.validate(text, BENCH, CELLS[0], False)
    assert out["compared"]["dist_rel_err_max"]["value"] == "nan"


def test_a_roofline_share_over_105_is_refused():
    shares = [m for m in BENCH["per_layer"] if "roofline" in m["name"]]
    assert shares, "the benchmark names no roofline share"
    workload = shares[0]["workloads"][0]
    line = good(workload, True)
    line["metrics"][shares[0]["name"]]["value"] = 105.5
    with pytest.raises(lastline.LastLineError, match="over 105"):
        lastline.validate(json.dumps(line), BENCH, workload, True)


def test_an_unknown_workload_is_refused():
    with pytest.raises(lastline.LastLineError):
        lastline.validate(json.dumps(good(CELLS[0], False)), BENCH,
                          "no.such-cell", False)
