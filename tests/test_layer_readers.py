"""What the benchmark reads, the program still has.

``benchmark/layers/*.json`` name the program's series, spans, kernels and
stats keys by their strings; a PR that renames one leaves every later
traced run without that metric (the ledger's ``per_layer: null``) and no
other tier-1 test notices.  This file reads ``benchmark/`` and edits
nothing there:

- every layer file's reader names something the package has: a cataloged
  series with the labels the catalog gives it, a span the package opens,
  a Pallas kernel the package launches (and the ``knn.`` device scope it
  runs under), a reading the drivers hand the harness;
- every ``program_span`` / ``program_counter`` entry of ``BENCHMARK.json``
  whose reader is a ``span`` or a ``counter``, with every query kind among
  its cells: after one tiny CPU call of that kind the registry's change
  over the call holds the series, read by the harness's own reader (a
  bulk self-join call of one block among the kinds: its stages are a
  block's, one record each).
"""

import ast
import glob
import json
import os
import re
import sys

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (os.path.join(ROOT, "benchmark"), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402  (benchmark/)
import system  # noqa: E402  (benchmark/)
from test_certified_spans import SCOPES  # noqa: E402  (tests/)
from test_yfcc_filter import random_bags  # noqa: E402  (tests/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.join import knn_self_join  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import pallas_knn, radius, tagfilter  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402

K, TILE = 10, 1024

def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


LAYERS = {os.path.basename(p)[:-len(".json")]: _json(p)
          for p in sorted(glob.glob(
              os.path.join(ROOT, "benchmark", "layers", "*.json")))}
BENCH = _json("BENCHMARK.json")

#: every device scope the package names: the five of the plain certified
#: program (tests/test_certified_spans.py holds the lowered text to them)
#: and those of the programs only some kinds launch
ALL_SCOPES = SCOPES + (pallas_knn.SCOPE_SELECT_MERGE, sh.SCOPE_MERGE,
                       radius.SCOPE_RANGE_COMPLETE,
                       tagfilter.SCOPE_FILTER_MASK,
                       tagfilter.SCOPE_RANGE_MASK)
#: the device scope under which the operations a trace pattern names run
PATTERN_SCOPE = {
    "^%_bin_candidates(\\.\\d+)? = ": pallas_knn.SCOPE_KERNEL,
    "^%filter_mask(\\.\\d+)? = ": tagfilter.SCOPE_FILTER_MASK,
    "^%range_mask(\\.\\d+)? = ": tagfilter.SCOPE_RANGE_MASK,
    " (collective-permute|all-gather|all-reduce)(-start|-done)?\\(":
        sh.SCOPE_MERGE,
    "\\bu32\\[[0-9]+,": radius.SCOPE_RANGE_COMPLETE,
    # lax.top_k's sort of the candidates against an iota (PR 55)
    "^%sort[.\\d]* = \\(f32\\[\\d+,31744\\]":
        pallas_knn.SCOPE_FINAL_SELECT,
}
#: readings the sweep drivers sum from a certified call's ``stats``
FROM_STATS = ("fallback_queries", "rank_corrected_queries")


def _constants(pattern: str) -> set:
    out = set()
    for path in glob.glob(os.path.join(ROOT, pattern), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        out |= {n.value for n in ast.walk(tree)
                if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    return out


@pytest.fixture(scope="module")
def package_strings():
    return _constants("knn_tpu/**/*.py")


@pytest.fixture(scope="module")
def driver_strings():
    return _constants("benchmark/drivers/*.py")


@pytest.fixture(scope="module")
def kernel_names():
    """What a Pallas kernel of the package shows as in a trace: the
    ``name=`` of its ``pallas_call``, or the function launched."""
    names = set()
    for path in glob.glob(os.path.join(ROOT, "knn_tpu", "ops", "*.py")):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and getattr(
                    node.func, "attr", None) == "pallas_call":
                names |= {kw.value.value for kw in node.keywords
                          if kw.arg == "name"}
    assert callable(pallas_knn._bin_candidates)
    return names | {"_bin_candidates"}


def _mesh():
    return make_mesh(1, 1, devices=jax.devices()[:1])


def _cataloged(selector: dict) -> None:
    labels = mn.CATALOG[selector["name"]][1]
    asked = set(selector.get("labels", {}))
    if "times_label" in selector:
        asked.add(selector["times_label"])
    assert asked <= set(labels), (selector, labels)


def _opened(span: str, strings: set) -> bool:
    """A span the package opens under its own name, or one a call's
    account records (``obs.trace.CallAccount.close``)."""
    if span in strings:
        return True
    root, _, rest = span.partition(".")
    return root in strings and (
        rest == "exposed" or (rest.startswith("inflight.")
                              and rest[len("inflight."):] in strings))


# --- every layer file names something the package has -------------------------
@pytest.mark.parametrize("name", sorted(LAYERS))
def test_a_layer_files_reader_names_what_the_package_has(
        name, package_strings, driver_strings, kernel_names):
    layer = LAYERS[name]
    assert layer["metric"] == name
    rd = layer["reader"]
    assert rd["type"] in harness.READERS
    if rd["type"] == "span":
        kind, labels, _ = mn.CATALOG[rd["series"]]
        assert kind == "histogram" and set(rd["labels"]) <= set(labels)
        assert _opened(rd["labels"]["span"], package_strings)
    elif rd["type"] == "counter":
        for selector in rd["num"] + rd["den"]:
            _cataloged(selector)
    elif rd["type"] == "bench":
        for key in {"value", "num", "den"} & set(rd):
            assert rd[key] in driver_strings, (name, key)
    else:
        for key in ("pattern", "minus_pattern"):
            if key not in rd:
                continue
            re.compile(rd[key])
            scope = PATTERN_SCOPE[rd[key]]
            assert scope.startswith("knn.") and scope in ALL_SCOPES
            kernel = re.match(r"\^%(\w+)\(", rd[key])
            assert kernel is None or kernel.group(1) in kernel_names
        per = rd.get("per", "batches")
        if isinstance(per, str):
            assert per in driver_strings
        else:
            for selector in per:
                _cataloged(selector)
        if "work" in rd:
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", "work", rd["work"] + ".py"))


# --- one tiny call of every kind a cell sends ---------------------------------
def _l2():
    rng = np.random.default_rng(5)
    db = rng.normal(size=(3000, 32)).astype(np.float32)
    prog = ShardedKNN(db, mesh=_mesh(), k=K)
    q = rng.normal(size=(96, 32)).astype(np.float32)
    return lambda: prog.search_certified(q, selector="pallas", tile_n=TILE)


def _metric(metric: str):
    def build():
        rng = np.random.default_rng(31)
        db = rng.normal(size=(3000, 48)).astype(np.float32)
        db *= rng.uniform(0.5, 2.0, size=(3000, 1)).astype(np.float32)
        prog = ShardedKNN(db, mesh=_mesh(), k=K, metric=metric)
        q = rng.normal(size=(48, 48)).astype(np.float32)
        return lambda: prog.search_certified(q, selector="pallas",
                                             tile_n=TILE)
    return build


def _range():
    rng = np.random.default_rng(34)
    db = rng.random((2500, 24), dtype=np.float32)
    prog = ShardedKNN(db, mesh=_mesh(), k=K, train_tile=TILE)
    q = rng.random((48, 24), dtype=np.float32)
    # the 40th distance of one query: lists shorter and longer than K
    radius_sq = float(np.sort(
        ((db.astype(np.float64) - q[3]) ** 2).sum(-1))[39])
    return lambda: prog.range_search_certified(q, radius_sq=radius_sq)


def _filtered():
    rng = np.random.default_rng(40)
    db = rng.integers(0, 256, size=(3000, 24)).astype(np.float32)
    indptr, tags = random_bags(rng, 3000, 2500, 6)
    prog = ShardedKNN(db, mesh=_mesh(), k=K, train_tile=TILE,
                      row_tags=(indptr, tags))
    q = rng.integers(0, 256, size=(48, 24)).astype(np.float32)
    ft = rng.integers(0, 2500, size=(48, 2)).astype(np.int32)
    ft[::2, 1] = -1
    return lambda: prog.search_certified(q, selector="pallas",
                                         filter_tags=ft, tile_n=TILE)


def _ranged():
    """The cosine call under a range on a shuffled attribute, half the
    queries at a range few rows lie in."""
    rng = np.random.default_rng(57)
    db = rng.normal(size=(3000, 48)).astype(np.float32)
    db *= rng.uniform(0.5, 2.0, size=(3000, 1)).astype(np.float32)
    prog = ShardedKNN(db, mesh=_mesh(), k=K, metric="cosine",
                      row_attr=rng.permutation(3000))
    q = rng.normal(size=(48, 48)).astype(np.float32)
    fr = np.tile([[30, 3000], [2970, 3000]], (24, 1))
    return lambda: prog.search_certified(q, selector="pallas",
                                         filter_range=fr, tile_n=TILE)


def _voted():
    rng = np.random.default_rng(48)
    db = rng.normal(size=(3000, 48)).astype(np.float32)
    prog = ShardedKNN(db, mesh=_mesh(), k=K, metric="cosine",
                      labels=rng.integers(0, 30, 3000).astype(np.int32),
                      num_classes=30)
    q = rng.normal(size=(48, 48)).astype(np.float32)
    return lambda: prog.predict_certified(
        q, vote="softmax", temperature=0.07, classes_out=5,
        selector="pallas", tile_n=TILE)


def _self():
    rng = np.random.default_rng(51)
    db = rng.normal(size=(3000, 32)).astype(np.float32)
    db[1500:1520] = db[:20]  # exact copies
    prog = ShardedKNN(db, mesh=_mesh(), k=K)
    return lambda: knn_self_join(prog, rows=(200, 2200))


BUILDERS = {"l2": _l2, "dot": _metric("dot"), "cosine": _metric("cosine"),
            "range": _range, "filtered": _filtered, "ranged": _ranged,
            "voted": _voted, "self": _self}
#: the query kind of a cell, by its traffic file's kind and its metric
KIND_OF_CELL = {}
for _cell in BENCH["workloads"]:
    _traffic = _json("benchmark", "traffic", _cell["traffic"] + ".json")
    _config = _json("benchmark", "configs", _cell["config"] + ".json")
    KIND_OF_CELL[_cell["name"]] = {
        "sweep_range": "range", "sweep_filter": "filtered",
        "sweep_cos_filter": "ranged",
        "sweep_vote": "voted", "graph_build": "self"}.get(
            _traffic["kind"], _config["metric"])

PAIRS = sorted(
    (m["name"], kind)
    for m in BENCH["per_layer"]
    if m["source"] in ("program_span", "program_counter")
    and LAYERS[m["name"]]["reader"]["type"] in ("span", "counter")
    for kind in {KIND_OF_CELL[w] for w in m["workloads"]})


@pytest.fixture(scope="module")
def deltas():
    """By kind, lazily: ``(the registry's change over one warm call, the
    call's stats)``; one placed index a kind."""
    made = {}

    def of(kind: str):
        if kind not in made:
            obs.reset(enabled=True)
            call = BUILDERS[kind]()
            call()  # the first call's one-time passes and compiles
            before = system.registry_snapshot()
            out = call()
            made[kind] = (system.registry_delta(
                before, system.registry_snapshot()), out[-1])
        return made[kind]

    yield of
    obs.reset()


def test_the_cells_are_the_eight_kinds():
    assert set(KIND_OF_CELL.values()) == set(BUILDERS)


@pytest.mark.parametrize("metric,kind", PAIRS)
def test_a_call_of_the_cells_kind_feeds_the_reader(metric, kind, deltas):
    registry, _ = deltas(kind)
    outcome = harness.Outcome(attempted=0, failed=0, end_to_end={},
                              checks=None, bench={}, registry=registry,
                              resident_bytes=0)
    value = harness.read_metric(
        LAYERS[metric], harness.Readings(None, outcome, {}, None))
    assert value is not None and np.isfinite(value) and value >= 0
    rd = LAYERS[metric]["reader"]
    if rd["type"] == "span":
        key = (rd["series"], tuple(sorted(
            (k, str(v)) for k, v in rd["labels"].items())))
        assert registry[key][0] == 1, "one record of the span a call"


def test_a_calls_stats_carry_what_the_drivers_sum(deltas, driver_strings):
    for kind in BUILDERS:
        stats = deltas(kind)[1]
        for key in FROM_STATS:
            assert key in driver_strings and key in stats, (kind, key)
