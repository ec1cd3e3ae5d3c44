"""``predict_certified(vote="softmax")``: the weighted vote of the k-NN
evaluation protocol on the certified path, and the cell that measures
it, ``imagenet-knn768.sweep_vote``.

The path is held to ``benchmark/reference_vote.py`` (float64 numpy,
nothing of ``knn_tpu``) on seeded labelled rows under every selector, on
one and on four CPU devices, and on a BUILT corpus whose queries each
hold one planted case of the vote certificate: two classes whose totals
differ in the 8th digit, a k-th and (k+1)-th row one float32 ulp apart
under different and under equal labels, tight pairs inside the first k
only, fewer than five classes, a zero row, a zero query, duplicates.
Then ``vote="majority"`` against today's composition, the counters, the
span and ``stats``, the controls, the generator, the cell through the
harness at ``tinyroot``'s size, and broken timed paths that each have to
come out ``correct: false``.
"""

import functools
import inspect
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops import vote
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.parallel import sharded as sh

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (HERE, BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import datagen  # noqa: E402  (benchmark/)
import datagen_labels  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference_vote  # noqa: E402
import tiny_vote  # noqa: E402  (benchmark/tests/)
import tinyroot  # noqa: E402
from tiny_vote import CELL  # noqa: E402

K, T, OUT = 20, 0.07, 5
SELECTORS = ["pallas", "approx", "exact"]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "imagenet-knn768.json")
TRAFFIC = _json("benchmark", "traffic", "sweep_vote.json")
BENCH = tinyroot.load_bench()
#: the configuration's law at a test's size: 40 classes of 50 rows
SPEC = {**CONFIG["rows"], "classes": 40, "groups": 4}


def mesh(db_shards: int = 1):
    return make_mesh(1, db_shards, devices=jax.devices()[:db_shards])


def place(db, labels, classes, shards=1, k=K, **kw):
    return ShardedKNN(db, mesh=mesh(shards), k=k, metric="cosine",
                      labels=labels, num_classes=classes, train_tile=1024,
                      **kw)


def softmax(prog, q, selector="pallas", out=OUT, **kw):
    return prog.predict_certified(q, vote="softmax", temperature=T,
                                  classes_out=out, selector=selector, **kw)


def brute(db, labels, q, k, classes, out=OUT, temperature=T):
    """The semantics spelled out, dense: float64 cosines of the rows as
    given (einsum's own loop, so equal rows tie to the bit), the first k
    by (c, index), totals one neighbour at a time, classes by (-total,
    class)."""
    d64, q64 = db.astype(np.float64), q.astype(np.float64)
    den = (np.sqrt((q64 * q64).sum(-1))[:, None]
           * np.sqrt((d64 * d64).sum(-1))[None, :])
    cos = np.zeros_like(den)
    np.divide(np.einsum("qd,nd->qn", q64, d64), den, out=cos, where=den > 0)
    c = 1.0 - cos
    got_c = np.full((len(q), out), -1, np.int64)
    got_t = np.zeros((len(q), out))
    for r in range(len(q)):
        order = np.lexsort((np.arange(len(db)), c[r]))[:k]
        totals = np.zeros(classes)
        for i in order:
            totals[labels[i]] += np.exp((1.0 - c[r, i]) / temperature)
        rank = np.lexsort((np.arange(classes), -totals))[:out]
        there = totals[rank] > 0
        got_c[r, : len(rank)] = np.where(there, rank, -1)
        got_t[r, : len(rank)] = np.where(there, totals[rank], 0)
    return got_c, got_t


@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


# --- seeded rows, every selector, one and four devices ------------------------
@pytest.fixture(scope="module")
def seeded():
    db, labels = datagen_labels.draw_rows(SPEC, 2000, 96, 2**31 + 48,
                                          datagen.STREAM_ROWS)
    q, _ = datagen_labels.draw_queries(SPEC, 48, 96, 2**31 + 48,
                                       datagen.STREAM_QUERIES)
    want = reference_vote.oracle(db, labels, q, K, T, 40, OUT)
    return db, labels, q, want


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("selector", SELECTORS)
def test_the_path_answers_as_the_reference(seeded, selector, shards):
    db, labels, q, (want_c, want_t, _) = seeded
    prog = place(db, labels, 40, shards)
    classes, totals, stats = softmax(prog, q, selector)
    np.testing.assert_array_equal(classes, want_c)
    assert classes.dtype == np.int32 and totals.dtype == np.float64
    cmp = reference_vote.compare(classes, totals, want_c, want_t)
    assert cmp["mismatched_classes"] == 0
    if selector == "pallas":
        # the device's float32 totals, inside the certificate's bound
        assert cmp["total_rel_err_max"] < sh.vote_delta(T, K)
        assert stats["db_shards"] == shards
        assert (stats["certified"] + stats["fallback_queries"]) == len(q)
    else:
        assert cmp["total_rel_err_max"] < 1e-12
        assert stats["vote_repaired_queries"] == len(q)
    assert (stats["vote"], stats["temperature"], stats["classes_out"]) == (
        "softmax", T, OUT)


def test_the_reference_is_the_semantics_spelled_out(seeded):
    db, labels, q, (want_c, want_t, idx) = seeded
    got_c, got_t = brute(db, labels, q, K, 40)
    np.testing.assert_array_equal(want_c, got_c)
    np.testing.assert_allclose(want_t, got_t, rtol=1e-13)
    assert idx.shape == (len(q), K)


# --- the built corpus: one planted case a query -------------------------------
DIM, SIDE = 64, 32  # a case's axis among the first SIDE columns
CASES = ["margin", "boundary", "boundary_same_label", "inside", "few",
         "duplicates", "plain"]
N_CLASSES = 1000


def _ulp_up(row, axis):
    out = row.copy()
    out[axis] = np.nextafter(out[axis], np.float32(np.inf))
    return out


def built_corpus():
    """(rows, labels, queries, the case of each query).  Query j is a
    multiple of the unit vector e_j; its 40 planted rows are ``s * (g e_j
    + sqrt(1 - g^2) u)`` with cosines g from 0.99 down in steps of 0.004,
    u a unit vector in the last columns and s a scale of its own; every
    other row lies in the last columns alone (cosine 0 to every query),
    so a query's window holds its own planted rows and nothing else."""
    rng = np.random.default_rng(2**31 + 481)
    rows, labels = [], []

    def planted(axis, g, label):
        u = np.zeros(DIM)
        u[SIDE:] = rng.normal(size=DIM - SIDE)
        u /= np.linalg.norm(u)
        r = np.zeros(DIM)
        r[axis] = g
        r += np.sqrt(1.0 - g * g) * u
        rows.append((rng.lognormal(0.0, 0.3) * r).astype(np.float32))
        labels.append(label)

    for j, case in enumerate(CASES):
        base = 100 * j
        cosines = 0.99 - 0.004 * np.arange(40)
        # labels of the ranked planted rows: distinct classes by default
        lab = base + np.arange(40)
        if case == "margin":
            lab[:2] = base  # one class of two, then single rows
        elif case in ("boundary", "boundary_same_label"):
            lab[:16] = base  # sixteen of one class, then single rows
        elif case == "inside":
            lab[:8] = base
            lab[8:12] = base + 1
        elif case == "few":
            lab[:] = base + np.arange(40) % 3
        elif case == "duplicates":
            lab[:16] = base
        for g, y in zip(cosines, lab):
            planted(j, g, int(y))
        mine = len(rows) - 40
        if case == "margin":
            # ranks 3 and 4, single rows of two classes, one ulp apart:
            # their totals differ in the 8th digit
            rows[mine + 3] = _ulp_up(rows[mine + 2], j)
        elif case == "boundary":
            # the k-th and the (k+1)-th one ulp apart, labels differ
            rows[mine + K] = _ulp_up(rows[mine + K - 1], j)
        elif case == "boundary_same_label":
            rows[mine + K] = _ulp_up(rows[mine + K - 1], j)
            labels[mine + K] = labels[mine + K - 1]
        elif case == "inside":
            # near ties INSIDE the first k only, under labels that share
            # a class with others: nothing for the certificate to flag
            rows[mine + 5] = _ulp_up(rows[mine + 4], j)
            rows[mine + 10] = _ulp_up(rows[mine + 9], j)
        elif case == "duplicates":
            # the k-th, (k+1)-th and (k+2)-th are ONE row under three
            # labels: the lowest index is the neighbour
            rows[mine + K] = rows[mine + K - 1].copy()
            rows[mine + K + 1] = rows[mine + K - 1].copy()
    n_far = 600
    far = np.zeros((n_far, DIM), np.float32)
    far[:, SIDE:] = rng.normal(size=(n_far, DIM - SIDE))
    db = np.concatenate([np.stack(rows), far])
    labels = np.concatenate([np.asarray(labels, np.int32),
                             rng.integers(900, 1000, n_far).astype(np.int32)])
    order = rng.permutation(len(db))  # index order says nothing
    q = np.zeros((len(CASES), DIM), np.float32)
    q[np.arange(len(CASES)), np.arange(len(CASES))] = 3.7
    return db[order], labels[order], q


@pytest.fixture(scope="module")
def built():
    db, labels, q = built_corpus()
    return db, labels, q, brute(db, labels, q, K, N_CLASSES)


#: case -> (boundary, margin, fallback) of its one query's call
FLAGS = {"margin": (0, 1, 0), "boundary": (1, 0, 0),
         "boundary_same_label": (1, 0, 0), "inside": (0, 0, 0),
         "few": (0, 0, 0), "duplicates": (1, 0, 0), "plain": (0, 0, 0)}


def test_the_oracle_answers_the_built_corpus_as_brute_force_does(built):
    db, labels, q, (want_c, want_t) = built
    got_c, got_t, _ = reference_vote.oracle(db, labels, q, K, T, N_CLASSES,
                                            OUT)
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_allclose(got_t, want_t, rtol=1e-13)
    few = CASES.index("few")
    assert list(want_c[few, 3:]) == [-1, -1] and not want_t[few, 3:].any()
    # the planted near ties are near ties: totals agree to seven digits
    m = CASES.index("margin")
    assert 0 < abs(want_t[m, 1] - want_t[m, 2]) < 1e-6 * want_t[m, 1]


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("selector", SELECTORS)
def test_every_planted_case_is_answered_as_float64_answers_it(
        built, selector, shards):
    db, labels, q, (want_c, want_t) = built
    classes, totals, _ = softmax(place(db, labels, N_CLASSES, shards), q,
                                 selector)
    np.testing.assert_array_equal(classes, want_c)
    np.testing.assert_allclose(totals, want_t, rtol=sh.vote_delta(T, K))


VOTE_FLAGS = ["vote_boundary_queries", "vote_margin_queries",
              "vote_repaired_queries", "fallback_queries", "certified",
              "slack_fallback_queries"]


@pytest.mark.parametrize("corpus,shards,bs", [
    ("seeded", 1, 12), ("seeded", 4, 12), ("built", 1, 2)])
def test_a_voted_call_cut_into_sub_batches_answers_as_the_uncut_call(
        request, corpus, shards, bs):
    """The queries are mapped a sub-batch at a time, each before its
    dispatch: classes, totals and every flag are the uncut call's to the
    bit, and the float64 answer's."""
    db, labels, q, want = request.getfixturevalue(corpus)
    prog = place(db, labels, 40 if corpus == "seeded" else N_CLASSES, shards)
    c1, t1, s1 = softmax(prog, q)
    cn, tn, sn = softmax(prog, q, batch_size=bs)
    assert (s1["batches"], sn["batches"]) == (1, -(-len(q) // bs))
    np.testing.assert_array_equal(c1, want[0])
    np.testing.assert_array_equal(cn, c1)
    np.testing.assert_array_equal(tn, t1)
    assert {f: sn[f] for f in VOTE_FLAGS} == {f: s1[f] for f in VOTE_FLAGS}
    if corpus == "built":  # every planted flag is raised in both
        assert s1["vote_repaired_queries"] == sum(
            any(FLAGS[case]) for case in CASES)


@pytest.mark.parametrize("cut", [4, 1])
def test_a_voted_calls_later_maps_run_under_its_first_launch(
        seeded, fresh_registry, call_order, cut):
    db, labels, q, _ = seeded
    prog = place(db, labels, 40)
    softmax(prog, q)  # the placement's walk and its programs
    obs.reset_event_log(None)
    del call_order[:]
    bs = len(q) // cut
    *_, stats = softmax(prog, q, batch_size=bs)
    assert stats["batches"] == cut
    steps = [s for s in call_order if s != ("launched", "reselect")]
    assert steps[: 2 * cut] == [
        step for lo in range(0, len(q), bs)
        for step in (("map", lo, lo + bs), ("launched", "certified"))]
    (whole,) = [e for e in obs.get_event_log().recent()
                if e.get("span") == "certified.metric_map"]
    assert whole["under_batches"] == cut - 1 and whole["before_s"] > 0
    assert (whole["under_s"] > 0) == (cut > 1) and whole["after_s"] == 0
    assert whole["dur_s"] == pytest.approx(
        whole["before_s"] + whole["under_s"], abs=2e-6)
    under, = [e for e in obs.get_event_log().recent()
              if e.get("span") == "certified.metric_map.under"]
    assert under["parent"] == "certified.metric_map"
    assert under["dur_s"] == pytest.approx(whole["under_s"], abs=1e-6)


@pytest.mark.parametrize("case", CASES)
def test_the_certificate_flags_what_it_must_and_nothing_else(built, case):
    db, labels, q, (want_c, want_t) = built
    j = CASES.index(case)
    classes, totals, stats = softmax(place(db, labels, N_CLASSES),
                                     q[j : j + 1])
    np.testing.assert_array_equal(classes, want_c[j : j + 1])
    got = (stats["vote_boundary_queries"], stats["vote_margin_queries"],
           stats["fallback_queries"])
    assert got == FLAGS[case]
    if any(got):
        # the host's float64 totals
        np.testing.assert_allclose(totals, want_t[j : j + 1], rtol=1e-13)
        assert stats["vote_repaired_queries"] == 1
    else:
        np.testing.assert_allclose(totals, want_t[j : j + 1],
                                   rtol=sh.vote_delta(T, K))
        assert stats["vote_repaired_queries"] == 0
        assert np.abs(totals - want_t[j]).max() > 0  # the device's float32


def test_a_zero_row_and_a_zero_query(built):
    """A zero row has cosine 0 to everything and is placed as it is (the
    device sees it at half its distance): a query with one in its window
    is repaired.  A zero query has cosine 0 to every row: its neighbours
    are the first k rows by index."""
    db, labels, q, _ = built
    db = db.copy()
    j = CASES.index("plain")
    rank = np.argsort(-(db @ q[j]) / np.maximum(
        np.linalg.norm(db, axis=1), 1e-30))
    # cosine 0: the device ranks it where cosine 0.5 would lie, inside
    # this query's window of 37 (its planted rows reach down to 0.83)
    db[rank[45]] = 0.0
    q = np.concatenate([q[j : j + 1], np.zeros((1, DIM), np.float32)])
    want_c, want_t = brute(db, labels, q, K, N_CLASSES)
    prog = place(db, labels, N_CLASSES)
    assert prog._cos_zero_rows.size == 1
    classes, totals, stats = softmax(prog, q)
    np.testing.assert_array_equal(classes, want_c)
    np.testing.assert_allclose(totals, want_t, rtol=sh.vote_delta(T, K))
    assert stats["fallback_queries"] >= 1
    # every neighbour of the zero query at cosine 0: weight exp(0), so
    # its totals are the counts of the first k rows' labels
    counts = np.bincount(labels[:K])
    assert want_c[1, 0] == counts.argmax()
    assert list(want_t[1]) == sorted(counts[counts > 0])[::-1][:OUT]


def test_fewer_candidates_than_classes_out_pad(built):
    db, labels, q, _ = built
    prog = ShardedKNN(db, mesh=mesh(), k=2, metric="cosine", labels=labels,
                      num_classes=N_CLASSES, train_tile=1024)
    want_c, want_t = brute(db, labels, q, 2, N_CLASSES, out=2)
    for selector in SELECTORS:
        classes, totals, _ = prog.predict_certified(
            q, vote="softmax", temperature=T, classes_out=2,
            selector=selector)
        np.testing.assert_array_equal(classes, want_c)
        np.testing.assert_allclose(totals, want_t, rtol=sh.vote_delta(T, 2))


# --- what the call refuses, and what it keeps ----------------------------------
def test_what_the_call_refuses(seeded):
    db, labels, q, _ = seeded
    prog = place(db, labels, 40)
    with pytest.raises(ValueError, match="unknown vote"):
        prog.predict_certified(q, vote="borda")
    with pytest.raises(ValueError, match="temperature"):
        prog.predict_certified(q, vote="softmax")
    with pytest.raises(ValueError, match="temperature"):
        prog.predict_certified(q, vote="softmax", temperature=0.001)
    with pytest.raises(ValueError, match="classes_out"):
        softmax(prog, q, out=K + 1)
    with pytest.raises(ValueError, match="no temperature"):
        prog.predict_certified(q, temperature=T)
    for metric in ("l2", "dot"):
        other = ShardedKNN(db, mesh=mesh(), k=K, metric=metric,
                           labels=labels, num_classes=40)
        with pytest.raises(ValueError, match="cosine placement only"):
            softmax(other, q)
    with pytest.raises(RuntimeError, match="without labels"):
        ShardedKNN(db, mesh=mesh(), k=K, metric="cosine").predict_certified(
            q, vote="softmax", temperature=T)


def _one_hot_majority(neighbor_labels, num_classes):
    """``majority_vote`` as it stood before the K x K form."""
    lab = np.asarray(neighbor_labels)
    out = np.zeros(lab.shape[0], np.int32)
    for r, row in enumerate(lab):
        counts = np.zeros(num_classes, np.int64)
        best, winner = 0, 0
        for y in row:  # the reference's running argmax, strict >
            if 0 <= y < num_classes:
                counts[y] += 1
                if counts[y] > best:
                    best, winner = counts[y], y
        final = counts.max()
        reach = np.zeros(num_classes, np.int64)
        first = {}
        for step, y in enumerate(row):
            if 0 <= y < num_classes:
                reach[y] += 1
                if reach[y] == final and y not in first:
                    first[y] = step
        out[r] = min(first, key=first.get) if first else 0
        assert not first or out[r] == winner or final == 0
    return out


@pytest.mark.parametrize("num_classes", [3, 7, 21, 1000])
@pytest.mark.parametrize("k", [1, 5, 20])
def test_both_forms_of_the_majority_vote_answer_alike(k, num_classes):
    """Tie-heavy labels (six distinct values, some outside the classes):
    the K x K form a call of many classes takes answers as the one-hot
    form does, which is the reference's first-to-reach rule."""
    rng = np.random.default_rng(k * 1009 + num_classes)
    lab = rng.integers(-1, min(num_classes, 5) + 1, size=(400, k)).astype(
        np.int32)
    want = _one_hot_majority(lab, num_classes)
    pairs = np.asarray(vote._majority_vote_pairs(jnp.asarray(lab),
                                                 num_classes))
    np.testing.assert_array_equal(pairs, want)
    np.testing.assert_array_equal(
        np.asarray(vote.majority_vote(jnp.asarray(lab), num_classes)), want)


@pytest.mark.parametrize("selector", SELECTORS)
def test_the_majority_vote_answers_as_before(seeded, selector):
    """``vote="majority"`` (the default) is today's composition to the
    label: the ranked neighbours, the reference's first-to-reach vote."""
    db, labels, q, _ = seeded
    prog = place(db, labels, 40)
    got, stats = prog.predict_certified(q, selector=selector)
    _, idx, _ = prog.search_certified(q, selector=selector)
    np.testing.assert_array_equal(got, _one_hot_majority(labels[idx], 40))
    assert got.dtype == np.int32 and "vote" not in stats
    # the labels are the host's copy, kept at construction
    assert prog._labels_host is not None
    src = inspect.getsource(ShardedKNN.predict_certified)
    assert "np.asarray(self._labels)" not in src


def test_the_classifier_reaches_the_path(seeded):
    import knn_tpu

    db, labels, q, (want_c, _, _) = seeded
    clf = knn_tpu.KNNClassifier(
        k=K, metric="cosine", mesh=mesh(), mode="certified",
        selector="pallas", vote="softmax", temperature=T).fit(db, labels)
    np.testing.assert_array_equal(np.asarray(clf.predict(q)), want_c[:, 0])
    plain = knn_tpu.KNNClassifier(
        k=K, metric="cosine", mesh=mesh(), mode="certified",
        selector="pallas").fit(db, labels)
    got, _ = plain._program.predict_certified(q, selector="pallas")
    np.testing.assert_array_equal(np.asarray(plain.predict(q)), got)
    with pytest.raises(ValueError, match="mode='certified'"):
        knn_tpu.KNNClassifier(k=K, vote="softmax", temperature=T)


# --- counters, span, stats ------------------------------------------------------
def test_counters_span_and_stats(built, fresh_registry):
    db, labels, q, _ = built
    prog = place(db, labels, N_CLASSES)
    classes, totals, stats = softmax(prog, q)
    want = {"device": 0, "boundary": 0, "margin": 0, "fallback": 0}
    for case in CASES:
        b, m, f = FLAGS[case]
        want["fallback" if f else "boundary" if b else "margin" if m
             else "device"] += 1
    series = {s["labels"]["outcome"]: s["value"] for s in
              obs.snapshot()[mn.VOTE_QUERIES]["series"]}
    assert series == {**want, "host": 0}
    assert stats["vote_boundary_queries"] == want["boundary"]
    assert stats["vote_margin_queries"] == want["margin"]
    assert stats["vote_repaired_queries"] == len(CASES) - want["device"]
    assert stats["vote_delta"] == sh.vote_delta(T, K)
    assert 1.1e-4 < stats["vote_delta"] < 1.3e-4
    for key in ("tuning", "pallas_knobs", "sub_batch", "operands",
                "row_steps", "terms", "slack_fallback_queries"):
        assert key in stats
    events = obs.get_event_log().recent()
    call, = [e for e in events if e.get("span") == "certified.call"]
    assert (call["vote"], call["temperature"], call["classes_out"]) == (
        "softmax", T, OUT)
    assert call["vote_boundary_queries"] == want["boundary"]
    repair, = [e for e in events if e.get("span") == "certified.vote_repair"]
    assert repair["queries"] == want["boundary"] + want["margin"]
    # a margin's first k, a boundary's whole window
    w = sh._analysis_window(K, min(K + 28, len(db)))
    assert repair["members"] == want["margin"] * K + want["boundary"] * w
    spans = {s["labels"]["span"]: s["value"] for s in
             obs.snapshot()["knn_tpu_span_seconds"]["series"]}
    for name in ("certified.vote_repair", "certified.dispatch",
                 "certified.device_wait", "certified.d2h",
                 "certified.unpack", "certified.exposed",
                 "certified.inflight.certified", "certified.repair",
                 "certified.metric_map"):
        assert spans[name]["count"] == 1, name
    # what crosses in every call is the answer; a sub-batch with a
    # flagged query sends its windows after it, in a copy and not by a
    # program (PR 49: a program queued behind the later sub-batches)
    d2h = [e for e in events if e.get("span") == "certified.d2h"]
    assert d2h[0]["d2h_bytes"] == (
        len(CASES) * (2 * OUT + 1) * 4 + len(CASES) * w * 4)
    launches = {s["labels"]["program"]: s["value"] for s in
                obs.snapshot()[mn.PROGRAM_LAUNCHES]["series"]}
    assert "vote_rows" not in launches and launches["certified"] == 1
    assert "certified.inflight.vote_rows" not in spans
    # a counted selector's call votes on the host
    softmax(prog, q, "exact")
    series = {s["labels"]["outcome"]: s["value"] for s in
              obs.snapshot()[mn.VOTE_QUERIES]["series"]}
    assert series["host"] == len(CASES)


def test_the_names_are_catalogued_and_documented():
    assert mn.VOTE_QUERIES in mn.CATALOG
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for name in (mn.VOTE_QUERIES, "certified.vote_repair", "knn.vote"):
        assert name in doc, name
    assert sh.SCOPE_VOTE == "knn.vote"


def test_the_device_vote_is_inside_its_named_scope(seeded):
    db, labels, q, _ = seeded
    prog = place(db, labels, 40)
    softmax(prog, q)
    vote_prog, *_ = prog._pallas_setup(
        28, None, "bf16x3", include_distances=False,
        vote=(1.0 / T, OUT, sh.vote_delta(T, K)))
    tail = prog._pallas_operands("bf16x3") + (prog._vote_labels(),)
    text = vote_prog.lower(prog._place_queries(q)[0], prog._tp,
                           *tail).as_text(debug_info=True)
    assert "knn.certify_pack/knn.vote" in text


def test_the_search_programs_are_what_they_were():
    """The vote is a new argument's new branch: the search program's
    jaxpr at the benchmark's shapes is the recorded one."""
    import program_digest  # tests/

    whole = _json("tests", "fixtures", "unfiltered_program_digests.json")
    cell = "text2image2m5.sweep_ip"
    assert program_digest.digest(cell) == whole[cell]


# --- the generator ---------------------------------------------------------------
def test_the_generator():
    spec = CONFIG["rows"]
    sizes = datagen_labels.class_sizes(spec, CONFIG["rows_n"], 7)
    assert sizes.sum() == 1_281_167 and len(sizes) == 1000
    assert 732 <= sizes.min() and sizes.max() == 1300
    assert 30 < (sizes < 1300).sum() < 200
    small = datagen_labels.class_sizes(spec, 3000, 7)
    assert small.sum() == 3000 and small.max() <= 4
    a = datagen_labels.draw_rows(SPEC, 500, 32, 5, datagen.STREAM_ROWS)
    b = datagen_labels.draw_rows(SPEC, 500, 32, 5, datagen.STREAM_ROWS)
    c = datagen_labels.draw_rows(SPEC, 500, 32, 6, datagen.STREAM_ROWS)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and a[1].dtype == np.int32
    assert np.bincount(a[1], minlength=40).min() >= 1
    # row order says nothing of the class, and the norms spread
    assert (np.diff(a[1]) != 0).mean() > 0.9
    norms = np.linalg.norm(a[0], axis=1)
    assert norms.std() / norms.mean() > 0.05
    q, asked = datagen_labels.draw_queries(SPEC, 4000, 32, 5,
                                           datagen.STREAM_QUERIES)
    assert np.bincount(asked, minlength=40).min() > 50  # uniform
    with pytest.raises(ValueError, match="class_gauss_mix"):
        datagen_labels.draw_rows({"dist": "uniform"}, 10, 4, 1, 0)


# --- the files -------------------------------------------------------------------
def test_the_cells_files_agree():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "imagenet-knn768"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [] == list(CONFIG["reduced_from_source"])
    assert entry == BENCH["configs"][7]  # appended, the eighth
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert cell == BENCH["workloads"][7]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "imagenet-knn768", "sweep_vote", 1)
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["k"], CONFIG["metric"],
            CONFIG["classes"], CONFIG["temperature"], CONFIG["classes_out"],
            CONFIG["reference"]) == (
        1_281_167, 768, 20, "cosine", 1000, 0.07, 5, "vote")
    assert (TRAFFIC["kind"], TRAFFIC["batch_rows"], TRAFFIC["pool_batches"],
            TRAFFIC["selector"], TRAFFIC["check_rows"],
            TRAFFIC["trace_seconds"]) == ("sweep_vote", 4096, 12, "pallas",
                                          64, 4)
    assert set(CONFIG["limits"]) == {"mismatched_classes",
                                     "total_rel_err_max"}
    assert set(CONFIG["limits"]) == set(CONFIG["limits_why"])
    assert set(CONFIG["controls"]) == set(reference_vote.CONTROLS)
    for text in (cell["why"], entry["why"], TRAFFIC["what"]):
        assert len(text) <= 200
    (qps,) = [m for m in BENCH["end_to_end"] if m["name"] == "sweep_qps"]
    assert CELL in qps["workloads"]
    listed = [m for m in BENCH["per_layer"] if CELL in m["workloads"]]
    assert {m["name"] for m in listed} == LISTED | NEW
    for m in listed:
        layer = _json("benchmark", "layers", f"{m['name']}.json")
        assert len(m["layer"]) <= 200
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and len(layer["what"]) <= 200
            assert layer["metric"] == m["name"]


LISTED = {"kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
          "idle_pct.sweep", "dispatch_ms", "device_wait_ms", "d2h_ms",
          "unpack_ms",
          # the call's account, listed since PR 53 (a voted call records
          # the three rank_* at 0.0: a constant, not listed here)
          "host_exposed_ms", "reselect_inflight_ms"}
NEW = {"vote_repair_ms", "vote_boundary_pct", "vote_margin_pct"}


# --- the cell through the benchmark's harness -----------------------------------
@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tinyroot``'s copy, this configuration cut in width too (3,000 x
    768 interpreted is slow) and in classes, so that a class has rows
    enough to vote."""
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_vote")))
    path = os.path.join(root, "benchmark", "configs", "imagenet-knn768.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(dim=128, classes=40, rows={**cfg["rows"], "classes": 40,
                                          "groups": 4})
    with open(path, "w") as f:
        json.dump(cfg, f)
    return root


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    # the CPU backend reports no memory; the validator refuses 0
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run_cell(root, traced: bool, seed=2**31 + 48) -> dict:
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.5, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_through_the_harness(root, cpu_memory_reading, traced):
    cell = harness.load_cell(root, CELL)
    assert cell.traffic["kind"] == "sweep_vote" and cell.chips == 1
    out = run_cell(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["compared"]) == {
        "mismatched_classes", "total_rel_err_max", "uncounted_batches",
        "changed_answers", "compiles_in_window"}
    want = {m["name"] for m in lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        assert want == LISTED | NEW
        for name in ("dispatch_ms", "device_wait_ms", "d2h_ms", "unpack_ms",
                     "kernel_ms"):
            assert out["metrics"][name]["value"] > 0, name
        for name in NEW | {"fallback_pct"}:
            assert 0 <= out["metrics"][name]["value"] <= 100, name
        assert out["metrics"]["pallas_knn_roofline"]["value"] <= 105
    else:
        assert want == {"sweep_qps", "setup_s"}


def _answer_with(monkeypatch, wrong):
    """The timed path answers every batch with ``wrong(rows, labels,
    queries, k, classes, out)`` in the program's place."""
    real = ShardedKNN.predict_certified

    @functools.wraps(real)  # the driver asks the signature for the path
    def broken(self, queries, **kw):
        _, _, stats = real(self, queries, **kw)
        classes, totals = wrong(
            self._host_train(), self._labels_host, np.asarray(queries),
            self.k, self.num_classes, kw["classes_out"])
        return classes.astype(np.int32), totals, stats

    monkeypatch.setattr(ShardedKNN, "predict_certified", broken)


def _control(how):
    def wrong(db, labels, q, k, classes, out):
        return reference_vote.control(db, labels, q, k, T, classes, out, how)
    return wrong


def _one_row_too_many(db, labels, q, k, classes, out):
    got_c, got_t, _ = reference_vote.oracle(db, labels, q, k + 1, T, classes,
                                            out)
    return got_c, got_t


@pytest.mark.parametrize("name,wrong,breaks", [
    ("the unweighted vote", _control("majority"), "total_rel_err_max"),
    ("float32 ranking and totals, no certificate", _control("f32"),
     "total_rel_err_max"),
    ("a vote over k + 1 rows", _one_row_too_many, "total_rel_err_max"),
])
def test_a_broken_timed_path_is_not_correct(root, cpu_memory_reading,
                                            monkeypatch, name, wrong, breaks):
    _answer_with(monkeypatch, wrong)
    out = run_cell(root, False)
    assert out["correct"] is False, name
    row = out["compared"][breaks]
    assert not row["value"] <= row["limit"], name


def test_swapped_classes_are_not_correct(root, cpu_memory_reading,
                                         monkeypatch):
    tiny_vote._break_sweep_vote(monkeypatch)
    out = run_cell(root, False)
    assert out["correct"] is False
    assert out["compared"]["mismatched_classes"]["value"] > 0


@pytest.mark.parametrize("how", reference_vote.CONTROLS)
def test_every_control_breaks_what_the_configuration_names(how):
    """``control_vote.py``'s comparison at a test's size (the rows' law
    at 20,000 x 768 and the cell's k, T and classes; at the cell's own
    size it runs on the host in a minute a seed)."""
    spec = {**CONFIG["rows"], "classes": 100, "groups": 5}
    db, labels = datagen_labels.draw_rows(spec, 20_000, 768, 2**31 + 5,
                                          datagen.STREAM_ROWS)
    q, _ = datagen_labels.draw_queries(spec, 64, 768, 2**31 + 5,
                                       datagen.STREAM_QUERIES)
    args = (db, labels, q, K, T, 100, OUT)
    want_c, want_t, _ = reference_vote.oracle(*args)
    got_c, got_t = reference_vote.control(*args, how)
    cmp = reference_vote.compare(got_c, got_t, want_c, want_t)
    broke = {name for name, limit in CONFIG["limits"].items()
             if not cmp[name] <= limit}
    assert set(CONFIG["controls"][how]) <= broke, (how, cmp)


def test_a_program_without_the_path_fails_before_anything_is_drawn(
        root, monkeypatch):
    """What the parent commit meets under this PR's benchmark files."""
    def parents(self, queries, *, margin=28, selector="approx",
                batch_size=None, tile_n=None, precision=None, kernel=None,
                tune_cache=None):
        raise AssertionError("never called")

    monkeypatch.setattr(ShardedKNN, "predict_certified", parents)
    drawn = []
    monkeypatch.setattr(datagen_labels, "draw_rows",
                        lambda *a, **kw: drawn.append(a))
    t0 = time.perf_counter()
    with pytest.raises(harness.BenchError, match="no weighted vote"):
        harness.run_cell(root, CELL, 1, 1.0, False, time.perf_counter())
    assert time.perf_counter() - t0 < 1.0 and not drawn
