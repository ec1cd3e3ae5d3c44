"""Bounded-retry fault injection for the sharded search paths (SURVEY §5
failure row; VERDICT r3 item 8): a transient device error inside a long
sweep must be retried per batch — on the dispatch side (the program call
raises) and on the fetch side (the async error surfaces at np.asarray) —
without killing the job or changing the exact result.  Caller bugs
(ValueError/TypeError) must NOT be retried.
"""

from collections import Counter

import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.parallel import sharded as sh
from knn_tpu.parallel.mesh import make_mesh
from knn_tpu.parallel.sharded import ShardedKNN


def _oracle(db, queries, k):
    d = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None])
         ** 2).sum(-1)
    idx = np.lexsort(
        (np.broadcast_to(np.arange(db.shape[0]), d.shape), d), axis=-1
    )[:, :k]
    return np.take_along_axis(d, idx, axis=-1), idx


@pytest.fixture
def data(rng):
    db = (rng.random((500, 12)) * 20).astype(np.float32)
    q = (rng.random((10, 12)) * 20).astype(np.float32)
    return db, q


class _FlakyArray:
    """Defers to a real array but raises ONCE at host-fetch time —
    models an async device failure surfacing at the transfer."""

    def __init__(self, arr, state):
        self._arr = arr
        self._state = state

    def __array__(self, dtype=None, copy=None):
        if not self._state["tripped"]:
            self._state["tripped"] = True
            raise RuntimeError("injected async device failure")
        a = np.asarray(self._arr)
        return a.astype(dtype) if dtype is not None else a


def test_search_retries_dispatch_failure(data, monkeypatch):
    db, q = data
    real = sh._knn_program
    state = {"fails": 1}

    def flaky_knn_program(*a, **kw):
        prog = real(*a, **kw)

        def wrapper(*pa, **pkw):
            if state["fails"] > 0:
                state["fails"] -= 1
                raise RuntimeError("injected dispatch failure")
            return prog(*pa, **pkw)

        return wrapper

    monkeypatch.setattr(sh, "_knn_program", flaky_knn_program)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
    _, ref_i = _oracle(db, q, 5)
    _, i = prog.search(q)
    np.testing.assert_array_equal(np.asarray(i), ref_i)
    assert state["fails"] == 0  # the injection actually fired


def test_certified_counted_retries_fetch_failure(data, monkeypatch):
    db, q = data
    real = sh._knn_program
    state = {"tripped": False}

    def flaky_knn_program(*a, **kw):
        prog = real(*a, **kw)

        def wrapper(*pa, **pkw):
            d, i = prog(*pa, **pkw)
            if not state["tripped"]:
                return d, _FlakyArray(i, state)
            return d, i

        return wrapper

    monkeypatch.setattr(sh, "_knn_program", flaky_knn_program)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
    _, ref_i = _oracle(db, q, 5)
    d, i, stats = prog.search_certified(q, selector="exact", margin=6)
    np.testing.assert_array_equal(i, ref_i)
    assert state["tripped"]


def test_certified_pallas_retries_fetch_failure(data, monkeypatch):
    db, q = data
    real = sh._pallas_certified_program
    state = {"tripped": False}

    def flaky_pallas_program(*a, **kw):
        prog = real(*a, **kw)

        def wrapper(*pa, **pkw):
            out = prog(*pa, **pkw)
            if not state["tripped"]:
                return _FlakyArray(out, state)
            return out

        return wrapper

    monkeypatch.setattr(sh, "_pallas_certified_program", flaky_pallas_program)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
    _, ref_i = _oracle(db, q, 5)
    d, i, stats = prog.search_certified(q, selector="pallas", margin=6)
    np.testing.assert_array_equal(i, ref_i)
    assert state["tripped"]


class _FlakyReady:
    """A device output whose failure surfaces ONCE at the stage named:
    while the host waits for the device (``block_until_ready``) or at
    the copy to the host (``__array__``)."""

    def __init__(self, arr, state, stage):
        self._arr, self._state, self._stage = arr, state, stage

    def _trip(self, stage):
        if self._stage == stage and not self._state["tripped"]:
            self._state["tripped"] = True
            raise RuntimeError(f"injected async failure at {stage}")

    def block_until_ready(self):
        self._trip("certified.device_wait")
        self._arr.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        self._trip("certified.d2h")
        a = np.asarray(self._arr)
        return a.astype(dtype) if dtype is not None else a


@pytest.mark.parametrize("stage", ["certified.device_wait", "certified.d2h"])
def test_certified_pallas_staged_fetch_retries_either_stage(
        data, monkeypatch, no_backoff, stage):
    """The fetch is two stages at one blocking point; a transient failure
    in either still goes through ``_fetch_or_redispatch``: the batch is
    dispatched again, the answer is exact, and the failed attempt's
    scope is in the stage's one record of the call (its seconds in the
    sum, its profiler annotation beside the good one's)."""
    db, q = data
    real = sh._pallas_certified_program
    state = {"tripped": False, "calls": 0}

    def flaky_pallas_program(*a, **kw):
        prog = real(*a, **kw)

        def wrapper(*pa, **pkw):
            state["calls"] += 1
            out = prog(*pa, **pkw)
            if not state["tripped"]:
                return _FlakyReady(out, state, stage)
            return out

        return wrapper

    monkeypatch.setattr(sh, "_pallas_certified_program", flaky_pallas_program)
    scopes = Counter()
    real_add = obs.trace.CallAccount.add

    def add(self, piece, seconds, **attrs):
        scopes[piece] += 1
        real_add(self, piece, seconds, **attrs)

    monkeypatch.setattr(obs.trace.CallAccount, "add", add)
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    try:
        prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
        _, ref_i = _oracle(db, q, 5)
        d, i, stats = prog.search_certified(q, selector="pallas", margin=6)
        spans = Counter(e["span"] for e in obs.get_event_log().recent()
                        if e.get("type") == "span")
    finally:
        obs.reset()
        obs.reset_event_log(from_env=True)
    np.testing.assert_array_equal(i, ref_i)
    assert state["tripped"] and state["calls"] == 2  # dispatched again
    # the failed attempt's scope and the good one's, one record of both
    assert scopes[stage] == 2 and spans[stage] == 1
    assert scopes["certified.device_wait"] + scopes["certified.d2h"] == (
        4 if stage == "certified.d2h" else 3)
    assert spans["certified.device_wait"] == spans["certified.d2h"] == 1
    assert spans["certified.dispatch"] == 1 and spans["certified.call"] == 1


def test_retry_gives_up_after_bounded_attempts(data, monkeypatch):
    db, q = data
    real = sh._knn_program

    def always_broken(*a, **kw):
        real(*a, **kw)  # keep compile cost honest

        def wrapper(*pa, **pkw):
            raise RuntimeError("permanently broken")

        return wrapper

    monkeypatch.setattr(sh, "_knn_program", always_broken)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
    with pytest.raises(RuntimeError, match="failed after"):
        prog.search(q)


@pytest.fixture
def no_backoff(monkeypatch):
    # pure-unit policy tests need no real exponential sleeps
    monkeypatch.setattr(sh, "_retry_wait", lambda attempt: None)


def test_deterministic_failures_are_not_retried(no_backoff):
    # a Mosaic compile error / OOM is deterministic — retrying
    # it only adds ~3.5 s of backoff per batch before the real error
    # surfaces.  The signature classifier must propagate it on attempt 1.
    calls = {"n": 0}

    def oom():
        calls["n"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating")

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        sh._retry_transient(oom, "probe")
    assert calls["n"] == 1

    calls["n"] = 0

    def mosaic():
        calls["n"] += 1
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    with pytest.raises(RuntimeError, match="Mosaic"):
        sh._retry_transient(mosaic, "probe")
    assert calls["n"] == 1


def test_unknown_repeating_failure_gives_up_early(no_backoff):
    # an unrecognized error that repeats VERBATIM is deterministic in
    # effect: stop after the repeat (2 calls), not the full window (3)
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise RuntimeError("some novel permanent failure")

    with pytest.raises(RuntimeError, match="failed after 2 attempts"):
        sh._retry_transient(broken, "probe")
    assert calls["n"] == 2


def test_known_transient_gets_full_retry_window(no_backoff):
    # known-transient errors (UNAVAILABLE etc.) keep the full bounded
    # window even when attempts fail identically — that is the hiccup
    # the backoff exists to outlast
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("UNAVAILABLE: connection reset by peer")
        return "ok"

    assert sh._retry_transient(flaky, "probe") == "ok"
    assert calls["n"] == 3


def test_fetch_deterministic_failure_not_redispatched(no_backoff):
    state = {"redo": 0}

    class OOMArray:
        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("RESOURCE_EXHAUSTED: device OOM")

    def redo():
        state["redo"] += 1
        return np.zeros(3)

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        sh._fetch_or_redispatch(OOMArray(), redo, "fetch")
    assert state["redo"] == 0


def test_caller_bugs_are_not_retried(data, monkeypatch):
    db, q = data
    real = sh._knn_program
    calls = {"n": 0}

    def buggy(*a, **kw):
        real(*a, **kw)

        def wrapper(*pa, **pkw):
            calls["n"] += 1
            raise ValueError("caller bug")

        return wrapper

    monkeypatch.setattr(sh, "_knn_program", buggy)
    prog = ShardedKNN(db, mesh=make_mesh(2, 2), k=5)
    with pytest.raises(ValueError, match="caller bug"):
        prog.search(q)
    assert calls["n"] == 1  # no retry on ValueError
