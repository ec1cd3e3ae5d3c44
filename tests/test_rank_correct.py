"""rank_correct_runs: targeted float64 repair of a device-ranked candidate
list driven by the near-tie mask alone — the pallas certified path's
stand-in for the full host refine.  Property under test: for ANY ranking
whose f32 values are within the slack band of the true distances, the
output (on rows the device would NOT flag bad) must equal refine_exact on
the same candidates."""

import os
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.ops import refine
from knn_tpu.ops.refine import _block_rows, rank_correct_runs, refine_exact

SLACK = 2.0 ** -18


def _device_sim(db, queries, m, k, rel_noise, rng, window_extra=16):
    """Simulate the device stage exactly as _pallas_certified_program
    computes it: noisy ranked distances, tight mask restricted to finite
    pairs before the first big gap at pair index >= k-1, and the
    unresolved flag."""
    d = ((db.astype(np.float64)[None] - queries.astype(np.float64)[:, None]) ** 2).sum(-1)
    noisy = d * (1.0 + rel_noise * (rng.random(d.shape) * 2 - 1))
    order = np.lexsort((np.broadcast_to(np.arange(d.shape[1]), d.shape), noisy))
    gi = order[:, :m]
    dv = np.take_along_axis(noisy, gi, -1).astype(np.float32).astype(np.float64)
    w = min(k + 1 + window_extra, m)
    dw = dv[:, :w]
    gaps = dw[:, 1:] - dw[:, :-1]
    tight = (gaps <= SLACK * dw[:, 1:]) & np.isfinite(dw[:, 1:])
    pair = np.arange(w - 1)
    big_after = (~tight) & (pair[None, :] >= k - 1)
    has_stop = big_after.any(-1)
    stop = np.where(has_stop, big_after.argmax(-1), w - 1)
    unresolved = (~has_stop) | ~np.isfinite(dw[:, : k + 1]).all(-1)
    tight_use = tight & (pair[None, :] < stop[:, None]) & ~unresolved[:, None]
    return gi, dv, tight_use, unresolved


@pytest.mark.parametrize("rel_noise", [0.0, 1e-6, 1.5e-6])
def test_rank_correct_runs_matches_full_refine(rng, rel_noise):
    # precondition: slack covers the two-sided pair error (2*rel <= slack)
    db = rng.normal(size=(600, 12)).astype(np.float32) * 10
    db[100:140] = db[:40]  # exact duplicates -> exactly tied distances
    queries = rng.normal(size=(64, 12)).astype(np.float32) * 10
    gi, dv, tight, unresolved = _device_sim(db, queries, 25, 9, rel_noise, rng)
    d, i, n_c = rank_correct_runs(gi, tight, 9, queries, db,
                                  d32k=dv[:, :9].copy())
    ref_d, ref_i = refine_exact(db, queries, gi, 9)
    ok = ~unresolved  # device flags unresolved rows bad -> repair path
    np.testing.assert_array_equal(i[ok], ref_i[ok])
    # uncorrected entries carry device f32 values (the contract), so the
    # distance tolerance floors at f32 rounding
    np.testing.assert_allclose(d[ok], ref_d[ok], rtol=max(4 * rel_noise, 2e-7))


def test_rank_correct_runs_without_distances(rng):
    db = rng.normal(size=(400, 8)).astype(np.float32) * 10
    db[30:50] = db[:20]
    queries = rng.normal(size=(16, 8)).astype(np.float32) * 10
    gi, dv, tight, unresolved = _device_sim(db, queries, 20, 5, 1e-6, rng)
    d, i, n_c = rank_correct_runs(gi, tight, 5, queries, db, d32k=None)
    assert d is None
    ref_d, ref_i = refine_exact(db, queries, gi, 5)
    ok = ~unresolved
    np.testing.assert_array_equal(i[ok], ref_i[ok])


def test_rank_correct_runs_corrected_entries_are_float64(rng):
    # duplicates force runs; corrected positions must carry exact f64
    db = rng.normal(size=(300, 6)).astype(np.float32)
    db[10:14] = db[5]  # five-way tie
    queries = (db[5][None] + 0.01).astype(np.float32)
    gi, dv, tight, unresolved = _device_sim(db, queries, 20, 7, 0.0, rng)
    assert tight.any(), "fixture must produce at least one tie run"
    d, i, n_c = rank_correct_runs(gi, tight, 7, queries, db,
                                  d32k=dv[:, :7].copy())
    ref_d, ref_i = refine_exact(db, queries, gi, 7)
    ok = ~unresolved
    np.testing.assert_array_equal(i[ok], ref_i[ok])
    # the five-way tie run occupies the leading positions: those entries
    # must be float64-exact; trailing uncorrected ones are f32-accurate
    np.testing.assert_array_equal(d[ok][:, :5], ref_d[ok][:, :5])
    np.testing.assert_allclose(d[ok], ref_d[ok], rtol=2e-7)
    assert n_c >= 1


def test_rank_correct_runs_clean_rows_untouched(rng):
    # well-separated data: no tight pairs, zero corrections, passthrough
    db = (rng.normal(size=(200, 8)) * 100).astype(np.float32)
    queries = (rng.normal(size=(9, 8)) * 100).astype(np.float32)
    gi, dv, tight, unresolved = _device_sim(db, queries, 15, 4, 0.0, rng)
    d, i, n_c = rank_correct_runs(gi, tight, 4, queries, db,
                                  d32k=dv[:, :4].copy())
    assert n_c == int(tight.any(-1).sum())
    ref_d, ref_i = refine_exact(db, queries, gi, 4)
    ok = ~unresolved
    np.testing.assert_array_equal(i[ok], ref_i[ok])


@pytest.fixture
def pool_of(monkeypatch):
    """``pool_of(width, range_pairs=None)``: the re-score pool answered
    from outside as one of ``width`` threads (made anew, shut down when
    the test ends), and the worth-a-thread rule as ``range_pairs`` tight
    pairs where a test's shapes are too small to cut by the rule as it
    is."""
    made = []

    def set_to(width, range_pairs=None):
        monkeypatch.setattr(refine, "_POOL_THREADS", width)
        monkeypatch.setattr(refine, "_pool", None)
        if range_pairs is not None:
            monkeypatch.setattr(refine, "_RANGE_PAIRS", range_pairs)
        made.append(None)

    yield set_to
    if made and refine._pool is not None:
        refine._pool.shutdown(wait=False, cancel_futures=True)


# --- the blocked, pooled re-score against the whole-array formula -----------
def _involved(tight):
    """[Q, W] bool: the positions either side of a tight pair."""
    inv = np.zeros((tight.shape[0], tight.shape[1] + 1), dtype=bool)
    inv[:, :-1] |= tight
    inv[:, 1:] |= tight
    return inv


def _whole_array(gi, tight, k, queries_np, db_np, d32k=None):
    """rank_correct_runs as it stood before the re-score was cut into
    blocks: every member's float64 distance from ONE gather, ONE widening
    and ONE einsum over all of them.  The reference the blocked form must
    equal bit for bit."""
    d_out = d32k.copy() if d32k is not None else None
    rows, cols = np.nonzero(_involved(tight))
    if rows.size == 0:
        return d_out, gi[:, :k].astype(np.int64), 0
    gw = gi[:, : tight.shape[1] + 1].astype(np.int64).copy()
    cand = gw[rows, cols]
    safe = np.clip(cand, 0, db_np.shape[0] - 1)
    diff = db_np[safe].astype(np.float64) - queries_np[rows].astype(
        np.float64)
    d64 = np.einsum("nd,nd->n", diff, diff)
    d64 = np.where(cand < db_np.shape[0], d64, np.inf)
    new_run = np.ones(rows.size, dtype=bool)
    new_run[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1] + 1)
    run_id = np.cumsum(new_run) - 1
    order = np.lexsort((cand, d64, run_id))
    gw[rows, cols] = cand[order]
    if d_out is not None:
        in_k = cols < k
        d_out[rows[in_k], cols[in_k]] = d64[order][in_k]
    return d_out, gw[:, :k], int(len(np.unique(rows)))


W, K_TIE, N_DB = 40, 30, 4000


def _tie_batch(members, dim, rng):
    """A batch whose tie mask involves exactly ``members`` candidate
    positions (runs of 2...9 positions, a clean position between two runs
    of one query), over rows with duplicates (exactly tied distances, so
    the index breaks the tie) and sentinel candidates inside runs."""
    runs, left = [], members
    while left:
        n = min(int(rng.integers(2, 10)), left)
        if left - n == 1:  # a run is two positions at the least
            n += 1
        runs.append(n)
        left -= n
    masks, row, col = [], np.zeros(W - 1, dtype=bool), 0
    for n in runs:
        if col + n > W:
            masks.append(row)
            row, col = np.zeros(W - 1, dtype=bool), 0
        row[col : col + n - 1] = True
        col += n + 1
    masks.append(row)
    tight = np.array(masks)
    n_q = tight.shape[0]
    db = rng.normal(size=(N_DB, dim)).astype(np.float32)
    db[N_DB // 2 : N_DB // 2 + 200] = db[:200]
    queries = rng.normal(size=(n_q, dim)).astype(np.float32)
    gi = rng.integers(0, N_DB // 2 + 200, size=(n_q, W + 5)).astype(np.int32)
    gi[rng.random(gi.shape) < 0.02] = N_DB + 7  # the kernel's sentinel
    for r in range(0, n_q, 7):  # a row and its copy, the higher index first
        c = int(tight[r].argmax())
        gi[r, c : c + 2] = (r % 200 + N_DB // 2, r % 200)
    return gi, tight, queries, db, rng.random((n_q, K_TIE))


@pytest.mark.parametrize("with_distances", [True, False],
                         ids=["d32k", "indices_only"])
@pytest.mark.parametrize("dim", [128, 960])
@pytest.mark.parametrize("members", [
    pytest.param(lambda b: 0, id="none"),
    # one tight pair involves two positions: no mask gives one member
    pytest.param(lambda b: 2, id="one_pair"),
    pytest.param(lambda b: b - 1, id="block_less_one"),
    pytest.param(lambda b: b, id="one_block"),
    pytest.param(lambda b: b + 1, id="block_plus_one"),
    pytest.param(lambda b: 3 * b + 17, id="blocks_ragged_last"),
])
def test_blocked_rescore_equals_the_whole_array_formula(
        rng, members, dim, with_distances):
    n = members(_block_rows(dim))
    gi, tight, queries, db, d32k = _tie_batch(n, dim, rng)
    inv = _involved(tight)
    assert int(inv.sum()) == n  # the fixture gives the count it was asked
    if n > 1000:
        assert (gi[:, :W][inv] >= N_DB).any(), "no sentinel inside a run"
    d32k = d32k if with_distances else None
    want = _whole_array(gi, tight, K_TIE, queries, db, d32k)
    got = rank_correct_runs(gi, tight, K_TIE, queries, db, d32k)
    if with_distances:
        assert got[0].dtype == np.float64
        np.testing.assert_array_equal(got[0], want[0])
    else:
        assert got[0] is None and want[0] is None
    assert got[1].dtype == np.int64
    np.testing.assert_array_equal(got[1], want[1])
    assert got[2] == want[2]


def _peak_bytes(fn):
    """Most bytes numpy held at once inside ``fn()`` over what it held
    before (numpy reports its data allocations to tracemalloc, from every
    thread)."""
    ours = not tracemalloc.is_tracing()
    if ours:
        tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        fn()
        return tracemalloc.get_traced_memory()[1] - before
    finally:
        if ours:
            tracemalloc.stop()


@pytest.mark.parametrize("blocks", [6, 24])
def test_rescore_temporaries_do_not_grow_with_the_batch(rng, blocks):
    # gist1m's width, a batch and four times the batch under ONE budget.
    # A block's temporaries are 12.6 MB at the most (its float64
    # difference and one float32 gather) and four threads hold a block
    # each, whatever the batch; what grows is a few arrays of 8 bytes a
    # member (5 MB at 24 blocks).  The whole-array form held 3 x 8 x 960
    # bytes a member: 150 MB at 6 blocks, 600 MB at 24
    gi, tight, queries, db, d32k = _tie_batch(
        blocks * _block_rows(960), 960, rng)
    peak = _peak_bytes(
        lambda: rank_correct_runs(gi, tight, K_TIE, queries, db, d32k))
    assert peak < 64 << 20


@pytest.mark.parametrize("range_pairs", [1 << 30, 64],
                         ids=["one_range", "cut_into_ranges"])
def test_callers_on_other_threads_share_the_pool(rng, pool_of, range_pairs):
    pool_of(4, range_pairs)
    gi, tight, queries, db, d32k = _tie_batch(
        3 * _block_rows(128) + 17, 128, rng)
    assert len(refine._member_ranges(tight.sum(1))) == (
        1 if range_pairs > 64 else 4)
    want = _whole_array(gi, tight, K_TIE, queries, db, d32k)
    callers = (os.cpu_count() or 1) + 2
    got = [None] * callers

    def call(slot):
        got[slot] = rank_correct_runs(gi, tight, K_TIE, queries, db, d32k)

    threads = [threading.Thread(target=call, args=(s,))
               for s in range(callers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for d, i, n_c in got:
        np.testing.assert_array_equal(d, want[0])
        np.testing.assert_array_equal(i, want[1])
        assert n_c == want[2]


# --- the cut by query ranges: every phase on the pool's threads -------------
N_CUT, W_CUT, K_CUT, D_CUT = 60, 24, 20, 32


def _mask(kind, rng):
    """A tie mask [N_CUT, W_CUT - 1] of one of the shapes the cut has to
    get right."""
    tight = np.zeros((N_CUT, W_CUT - 1), dtype=bool)
    if kind == "crowded":  # most members in five queries, next to each
        # other; a pair or none elsewhere
        tight[20:25] = rng.random((5, W_CUT - 1)) < 0.8
        for r in rng.choice(N_CUT, 12, replace=False):
            tight[r, int(rng.integers(0, W_CUT - 1))] = True
    elif kind == "one_pair":
        tight[37, 5] = True
    elif kind == "window_edge":  # runs that end at the window's last
        # column, past k
        tight[::3, -4:] = True
        tight[1::3, K_CUT - 2 : K_CUT + 1] = True
    else:
        assert kind == "none"
    return tight


@pytest.mark.parametrize("kind", ["crowded", "none", "one_pair",
                                  "window_edge"])
@pytest.mark.parametrize("metric", ["l2", "dot", "cosine"])
@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_the_cut_by_query_ranges_answers_as_one_part_to_the_bit(
        rng, pool_of, width, metric, kind):
    tight = _mask(kind, rng)
    db = rng.normal(size=(500, D_CUT)).astype(np.float32)
    db[250:300] = db[:50]  # exactly tied distances: the index decides
    queries = rng.normal(size=(N_CUT, D_CUT)).astype(np.float32)
    gi = rng.integers(0, 300, size=(N_CUT, W_CUT + 3)).astype(np.int32)
    gi[rng.random(gi.shape) < 0.03] = 507  # the kernel's sentinel
    d32k = rng.random((N_CUT, K_CUT))
    norms = ((refine.row_norms_f64(queries), refine.row_norms_f64(db))
             if metric == "cosine" else None)
    args = (gi, tight, K_CUT, queries, db)
    pool_of(1)
    want = rank_correct_runs(*args, d32k=d32k, metric=metric, norms=norms)
    if metric == "l2":  # and the single part is the formula it always was
        whole = _whole_array(*args, d32k)
        np.testing.assert_array_equal(want[0], whole[0])
        np.testing.assert_array_equal(want[1], whole[1])
        assert want[2] == whole[2]
    pool_of(width, range_pairs=1)  # a pair is worth a thread
    pairs = tight.sum(1)
    ranges = refine._member_ranges(pairs)
    if kind in ("none", "one_pair"):
        assert ranges == [(0, N_CUT)]
    elif kind == "crowded":  # one query holds more than a range's share
        assert min(width, 2) <= len(ranges) <= width
    else:
        assert len(ranges) == width
    got = rank_correct_runs(*args, d32k=d32k, metric=metric, norms=norms)
    assert got[0].dtype == np.float64 and got[1].dtype == np.int64
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert got[2] == want[2] == int((pairs > 0).sum())
    only_i = rank_correct_runs(*args, metric=metric, norms=norms)
    assert only_i[0] is None
    np.testing.assert_array_equal(only_i[1], want[1])


@pytest.mark.parametrize("shape,width,n_q,per_query,parts", [
    # a sub-batch of knnlm1m.sweep_k1024: every query corrected, ~100
    # pairs each: a range a thread
    ("knnlm1m", 8, 512, (40, 200), 8),
    ("knnlm1m", 4, 512, (40, 200), 4),
    # half as many queries: fewer ranges than threads, each worth one
    ("knnlm1m_short", 8, 150, (40, 200), 4),
    # of bigann5m.sweep: half the queries corrected, a pair each; of
    # gist1m.sweep: a few pairs a query: sorts too short to share
    ("bigann5m", 8, 1024, (0, 1), 1),
    ("gist1m", 4, 1024, (0, 4), 1),
    ("no_member", 4, 512, (0, 0), 1),
])
def test_the_cut_is_even_in_members_and_follows_what_a_thread_is_worth(
        rng, pool_of, shape, width, n_q, per_query, parts):
    pool_of(width)
    lo, hi = per_query
    pairs = rng.integers(lo, hi + 1, size=n_q)
    if shape == "knnlm1m":  # tie runs crowd in some queries
        pairs[100:130] = 1100
    ranges = refine._member_ranges(pairs)
    assert len(ranges) == parts
    # contiguous, in order, every query in exactly one range
    assert ranges[0][0] == 0 and ranges[-1][1] == n_q
    assert [a for a, _ in ranges[1:]] == [b for _, b in ranges[:-1]]
    assert all(a < b for a, b in ranges)
    if parts > 1:
        held = [int(pairs[a:b].sum()) for a, b in ranges]
        # a range ends at the query that takes it past its share: no
        # range is further from the even share than one query's pairs
        assert max(abs(h - pairs.sum() / parts) for h in held) <= pairs.max()
        assert min(held) >= refine._RANGE_PAIRS - pairs.max()


def _crowded_call(rng):
    """``rank_correct_runs``' positional arguments for the crowded mask."""
    tight = _mask("crowded", rng)
    db = rng.normal(size=(500, D_CUT)).astype(np.float32)
    queries = rng.normal(size=(N_CUT, D_CUT)).astype(np.float32)
    gi = rng.integers(0, 500, size=(N_CUT, W_CUT)).astype(np.int32)
    return gi, tight, K_CUT, queries, db


def test_the_span_is_told_wall_time_shares_and_the_cut(rng, pool_of):
    pool_of(4, range_pairs=1)
    args = _crowded_call(rng)
    with obs.span("certified.rank_correct") as sp:
        t0 = time.perf_counter()
        rank_correct_runs(*args, d32k=rng.random((N_CUT, K_CUT)))
        wall = time.perf_counter() - t0
    told = sp.attrs
    assert (told["members"], told["parts"], told["threads"]) == (
        int(_involved(args[1]).sum()), 4, 4)
    phases = told["buffers_s"] + told["score_s"] + told["order_s"]
    # shares of the stage's WALL time, though four threads ran them
    assert 0 < phases <= wall
    assert min(told[key] for key in ("buffers_s", "score_s", "order_s")) > 0
    # sums over the threads: inside the re-score, whoever ran it
    assert told["gather_s"] > 0 and told["arith_s"] > 0


def test_a_workers_exception_reaches_the_caller(rng, pool_of, monkeypatch):
    pool_of(4, range_pairs=1)
    caller = threading.get_ident()
    ran_on = set()

    def fails_in_the_pool(*args, **kwargs):
        ran_on.add(threading.get_ident())
        raise FloatingPointError("a range's re-score failed")

    monkeypatch.setattr(refine, "_score_members", fails_in_the_pool)
    with pytest.raises(FloatingPointError, match="a range's re-score"):
        rank_correct_runs(*_crowded_call(rng))
    assert ran_on and caller not in ran_on


def test_a_map_made_on_a_pool_thread_does_not_wait_for_the_pool(pool_of):
    # a range's blocks go through pool_map on a pool thread: on a pool
    # of ONE thread an inner map handed to the pool would never start
    pool_of(1)
    ran_on = []

    def inner(part):
        ran_on.append(threading.get_ident())
        return part * part

    def outer(part):
        return sum(refine.pool_map(inner, [part, part + 1]))

    done = []
    runner = threading.Thread(
        target=lambda: done.append(refine.pool_map(outer, [1, 3])))
    runner.start()
    runner.join(timeout=60)
    assert not runner.is_alive() and done == [[1 + 4, 9 + 16]]
    assert len(set(ran_on)) == 1 and threading.get_ident() not in ran_on
