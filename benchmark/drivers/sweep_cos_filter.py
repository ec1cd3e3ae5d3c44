"""Traffic kind ``sweep_cos_filter``: ``drivers/sweep.py``'s closed loop
(its ``_window`` and ``sample``'s stream, imported) for search under a
scalar range filter, with these differences and nothing else:

- the rows and the queries come from ``datagen_mix.draw`` by the
  configuration's ``rows`` entry, as ``sweep_ip``'s; every row carries
  one whole number, its ``id`` (:func:`row_ids`: the row's position, as
  the source assigns it), and every query the half-open range ``[N,
  rows_n)`` on it, N taken in turn from the traffic file's
  ``filter_from`` by the query's position in its batch, so every batch
  holds the same shares and every launch a mix;
- the corpus is placed with the ids (``ShardedKNN(..., row_attr=ids)``)
  and each batch is answered by ``search_certified(batch, selector=...,
  filter_range=<the batch's>)`` (:class:`RangedCalls` hands ``_window``
  the batch's ranges by the batch's own identity, and keeps which of
  its queries the last call repaired);
- the oracle and the comparison are ``reference_cosfilter.py``'s, over
  a sample of ``check_rows`` answered queries: the repaired queries of
  the answered batch that has most, at most ``check_flagged_rows`` of
  them, and seeded draws in equal shares of every N for the rest
  (:func:`pick`);
  every number of the configuration's ``limits`` that the comparison
  gives is held to its limit;
- the harness gets the registry's change over the window
  (``system.registry_delta``), so ``span`` and ``counter`` readers find
  the program's own series.

A tree whose ``ShardedKNN`` takes no ``row_attr`` cannot run the cell:
that is asked of its signature before a row is drawn.

Traffic file: ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``) and ``filter_from``,
``check_flagged_rows``.
"""

from __future__ import annotations

import inspect

import numpy as np

import datagen
import datagen_mix
import reference_cosfilter
import system
from harness import BenchError, Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


def row_ids(n: int) -> np.ndarray:
    """A row's ``id``: its position, as VectorDBBench assigns it."""
    return np.arange(n, dtype=np.int64)


def batch_ranges(filter_from, rows: int, n: int) -> np.ndarray:
    """int64 ``[rows, 2]``: query r of a batch carries ``[filter_from[r
    % len], n)``, the source's ``id >= N``."""
    lo = np.asarray(filter_from, np.int64)[np.arange(rows) % len(filter_from)]
    return np.stack([lo, np.full(rows, n, np.int64)], axis=1)


class RangedCalls:
    """The placed program as ``sweep._window`` calls it
    (``search_certified(batch, selector=...) -> (d, i, stats)``), every
    batch answered under its ranges; ``repaired[id(batch)]`` is the
    positions the last call of that batch fell back on."""

    def __init__(self, prog, ranges: np.ndarray):
        self.prog, self.ranges, self.repaired = prog, ranges, {}

    def search_certified(self, batch, *, selector: str):
        d, i, stats = self.prog.search_certified(
            batch, selector=selector, filter_range=self.ranges)
        self.repaired[id(batch)] = stats["fallback_positions"]
        return d, i, stats


def takes_row_attr() -> bool:
    from knn_tpu.parallel import ShardedKNN

    return "row_attr" in inspect.signature(ShardedKNN.__init__).parameters


def pick(seed: int, answered, rows: int, shares: int, n_check: int,
         flagged=((), ())):
    """The (pool batch, row) pairs whose answers are compared:
    ``flagged`` (pool batch, its repaired rows: at most the traffic
    file's ``check_flagged_rows``, the caller's cut) first, then seeded
    draws among the answered batches, in equal numbers for every one of
    the ``shares`` positions a batch's ranges turn through, up to
    ``n_check`` in all."""
    rng = datagen.rng_for(seed, datagen.STREAM_SAMPLE, 2)
    flag_b, flag_r = flagged
    pick_b, pick_r = list(flag_b), list(flag_r)
    left = n_check - len(pick_b)
    for share in range(shares):
        take = left // shares + (share < left % shares)
        turns = np.arange(share, rows, shares)
        pick_b += rng.choice(answered, size=take).tolist()
        pick_r += rng.choice(turns, size=take, replace=False).tolist()
    return np.asarray(pick_b, np.int64), np.asarray(pick_r, np.int64)


def run(ctx: Ctx) -> Outcome:
    import jax

    if not takes_row_attr():
        raise BenchError("this tree's ShardedKNN takes no row_attr: the "
                         "cell cannot run on it")
    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    t = system.now()
    db = datagen_mix.draw(cfg["rows"], n, dim, ctx.seed, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(
        cfg.get("queries", cfg["rows"]), rows * n_pool, dim, ctx.seed,
        datagen.STREAM_QUERIES, of=cfg["rows"])
    pool = [queries[b * rows:(b + 1) * rows] for b in range(n_pool)]
    ids, ranges = row_ids(n), batch_ranges(tr["filter_from"], rows, n)
    say(f"set-up: drew {n:,} x {dim} rows and {n_pool} batches of {rows} "
        f"queries from seed {ctx.seed}, every query under id >= N, N in "
        f"turn {list(tr['filter_from'])}: {system.now() - t:.1f} s")
    t = system.now()
    placed = system.place(cfg, db, ctx.cell.chips, row_attr=ids)
    prog = RangedCalls(placed, ranges)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.search_certified(pool[b], selector=tr["selector"])
        system.require(cfg, stats)
        if b == 0:
            say(f"set-up: first batch (places the ids, compiles or "
                f"loads): {system.now() - t:.1f} s; knobs "
                f"{stats['pallas_knobs']}; filter {stats['filter']}")
    say(f"set-up: warmed {n_pool} batches: {system.now() - t:.1f} s")

    seconds = min(ctx.seconds, float(tr["trace_seconds"])) if ctx.traced \
        else ctx.seconds
    compiles_before = system.COMPILES["backend_compiles"]
    if ctx.traced:
        jax.profiler.start_trace(ctx.trace_dir)
    setup_s = system.now() - ctx.t_found
    # after the stamp and before the window reads its clock: in neither
    before = system.registry_snapshot()
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            batches, elapsed, totals, last, changed = sweep._window(
                ctx, prog, pool, tr["selector"], seconds)
    finally:
        if ctx.traced:
            jax.profiler.stop_trace()
    registry = system.registry_delta(before, system.registry_snapshot())
    compiled = system.COMPILES["backend_compiles"] - compiles_before
    resident = resident_bytes(ctx.cell.chips)
    say(f"window: {batches} batches, {totals['queries']} queries in "
        f"{elapsed:.3f} s; certified {totals['certified']} + fallback "
        f"{totals['fallback_queries']}; rank-corrected "
        f"{totals['rank_corrected_queries']}; programs compiled inside "
        f"the window: {compiled}")

    # correct: the repaired queries of one answered batch and a seeded
    # sample of the rest, against the float64 filtered oracle, on the
    # host and outside the window
    answered = sorted(last)
    # the answered batch whose last call repaired most (the first such)
    held = max(answered, key=lambda b: len(prog.repaired[id(pool[b])]))
    repaired = list(prog.repaired[id(pool[held])])[
        :int(tr["check_flagged_rows"])]
    pick_b, pick_r = pick(ctx.seed, answered, rows, len(tr["filter_from"]),
                          int(tr["check_rows"]),
                          ([held] * len(repaired), repaired))
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got_d = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_i = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_i, want_d = reference_cosfilter.oracle_topk(
        db, ids, q, ranges[pick_r], k)
    cmp = reference_cosfilter.compare(got_i, got_d, want_i, want_d, ids,
                                      ranges[pick_r])
    say(f"check: float64 filtered oracle on {len(pick_r)} queries, "
        f"{len(repaired)} of them repaired ones of pool batch {held}: "
        f"{system.now() - t:.1f} s; {cmp}")
    checks = Checks()
    for name, limit in cfg["limits"].items():
        checks.add(name, cmp[name], limit)
    checks.add("uncounted_batches", totals["uncounted_batches"], 0)
    checks.add("changed_answers", changed, 0)
    checks.add("compiles_in_window", compiled, 0)

    return Outcome(
        attempted=totals["queries"], failed=0,
        end_to_end={"setup_s": setup_s,
                    "sweep_qps": totals["queries"] / elapsed},
        checks=checks,
        bench={"batches": float(batches), **{
            key: float(v) for key, v in totals.items()}},
        registry=registry, resident_bytes=resident)
