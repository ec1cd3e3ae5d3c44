"""Traffic kind ``sweep_range``: ``drivers/sweep.py``'s closed loop (its
``_window`` and ``sample``, imported) for range search, with these
differences and nothing else:

- the rows come from ``datagen_dup.draw`` by the configuration's
  ``rows`` entry, the queries from ``datagen_dup.draw_queries`` in the
  fixed ``shares`` of the traffic file (every batch holds the same
  number of no-result, short and long queries, and ``boundary_pairs``
  of its short ones lie at exactly the radius, and one unit past it,
  from a placed row);
- each batch is answered by ``ShardedKNN.range_search_certified(batch,
  radius_sq=<the configuration's>, selector=...)``, whose answer
  (``lims``, ``idx``, ``dist``) ``_window`` keeps as two arrays: the
  distances, and ``lims`` followed by ``idx`` (:class:`RangeCalls`);
- the oracle and the comparison are ``reference_range.py``'s
  (``oracle_range(db, q, radius_sq)``, ``compare(got, want)``) over a
  sample of ``check_rows`` queries of which ``check_heavy_rows`` are
  drawn among the long ones and ``2 * boundary_pairs`` are one
  answered batch's boundary queries, and every number of the configuration's
  ``limits`` that the comparison gives is held to its limit;
- the harness gets the registry's change over the window
  (``system.registry_delta``), so ``span`` and ``counter`` readers find
  the program's own series.

The same ``bench`` readings under the same names as ``sweep``, so the
layer files that serve it serve this.

Traffic file: ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``) and ``shares``,
``small_max``, ``heavy_min``, ``check_heavy_rows``, ``boundary_pairs``.
"""

from __future__ import annotations

import numpy as np

import datagen
import datagen_dup
import reference_range
import system
from harness import BenchError, Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


class RangeCalls:
    """The placed program as ``sweep._window`` calls it
    (``search_certified(batch, selector=...) -> (d, i, stats)``),
    answered by range search: ``d`` is the flat distances, ``i`` is
    ``lims`` followed by ``idx`` (:func:`split` is the inverse)."""

    def __init__(self, prog, radius_sq: float):
        self.prog, self.radius_sq = prog, radius_sq

    def search_certified(self, batch, *, selector: str):
        lims, idx, dist, stats = self.prog.range_search_certified(
            batch, radius_sq=self.radius_sq, selector=selector)
        return dist, np.concatenate([lims, idx]), stats


def split(answer, rows: int):
    """``(lims, idx, dist)`` of one ``_window`` answer ``(d, i)``."""
    d, i = answer
    return i[:rows + 1], i[rows + 1:], d


def pick(seed: int, answered, rows: int, kinds, n_check: int, n_heavy: int):
    """The (pool batch, row) pairs whose answers are compared:
    ``n_heavy`` drawn among the long queries (``kinds``:
    ``datagen_dup.draw_queries``' labels) of the batches answered, every
    boundary query of one of those batches, and ``sweep.sample``'s draw
    for the rest."""
    rng = datagen.rng_for(seed, datagen.STREAM_SAMPLE, 1)
    heavy = datagen_dup.KINDS.index("heavy_family")
    heavy_b = rng.choice(answered, size=n_heavy)
    heavy_r = [rng.choice(np.flatnonzero(
        kinds[b * rows:(b + 1) * rows] == heavy)) for b in heavy_b]
    edge_at = rng.choice(answered)
    edge_r = np.flatnonzero(
        kinds[edge_at * rows:(edge_at + 1) * rows] >= datagen_dup.AT_RADIUS)
    pick_b, pick_r = sweep.sample(seed, answered, rows,
                                  n_check - n_heavy - edge_r.size)
    return (np.concatenate([pick_b, heavy_b, np.full(edge_r.size, edge_at)]
                           ).astype(np.int64),
            np.concatenate([pick_r, heavy_r, edge_r]).astype(np.int64))


def run(ctx: Ctx) -> Outcome:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    radius_sq = float(cfg["radius_sq"])
    t = system.now()
    db = datagen_dup.draw(cfg["rows"], n, dim, ctx.seed, datagen.STREAM_ROWS)
    queries, kinds = datagen_dup.draw_queries(
        cfg["rows"], n, dim, ctx.seed, rows, n_pool, tr["shares"],
        int(tr["small_max"]), int(tr["heavy_min"]), int(cfg["radius_sq"]),
        int(tr["boundary_pairs"]))
    pool = [queries[b * rows:(b + 1) * rows] for b in range(n_pool)]
    say(f"set-up: drew {n:,} x {dim} rows and {n_pool} batches of {rows} "
        f"queries ({tr['shares']}) from seed {ctx.seed}: "
        f"{system.now() - t:.1f} s")
    t = system.now()
    placed = system.place(cfg, db, ctx.cell.chips)
    if not hasattr(placed, "range_search_certified"):
        raise BenchError("this tree's ShardedKNN has no "
                         "range_search_certified: the cell cannot run on it")
    prog = RangeCalls(placed, radius_sq)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.search_certified(pool[b], selector=tr["selector"])
        system.require(cfg, stats)
        if b == 0:
            say(f"set-up: first batch (compiles or loads): "
                f"{system.now() - t:.1f} s; knobs {stats['pallas_knobs']}; "
                f"range {stats['range']}")
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

    # correct: a seeded sample of the queries answered in the window,
    # some of them drawn among the long ones, against the float64
    # oracle, on the host and outside the window
    n_check, n_heavy = int(tr["check_rows"]), int(tr["check_heavy_rows"])
    pick_b, pick_r = pick(ctx.seed, sorted(last), rows, kinds, n_check,
                          n_heavy)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got = reference_range.concat(
        reference_range.take(split(last[b], rows), [r])
        for b, r in zip(pick_b, pick_r))
    t = system.now()
    want = reference_range.oracle_range(db, q, radius_sq)
    cmp = reference_range.compare(got, want)
    say(f"check: float64 range oracle on {n_check} queries ({n_heavy} of "
        f"them long, {int((want[2] == radius_sq).sum())} results at exactly "
        f"the radius): {system.now() - t:.1f} s; {cmp}")
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
