"""Traffic kind ``sweep``: a closed loop with one caller.  Consecutive
query batches, cycled from a pool drawn from the seed, go through
``ShardedKNN.search_certified(selector=...)`` with no knob passed; each
batch's answer is back on the host as numpy arrays before the next is
sent.  ``sweep_qps`` is all the queries answered over all the time from
the window's start to the last answer.  The harness gets the registry's
change over the window (``system.registry_delta``), so ``span`` and
``counter`` readers find the program's own series.

Traffic file: ``batch_rows``, ``pool_batches``, ``selector``,
``check_rows`` (queries compared with the oracle), ``trace_seconds``.
"""

from __future__ import annotations

import numpy as np

import datagen
import reference
import system
from harness import Ctx, Outcome, resident_bytes, say


def _window(ctx: Ctx, prog, pool, selector: str, seconds: float):
    """Drive batches until ``seconds`` have passed; returns (batches,
    elapsed to the last answer, summed stats, last answer per pool
    batch, answers that differed from an earlier one of the same
    batch)."""
    import jax

    totals = {"queries": 0, "certified": 0, "fallback_queries": 0,
              "rank_corrected_queries": 0, "uncounted_batches": 0}
    last, changed, n = {}, 0, 0
    t0 = system.now()
    while True:
        b = n % len(pool)
        with jax.profiler.TraceAnnotation("bench.call"):
            d, i, stats = prog.search_certified(pool[b], selector=selector)
        with jax.profiler.TraceAnnotation("bench.host-after-batch"):
            d, i = np.asarray(d), np.asarray(i)
            system.require(ctx.config, stats)
            rows = pool[b].shape[0]
            totals["queries"] += rows
            totals["certified"] += stats["certified"]
            totals["fallback_queries"] += stats["fallback_queries"]
            totals["rank_corrected_queries"] += stats.get(
                "rank_corrected_queries", 0)
            if stats["certified"] + stats["fallback_queries"] != rows:
                totals["uncounted_batches"] += 1
            if b in last and not (np.array_equal(last[b][1], i)
                                  and np.array_equal(last[b][0], d)):
                changed += 1
            last[b] = (d, i)
            n += 1
            elapsed = system.now() - t0
        if elapsed >= seconds:
            return n, elapsed, totals, last, changed


def sample(seed: int, answered, rows: int, n_check: int):
    """The (pool batch, row) pairs whose answers are compared, drawn from
    the seed among the pool batches the window answered."""
    rng = datagen.rng_for(seed, datagen.STREAM_SAMPLE)
    return (rng.choice(answered, size=n_check),
            rng.choice(rows, size=n_check, replace=False))


def run(ctx: Ctx) -> Outcome:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    t = system.now()
    db = datagen.draw(cfg["rows"], n, dim, ctx.seed, datagen.STREAM_ROWS)
    queries = datagen.draw(cfg["rows"], rows * n_pool, dim, ctx.seed,
                           datagen.STREAM_QUERIES)
    pool = [queries[b * rows:(b + 1) * rows] for b in range(n_pool)]
    say(f"set-up: drew {n:,} x {dim} rows and {n_pool} batches of {rows} "
        f"queries from seed {ctx.seed}: {system.now() - t:.1f} s")
    t = system.now()
    prog = system.place(cfg, db, ctx.cell.chips)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.search_certified(pool[b], selector=tr["selector"])
        system.require(cfg, stats)
        if b == 0:
            say(f"set-up: first batch (compiles or loads): "
                f"{system.now() - t:.1f} s; knobs {stats['pallas_knobs']}")
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
            batches, elapsed, totals, last, changed = _window(
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
    # against the float64 oracle, on the host and outside the window
    n_check = int(tr["check_rows"])
    pick_b, pick_r = sample(ctx.seed, sorted(last), rows, n_check)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got_d = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_i = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_i, want_d = reference.oracle_topk(db, q, k)
    say(f"check: float64 oracle on {n_check} queries: "
        f"{system.now() - t:.1f} s")
    cmp = reference.compare(got_i, got_d, want_i, want_d)
    limits = cfg["limits"]
    checks = reference.Checks()
    checks.add("mismatched_rows", cmp["mismatched_rows"],
               limits["mismatched_rows"])
    checks.add("dist_rel_err_max", cmp["dist_rel_err_max"],
               limits["dist_rel_err_max"])
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
