"""Traffic kind ``sweep_ip``: ``drivers/sweep.py``'s closed loop (its
``_window`` and ``sample``, imported) for a configuration whose metric
is not squared L2, with three differences and nothing else:

- the rows and the queries come from ``datagen_mix.draw`` by the
  configuration's ``rows`` and ``queries`` entries (queries off the
  rows' distribution, where the configuration says so);
- the oracle and the comparison are those of
  ``reference_<configuration's "reference">.py`` (``oracle_topk(db, q,
  k)`` and ``compare(got_i, got_d, want_i, want_d, db, q)``), and every
  number of the configuration's ``limits`` that the comparison gives is
  held to its limit;
- the harness gets the registry's change over the window
  (``system.registry_delta``), so ``span`` and ``counter`` readers find
  the program's own series.

The same ``bench`` readings under the same names as ``sweep``, so the
layer files that serve it serve this.  Another metric (cosine) is a
reference file and, where its data differs, a ``datagen_mix``
distribution; this file stays.

Traffic file: as ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``).
"""

from __future__ import annotations

import importlib

import numpy as np

import datagen
import datagen_mix
import system
from harness import Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


def run(ctx: Ctx) -> Outcome:
    import jax

    cfg, tr = ctx.config, ctx.traffic
    ref = importlib.import_module(f"reference_{cfg['reference']}")
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    t = system.now()
    db = datagen_mix.draw(cfg["rows"], n, dim, ctx.seed, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(
        cfg.get("queries", cfg["rows"]), rows * n_pool, dim, ctx.seed,
        datagen.STREAM_QUERIES, of=cfg["rows"])
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
    # against the float64 oracle, on the host and outside the window
    n_check = int(tr["check_rows"])
    pick_b, pick_r = sweep.sample(ctx.seed, sorted(last), rows, n_check)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got_d = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_i = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_i, want_d = ref.oracle_topk(db, q, k)
    say(f"check: float64 oracle ({ref.__name__}) on {n_check} queries: "
        f"{system.now() - t:.1f} s")
    cmp = ref.compare(got_i, got_d, want_i, want_d, db, q)
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
