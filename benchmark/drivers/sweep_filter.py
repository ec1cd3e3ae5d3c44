"""Traffic kind ``sweep_filter``: ``drivers/sweep.py``'s closed loop (its
``_window`` and ``sample``, imported) for filtered search, with these
differences and nothing else:

- the rows, their bags of tags and the queries come from
  ``datagen_tags`` by the configuration's ``rows``, ``tags`` and
  ``queries`` entries; every batch holds the traffic file's ``strata``:
  a fixed number of queries for each band of how many placed rows the
  query's whole filter matches (none, 1-9, ... 100,000 and over);
- the corpus is placed with its bags (``ShardedKNN(...,
  row_tags=(indptr, tags))``) and each batch is answered by
  ``search_certified(batch, selector=..., filter_tags=<the batch's>)``
  (:class:`FilteredCalls` hands ``_window`` the batch's tags by the
  batch's own identity);
- the oracle and the comparison are ``reference_filter.py``'s, over a
  sample of ``check_rows`` queries of which ``check_rows_a_band`` are
  drawn in each band that the answered batches hold, and every number
  of the configuration's ``limits`` that the comparison gives is held
  to its limit;
- the harness gets the registry's change over the window
  (``system.registry_delta``), so ``span`` and ``counter`` readers find
  the program's own series.

A tree whose ``ShardedKNN`` takes no ``row_tags`` cannot run the cell:
that is asked of its signature before a row is drawn.

Traffic file: ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``) and ``strata``,
``check_rows_a_band``.
"""

from __future__ import annotations

import inspect

import numpy as np

import datagen
import datagen_tags
import reference_filter
import system
from harness import BenchError, Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


class FilteredCalls:
    """The placed program as ``sweep._window`` calls it
    (``search_certified(batch, selector=...) -> (d, i, stats)``), each
    pool batch answered under its own tags."""

    def __init__(self, prog, pool, pool_tags):
        self.prog = prog
        self.tags = {id(b): t for b, t in zip(pool, pool_tags)}

    def search_certified(self, batch, *, selector: str):
        return self.prog.search_certified(
            batch, selector=selector, filter_tags=self.tags[id(batch)])


def takes_row_tags() -> bool:
    from knn_tpu.parallel import ShardedKNN

    return "row_tags" in inspect.signature(ShardedKNN.__init__).parameters


def pick(seed: int, answered, rows: int, bands: np.ndarray, n_check: int,
         a_band: int):
    """The (pool batch, row) pairs whose answers are compared: ``a_band``
    drawn in every band that the answered batches hold (``bands``: each
    pool query's band), and ``sweep.sample``'s draw for the rest."""
    rng = datagen.rng_for(seed, datagen.STREAM_SAMPLE, 1)
    pick_b, pick_r = [], []
    for band in np.unique(bands):
        for _ in range(a_band):
            b = int(rng.choice(answered))
            at = np.flatnonzero(bands[b * rows:(b + 1) * rows] == band)
            if at.size:
                pick_b.append(b), pick_r.append(int(rng.choice(at)))
    more_b, more_r = sweep.sample(seed, answered, rows,
                                  n_check - len(pick_b))
    return (np.concatenate([more_b, pick_b]).astype(np.int64),
            np.concatenate([more_r, pick_r]).astype(np.int64))


def run(ctx: Ctx) -> Outcome:
    import jax

    if not takes_row_tags():
        raise BenchError("this tree's ShardedKNN takes no row_tags: the "
                         "cell cannot run on it")
    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    tags_spec, clusters = cfg["tags"], int(cfg["rows"]["clusters"])
    t = system.now()
    db, cluster = datagen_tags.draw(cfg["rows"], n, dim, ctx.seed,
                                    datagen.STREAM_ROWS)
    indptr, tags = datagen_tags.draw_bags(tags_spec, clusters, cluster,
                                          ctx.seed)
    say(f"set-up: drew {n:,} x {dim} rows and their bags "
        f"({tags.size:,} row-tag pairs) from seed {ctx.seed}: "
        f"{system.now() - t:.1f} s")
    t = system.now()
    inverted = datagen_tags.Inverted(indptr, tags,
                                     int(tags_spec["vocabulary"]))
    queries, q_tags, matches, held = datagen_tags.draw_queries(
        cfg["rows"], cfg["queries"], tags_spec, inverted, dim, ctx.seed,
        rows, n_pool, tr["strata"])
    del inverted
    bands = datagen_tags.stratum_of(matches, tr["strata"])
    pool = [queries[b * rows:(b + 1) * rows] for b in range(n_pool)]
    pool_tags = [q_tags[b * rows:(b + 1) * rows] for b in range(n_pool)]
    say(f"set-up: drew {n_pool} batches of {rows} tagged queries, bands "
        f"{[s[0] for s in tr['strata']]} hold {held[0].tolist()} a batch"
        f"{'' if (held == held[0]).all() else ' (NOT in every batch: ' + str(held.tolist()) + ')'}"
        f": {system.now() - t:.1f} s")
    t = system.now()
    placed = system.place(cfg, db, ctx.cell.chips, row_tags=(indptr, tags))
    prog = FilteredCalls(placed, pool, pool_tags)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.search_certified(pool[b], selector=tr["selector"])
        system.require(cfg, stats)
        if b == 0:
            say(f"set-up: first batch (places the tag index, compiles or "
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

    # correct: a seeded sample of the queries answered in the window,
    # some drawn in every band, against the float64 filtered oracle, on
    # the host and outside the window
    n_check, a_band = int(tr["check_rows"]), int(tr["check_rows_a_band"])
    pick_b, pick_r = pick(ctx.seed, sorted(last), rows, bands, n_check,
                          a_band)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    ft = np.stack([pool_tags[b][r] for b, r in zip(pick_b, pick_r)])
    got_d = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_i = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_i, want_d = reference_filter.oracle_topk(db, indptr, tags, q, ft, k)
    cmp = reference_filter.compare(got_i, got_d, want_i, want_d, indptr,
                                   tags, ft)
    say(f"check: float64 filtered oracle on {n_check} queries: "
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
