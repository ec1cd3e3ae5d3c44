"""Traffic kind ``sweep_vote``: ``drivers/sweep.py``'s closed loop with
one caller for a configuration whose answer is a CLASS, not a neighbour
list.  Consecutive query batches, cycled from a pool drawn from the
seed, go through ``ShardedKNN.predict_certified(vote="softmax",
temperature=..., classes_out=..., selector=...)`` with no knob passed;
each batch's classes and totals are back on the host as numpy arrays
before the next is sent.  ``sweep_qps`` is all the queries answered over
all the time from the window's start to the last answer.

What differs from ``sweep_ip.py``, and nothing else:

- the rows come with a label each and the queries with the class they
  were drawn from (``datagen_labels.py``, by the configuration's ``rows``
  entry); the labels are handed to the placement (``system.place(...,
  labels=, num_classes=)``: public constructor arguments);
- the oracle and the comparison are ``reference_vote.py``'s (float64
  cosine neighbours of the rows as given, the weighted vote, the first
  ``classes_out`` classes by (-total, class) and their totals), and
  every number of the configuration's ``limits`` that the comparison
  gives is held to its limit;
- before anything is drawn or placed the program is asked whether it
  HAS the path (``predict_certified`` takes ``vote``): a tree from before
  it ends here, in under a second, with a plain message;
- the run prints ``ref_top1_pct``, the share of the checked queries
  whose reference top-1 class is the class the query was drawn from, and
  ``top1_pct``, the same share of every query of the pool by the
  program's answers: a corpus on which every neighbour agrees, or none,
  is seen (the configuration's ``assumed`` entry names the range).

The window (``sweep.sample`` and the loop's shape) is ``sweep.py``'s; the
loop itself is written out here because its call and its answer differ.
The ``bench`` readings carry ``sweep``'s names (``batches``,
``queries``, ``certified``, ``fallback_queries``) so the layer files
that serve it serve this, and ``vote_boundary_queries``,
``vote_margin_queries`` beside them.

Traffic file: as ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``).
"""

from __future__ import annotations

import inspect

import numpy as np

import datagen
import datagen_labels
import reference_vote
import system
from harness import BenchError, Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


def require_the_path() -> None:
    """Fail, before a row is drawn, on a program without the voted
    path."""
    from knn_tpu.parallel import ShardedKNN

    have = inspect.signature(ShardedKNN.predict_certified).parameters
    lack = [p for p in ("vote", "temperature", "classes_out")
            if p not in have]
    if lack:
        raise BenchError(
            f"this program's ShardedKNN.predict_certified takes no "
            f"{', '.join(lack)}: it has no weighted vote on the certified "
            f"path, so the cell cannot run on it")


def _window(ctx: Ctx, prog, pool, call: dict, seconds: float):
    """Drive batches until ``seconds`` have passed; returns (batches,
    elapsed to the last answer, summed stats, last answer per pool
    batch, answers that differed from an earlier one of the same
    batch)."""
    import jax

    totals = {"queries": 0, "certified": 0, "fallback_queries": 0,
              "vote_boundary_queries": 0, "vote_margin_queries": 0,
              "uncounted_batches": 0}
    last, changed, n = {}, 0, 0
    t0 = system.now()
    while True:
        b = n % len(pool)
        with jax.profiler.TraceAnnotation("bench.call"):
            classes, tot, stats = prog.predict_certified(pool[b], **call)
        with jax.profiler.TraceAnnotation("bench.host-after-batch"):
            classes, tot = np.asarray(classes), np.asarray(tot)
            system.require(ctx.config, stats)
            rows = pool[b].shape[0]
            totals["queries"] += rows
            for key in ("certified", "fallback_queries",
                        "vote_boundary_queries", "vote_margin_queries"):
                totals[key] += stats[key]
            if stats["certified"] + stats["fallback_queries"] != rows:
                totals["uncounted_batches"] += 1
            if b in last and not (np.array_equal(last[b][0], classes)
                                  and np.array_equal(last[b][1], tot)):
                changed += 1
            last[b] = (classes, tot)
            n += 1
            elapsed = system.now() - t0
        if elapsed >= seconds:
            return n, elapsed, totals, last, changed


def run(ctx: Ctx) -> Outcome:
    import jax

    require_the_path()
    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    n_classes, out = int(cfg["classes"]), int(cfg["classes_out"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    call = {"vote": "softmax", "temperature": float(cfg["temperature"]),
            "classes_out": out, "selector": tr["selector"]}
    t = system.now()
    db, labels = datagen_labels.draw_rows(cfg["rows"], n, dim, ctx.seed,
                                          datagen.STREAM_ROWS)
    queries, asked = datagen_labels.draw_queries(
        cfg["rows"], rows * n_pool, dim, ctx.seed, datagen.STREAM_QUERIES)
    pool = [queries[b * rows:(b + 1) * rows] for b in range(n_pool)]
    sizes = np.bincount(labels, minlength=n_classes)
    say(f"set-up: drew {n:,} x {dim} rows of {n_classes} classes "
        f"({sizes.min()} to {sizes.max()} rows a class) "
        f"and {n_pool} batches of {rows} queries from seed {ctx.seed}: "
        f"{system.now() - t:.1f} s")
    t = system.now()
    prog = system.place(cfg, db, ctx.cell.chips, labels=labels,
                        num_classes=n_classes)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the second read's buckets and the
    # repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.predict_certified(pool[b], **call)
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
                ctx, prog, pool, call, seconds)
    finally:
        if ctx.traced:
            jax.profiler.stop_trace()
    registry = system.registry_delta(before, system.registry_snapshot())
    compiled = system.COMPILES["backend_compiles"] - compiles_before
    resident = resident_bytes(ctx.cell.chips)
    say(f"window: {batches} batches, {totals['queries']} queries in "
        f"{elapsed:.3f} s; certified {totals['certified']} + fallback "
        f"{totals['fallback_queries']}; re-voted for the boundary "
        f"{totals['vote_boundary_queries']}, for a margin "
        f"{totals['vote_margin_queries']}; programs compiled inside the "
        f"window: {compiled}")
    hit = sum(int((last[b][0][:, 0] == asked[b * rows:(b + 1) * rows]).sum())
              for b in last)
    say(f"top1_pct: {100.0 * hit / (rows * len(last)):.2f} (the program's "
        f"first class is the class the query was drawn from, over the "
        f"{rows * len(last)} queries of the batches answered)")

    # correct: a seeded sample of the queries answered in the window,
    # against the float64 oracle, on the host and outside the window
    n_check = int(tr["check_rows"])
    pick_b, pick_r = sweep.sample(ctx.seed, sorted(last), rows, n_check)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got_c = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_t = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_c, want_t, _ = reference_vote.oracle(
        db, labels, q, k, float(cfg["temperature"]), n_classes, out)
    say(f"check: float64 oracle (reference_vote) on {n_check} queries: "
        f"{system.now() - t:.1f} s")
    own = asked[pick_b * rows + pick_r]
    say(f"ref_top1_pct: {100.0 * float((want_c[:, 0] == own).mean()):.2f} "
        f"(the reference's first class is the class the query was drawn "
        f"from, over the {n_check} checked queries)")
    cmp = reference_vote.compare(got_c, got_t, want_c, want_t)
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
