"""Traffic kind ``sweep_topk``: ``drivers/sweep_ip.py``'s closed loop
(``sweep``'s ``_window`` and ``sample``, imported; the rows and queries
by the configuration's ``rows`` and ``queries`` entries, the oracle and
the comparison of ``reference_<configuration's "reference">.py``, every
number of the configuration's ``limits`` held to its limit, the
registry's change over the window for the ``span`` and ``counter``
readers) for a cell whose k is the deployment's own and LARGE, with two
differences and nothing else:

- the PLAN comes first.  After placing and before the first call the
  program is asked what a call of ``batch_rows`` queries would run
  (``ShardedKNN.certified_plan``), the answer is printed, and it is
  held to the configuration's ``require.plan``: ``overflow_share_max``
  (the modelled share of queries whose certificate fails on a full bin,
  at the survivor depth the program chose) and ``fits`` (a launch's
  bytes inside what the device has left beside the placement).  A
  program with no such method, and one whose plan fails, give ``no
  result`` there: the former before a row is drawn, in under a second
  (a tree from before the method cannot run k = 1,024 in any useful
  sense: 94 % of its queries would go to the host's repair and a launch
  of 1,024 queries would not fit the chip);
- the answer's bytes a batch (indices and distances as they reach the
  host) are a ``bench`` reading, ``answer_bytes``, beside ``sweep``'s.

Traffic file: as ``sweep``'s (``batch_rows``, ``pool_batches``,
``selector``, ``check_rows``, ``trace_seconds``).
"""

from __future__ import annotations

import importlib

import numpy as np

import datagen
import datagen_mix
import system
from harness import BenchError, Ctx, Outcome, _module, resident_bytes, say
from reference import Checks

sweep = _module("sweep", "drivers")


def the_plan_method():
    """``ShardedKNN.certified_plan``, or a plain refusal before a row is
    drawn on a program without one."""
    from knn_tpu.parallel import ShardedKNN

    if not hasattr(ShardedKNN, "certified_plan"):
        raise BenchError(
            "this program's ShardedKNN has no certified_plan: it cannot "
            "say what a certified call would run before the call is made "
            "(survivor depth, sub-batch, what a launch holds), so the "
            "cell cannot run on it")
    return ShardedKNN.certified_plan


def hold_plan(plan: dict, want: dict) -> None:
    """Hold a plan to the configuration's ``require.plan``."""
    share, limit = plan["overflow_share"], want.get("overflow_share_max")
    if limit is not None and not share <= limit:
        raise BenchError(
            f"the plan's modelled full-bin fallback share {share:.4g} at "
            f"survivor depth {plan['survivor_depth']} is over the "
            f"configuration's {limit}")
    room = plan["room_bytes"]
    if want.get("fits") and room and plan["launch_bytes"] > room:
        raise BenchError(
            f"the plan's launch of {plan['sub_batch_rows']} queries holds "
            f"{plan['launch_bytes']:,} bytes; the device has "
            f"{room:,} beside the placement")


def run(ctx: Ctx) -> Outcome:
    import jax

    plan_of = the_plan_method()
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
    t = system.now()
    plan = plan_of(prog, rows)
    say(f"set-up: plan of a {rows}-query call (row operands placed): "
        f"{system.now() - t:.1f} s; {plan}")
    hold_plan(plan, cfg.get("require", {}).get("plan", {}))
    # every batch of the pool once: the window then repeats exactly this
    # work, so every program it needs (the repair's too) is compiled
    t = system.now()
    for b in range(n_pool):
        _, _, stats = prog.search_certified(pool[b], selector=tr["selector"])
        system.require(cfg, stats)
        if b == 0:
            say(f"set-up: first batch (compiles or loads): "
                f"{system.now() - t:.1f} s; knobs {stats['pallas_knobs']}")
            told = {key: (plan[key], stats["pallas_knobs"][key])
                    for key in plan.keys() & stats["pallas_knobs"].keys()
                    if plan[key] != stats["pallas_knobs"][key]}
            if told:
                raise BenchError(
                    f"the call ran another plan than the program gave "
                    f"before it (plan, call): {told}")
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
    answer_bytes = sum(a.nbytes for a in next(iter(last.values())))
    say(f"window: {batches} batches, {totals['queries']} queries in "
        f"{elapsed:.3f} s; certified {totals['certified']} + fallback "
        f"{totals['fallback_queries']}; rank-corrected "
        f"{totals['rank_corrected_queries']}; {answer_bytes:,} bytes an "
        f"answer; programs compiled inside the window: {compiled}")

    # correct: a seeded sample of the queries answered in the window,
    # against the float64 oracle, on the host and outside the window
    n_check = int(tr["check_rows"])
    pick_b, pick_r = sweep.sample(ctx.seed, sorted(last), rows, n_check)
    q = np.stack([pool[b][r] for b, r in zip(pick_b, pick_r)])
    got_d = np.stack([last[b][0][r] for b, r in zip(pick_b, pick_r)])
    got_i = np.stack([last[b][1][r] for b, r in zip(pick_b, pick_r)])
    t = system.now()
    want_i, want_d = ref.oracle_topk(db, q, k)
    say(f"check: float64 oracle ({ref.__name__}) on {n_check} queries at "
        f"k={k}: {system.now() - t:.1f} s")
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
        bench={"batches": float(batches),
               "answer_bytes": float(answer_bytes), **{
                   key: float(v) for key, v in totals.items()}},
        registry=registry, resident_bytes=resident)
