"""Traffic kind ``graph_build``: a closed loop with one caller whose
every call is a BULK job, the exact k-NN graph of a run of consecutive
rows of the placed corpus (``knn_tpu.join.knn_self_join(prog, rows=(lo,
hi))``, no knob passed).  Every row is a query of the corpus it is part
of, and its own row is no answer; the program cuts a call into blocks
and owns their pipeline.  ``sweep_qps`` is all the rows answered over
all the time from the window's start to the last answer: rows are its
queries.

What differs from ``sweep.py``, and nothing else:

- there is no query set: the rows (``datagen_graph.py``: unit-length
  clustered rows, a share of them exact copies of another row) are the
  queries, so nothing is drawn, padded or sent for a call but two
  numbers;
- a call is ``call_rows`` consecutive rows (131,072: 32 blocks of
  ``block_rows``), the first at a block offset drawn from the seed, each
  next one the next rows, wrapping at ``rows_n`` (the last call before
  the wrap is the rows that are left); the next call is sent when the
  last one's arrays are back on the host;
- the oracle and the comparison are ``reference_graph.py``'s (float64
  distances of a checked row to all rows, the row itself out by id,
  lexicographic (distance, id) top-k), over ``check_rows`` rows of the
  window's FIRST call as that call returned them, half of them rows
  that have an exact copy;
- every answer of the window is also held to the one thing that can be
  read off it without an oracle: no row names itself
  (``self_in_answers``), and the program says it took every row's own
  out (``self_excluded``);
- before anything is drawn or placed the program is asked whether it
  HAS the path (``knn_tpu.join.knn_self_join``): a tree from before it
  ends here, in under a second, with a plain message.

The ``bench`` readings carry ``sweep``'s names with a batch = one BLOCK
of ``block_rows`` rows (``batches``, ``queries``, ``certified``,
``fallback_queries``, ``rank_corrected_queries``), so the layer files
that give milliseconds a batch serve this cell and compare with the
other cells', and ``calls`` beside them.

Traffic file: ``call_rows``, ``block_rows``, ``batch_rows`` (the block
again, for ``work/knn_scan.py``), ``selector``, ``check_rows``,
``check_copied_share``, ``trace_seconds``.
"""

from __future__ import annotations

import numpy as np

import datagen
import datagen_graph
import reference_graph
import system
from harness import BenchError, Ctx, Outcome, resident_bytes, say
from reference import Checks


def the_path():
    """The program's bulk self-join, or a plain refusal before a row is
    drawn on a program without one."""
    try:
        from knn_tpu.join import knn_self_join
    except ImportError:
        raise BenchError(
            "this program's knn_tpu.join has no knn_self_join: it cannot "
            "answer every row of a placement against the placement with "
            "the row itself left out, so the cell cannot run on it")
    return knn_self_join


def first_row(seed: int, n: int, block: int) -> int:
    """The window's first row: a block offset drawn from the seed."""
    return block * int(datagen.rng_for(seed, datagen.STREAM_TRAFFIC)
                       .integers(0, n // block))


def _window(ctx: Ctx, join, prog, start: int, seconds: float):
    """Drive calls until ``seconds`` have passed; returns (calls, elapsed
    to the last answer, summed stats, the first call's ``(lo, d, i)``)."""
    import jax

    n, rows = int(ctx.config["rows_n"]), int(ctx.traffic["call_rows"])
    totals = {"queries": 0, "batches": 0, "certified": 0,
              "fallback_queries": 0, "rank_corrected_queries": 0,
              "self_excluded": 0, "self_in_answers": 0,
              "uncounted_batches": 0}
    first, calls, lo = None, 0, start
    t0 = system.now()
    while True:
        hi = min(lo + rows, n)
        with jax.profiler.TraceAnnotation("bench.call"):
            d, i, stats = join(prog, rows=(lo, hi))
        with jax.profiler.TraceAnnotation("bench.host-after-batch"):
            d, i = np.asarray(d), np.asarray(i)
            system.require(ctx.config, stats)
            totals["queries"] += hi - lo
            totals["batches"] += stats["superblocks"]
            for key in ("certified", "fallback_queries",
                        "rank_corrected_queries", "self_excluded"):
                totals[key] += stats[key]
            totals["self_in_answers"] += int(
                (i == np.arange(lo, hi)[:, None]).sum())
            if (stats["certified"] + stats["fallback_queries"] != hi - lo
                    or i.shape != (hi - lo, int(ctx.config["k"]))):
                totals["uncounted_batches"] += 1
            if first is None:
                first = (lo, d, i)
            calls += 1
            lo = hi % n
            elapsed = system.now() - t0
        if elapsed >= seconds:
            return calls, elapsed, totals, first


def run(ctx: Ctx) -> Outcome:
    import jax

    join = the_path()
    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    rows, block = int(tr["call_rows"]), int(tr["block_rows"])
    if tr["selector"] != "pallas" or int(tr["batch_rows"]) != block:
        raise BenchError(
            f"traffic kind graph_build runs the certified self-join "
            f"(selector pallas) in blocks of batch_rows = block_rows; got "
            f"selector {tr['selector']!r}, batch_rows {tr['batch_rows']}, "
            f"block_rows {block}")
    t = system.now()
    db, pairs = datagen_graph.draw_rows(cfg["rows"], n, dim, ctx.seed,
                                        datagen.STREAM_ROWS)
    say(f"set-up: drew {n:,} x {dim} unit rows, {len(pairs):,} of them "
        f"exact copies of another row, from seed {ctx.seed}: "
        f"{system.now() - t:.1f} s")
    t = system.now()
    prog = system.place(cfg, db, ctx.cell.chips)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    # two blocks, then a whole call: every program the window can meet
    # (the self program at a launch's rows, the placement's row
    # operands, the re-select's one shape, which the program runs once
    # itself) is compiled, and the pipeline has run at its depth
    start = first_row(ctx.seed, n, block)
    t = system.now()
    _, _, stats = join(prog, rows=(start, min(start + 2 * block, n)))
    system.require(cfg, stats)
    say(f"set-up: first batch (compiles or loads): "
        f"{system.now() - t:.1f} s; knobs {stats['pallas_knobs']}")
    _, _, stats = join(prog, rows=(max(0, n - rows), n))
    say(f"set-up: warmed a call of {rows} rows in {stats['superblocks']} "
        f"blocks, depth {stats['depth']}: {system.now() - t:.1f} s")

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
            calls, elapsed, totals, first = _window(ctx, join, prog, start,
                                                    seconds)
    finally:
        if ctx.traced:
            jax.profiler.stop_trace()
    registry = system.registry_delta(before, system.registry_snapshot())
    compiled = system.COMPILES["backend_compiles"] - compiles_before
    resident = resident_bytes(ctx.cell.chips)
    say(f"window: {calls} calls, {totals['batches']} blocks, "
        f"{totals['queries']} rows in {elapsed:.3f} s; certified "
        f"{totals['certified']} + fallback {totals['fallback_queries']}; "
        f"rank-corrected {totals['rank_corrected_queries']}; own rows taken "
        f"out {totals['self_excluded']}; programs compiled inside the "
        f"window: {compiled}")

    # correct: rows of the window's FIRST call, as it returned them,
    # against the float64 oracle, on the host and outside the window
    lo, got_d, got_i = first
    at = datagen_graph.check_rows(
        pairs, lo, lo + got_i.shape[0], int(tr["check_rows"]), ctx.seed,
        datagen.STREAM_SAMPLE, float(tr["check_copied_share"]))
    t = system.now()
    want_i, want_d = reference_graph.oracle_graph(db, at, k)
    copied = int(np.isin(at, pairs).sum())
    say(f"check: float64 oracle (reference_graph) on {at.size} rows of "
        f"the first call ({lo} to {lo + got_i.shape[0]}), {copied} of them "
        f"rows that have an exact copy: {system.now() - t:.1f} s")
    cmp = reference_graph.compare(got_i[at - lo], got_d[at - lo],
                                  want_i, want_d)
    checks = Checks()
    for name, limit in cfg["limits"].items():
        checks.add(name, cmp[name], limit)
    checks.add("checked_copied_rows", copied,
               int(at.size * float(tr["check_copied_share"])),
               at_least=True)
    checks.add("self_in_answers", totals["self_in_answers"], 0)
    checks.add("self_not_excluded",
               totals["queries"] - totals["self_excluded"], 0)
    checks.add("uncounted_batches", totals["uncounted_batches"], 0)
    checks.add("compiles_in_window", compiled, 0)

    return Outcome(
        attempted=totals["queries"], failed=0,
        end_to_end={"setup_s": setup_s,
                    "sweep_qps": totals["queries"] / elapsed},
        checks=checks,
        bench={"calls": float(calls), **{
            key: float(v) for key, v in totals.items()}},
        registry=registry, resident_bytes=resident)
