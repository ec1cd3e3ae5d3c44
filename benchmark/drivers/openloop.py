"""Traffic kind ``openloop``: requests arrive on a schedule fixed in
advance (``arrivals.schedule``: the same requests and gaps for every
seed, in the order the seed shuffles them into), whatever the server is
doing, through ``QueryQueue`` over ``ServingEngine``.  Each request's
latency runs from when it was DUE to when its answer was set; a request
with no answer when the drain after the window ends counts as failed
and as slower than any limit.  One sender, on the main thread; how late
it ran is reported (``gen_late_p95_ms``).

Traffic file: ``rate_rps``, ``mix``, ``buckets``,
``max_wait_ms``, ``pool_rows``, ``drain_seconds``, ``check_requests``,
``check_rows_per_request``, ``trace_lead_seconds``, ``trace_seconds``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

import arrivals
import datagen
import reference
import system
from harness import Ctx, Outcome, resident_bytes, say


def _sleep_until(t: float) -> None:
    while True:
        left = t - system.now()
        if left <= 0:
            return
        time.sleep(left)


def plan_and_sample(tr: dict, seed: int, seconds: float, pool_rows: int):
    """The schedule ``[(due_s, rows)]``, each request's offset into the
    query pool, the requests whose answers are compared (drawn from the
    seed, one of the longest among them), and for each the rows of it
    that are compared."""
    rng = datagen.rng_for(seed, datagen.STREAM_TRAFFIC)
    plan = arrivals.schedule(tr, seconds, rng)
    longest = max(r for _, r in plan)
    offsets = rng.integers(0, pool_rows - longest + 1, size=len(plan))
    srng = datagen.rng_for(seed, datagen.STREAM_SAMPLE)
    sample = set(srng.choice(
        len(plan), size=min(int(tr["check_requests"]), len(plan)),
        replace=False).tolist())
    sample.add(int(srng.choice([i for i, (_, r) in enumerate(plan)
                                if r == longest])))
    per = int(tr["check_rows_per_request"])
    keep = {i: np.sort(srng.choice(plan[i][1], size=min(plan[i][1], per),
                                   replace=False))
            for i in sorted(sample)}
    return plan, offsets, sorted(sample), keep


def setup(ctx: Ctx):
    """Draw the corpus and the query pool from the seed, place the
    corpus, build the engine and warm its buckets."""
    from knn_tpu.serving import ServingEngine

    cfg, tr = ctx.config, ctx.traffic
    n, dim, k = int(cfg["rows_n"]), int(cfg["dim"]), int(cfg["k"])
    t = system.now()
    db = datagen.draw(cfg["rows"], n, dim, ctx.seed, datagen.STREAM_ROWS)
    pool = datagen.draw(cfg["rows"], int(tr["pool_rows"]), dim, ctx.seed,
                        datagen.STREAM_QUERIES)
    say(f"set-up: drew {n:,} x {dim} rows and a pool of {pool.shape[0]} "
        f"queries from seed {ctx.seed}: {system.now() - t:.1f} s")
    t = system.now()
    prog = system.place(cfg, db, ctx.cell.chips)
    say(f"set-up: placed: {system.now() - t:.1f} s")
    t = system.now()
    eng = ServingEngine(prog, buckets=tuple(int(b) for b in tr["buckets"]))
    counts = eng.warmup()
    say(f"set-up: warm-up compiled or loaded {counts}: "
        f"{system.now() - t:.1f} s")
    return db, pool, eng


def run(ctx: Ctx) -> Outcome:
    return serve(ctx, *setup(ctx), ctx.traffic)


def serve(ctx: Ctx, db, pool, eng, tr: dict) -> Outcome:
    """One window of the traffic ``tr`` against a warm engine (the rate
    sweep calls this once per rate), and the check after it."""
    import jax

    from knn_tpu.serving import QueryQueue

    cfg, k = ctx.config, int(ctx.config["k"])
    lead = float(tr["trace_lead_seconds"]) if ctx.traced else 0.0
    seconds = lead + min(ctx.seconds, float(tr["trace_seconds"])) \
        if ctx.traced else ctx.seconds
    plan, offsets, sample, keep = plan_and_sample(
        tr, ctx.seed, seconds, pool.shape[0])
    longest = max(r for _, r in plan)
    futures = {}

    done_t = np.full(len(plan), np.nan)
    errors = []
    lock = threading.Lock()

    def on_done(i, fut):
        t_done = system.now()
        exc = fut.exception()
        with lock:
            if exc is None:
                done_t[i] = t_done
            else:
                errors.append((i, repr(exc)))

    sent_t = np.empty(len(plan))
    with QueryQueue(eng, max_wait_ms=float(tr["max_wait_ms"])) as queue:
        # one request of each size through the queue, so the host path
        # (coalescing, padding, delivery) has run before the window
        for rows in sorted({r for _, r in plan}):
            queue.submit(pool[:rows]).result(timeout=300)
        compiles_before = system.COMPILES["backend_compiles"]
        window_span = None
        snap0 = snap1 = None
        if ctx.traced:
            jax.profiler.start_trace(ctx.trace_dir)
        else:
            snap0 = system.registry_snapshot()
        setup_s = system.now() - ctx.t_found
        t0 = system.now()
        try:
            for i, (due, rows) in enumerate(plan):
                if ctx.traced and snap0 is None and due >= lead:
                    _sleep_until(t0 + lead)
                    snap0 = system.registry_snapshot()
                    # made here: an annotation made before start_trace
                    # records nothing
                    window_span = jax.profiler.TraceAnnotation(
                        "bench.trace_window")
                    window_span.__enter__()
                with jax.profiler.TraceAnnotation("bench.wait"):
                    _sleep_until(t0 + due)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    sent_t[i] = system.now()
                    q = pool[offsets[i]:offsets[i] + rows]
                    fut = queue.submit(q)
                    fut.add_done_callback(
                        lambda f, i=i: on_done(i, f))
                    if i in keep:
                        futures[i] = fut
            with jax.profiler.TraceAnnotation("bench.wait"):
                _sleep_until(t0 + seconds)
            if ctx.traced:
                window_span.__exit__(None, None, None)
                snap1 = system.registry_snapshot()
            # drain: answers still due may come for this long
            with jax.profiler.TraceAnnotation("bench.drain"):
                deadline = system.now() + float(tr["drain_seconds"])
                while system.now() < deadline:
                    with lock:
                        if np.isfinite(done_t).sum() + len(errors) \
                                == len(plan):
                            break
                    time.sleep(0.002)
            t_end = system.now()
        finally:
            if ctx.traced:
                jax.profiler.stop_trace()
        if snap1 is None:
            snap1 = system.registry_snapshot()
        with lock:
            done = done_t.copy()
        resident = resident_bytes(ctx.cell.chips)
        answers = {i: f.result() for i, f in futures.items()
                   if np.isfinite(done[i])}
    compiled = system.COMPILES["backend_compiles"] - compiles_before

    due_abs = t0 + np.array([d for d, _ in plan])
    lat_ms = np.where(np.isfinite(done), (done - due_abs) * 1e3, np.inf)
    failed = int((~np.isfinite(done)).sum())
    late_ms = (sent_t - due_abs) * 1e3
    p50, p95 = (arrivals.percentile(lat_ms, p) for p in (50, 95))
    backlog = int((~(done <= t0 + seconds)).sum())
    registry = system.registry_delta(snap0, snap1)
    say(f"window: {len(plan)} requests ({sum(r for _, r in plan)} rows) "
        f"offered over {seconds:.1f} s at {tr['rate_rps']} /s; {failed} "
        f"without an answer {tr['drain_seconds']} s after it "
        f"({len(errors)} raised: {errors[:3]}); {backlog} "
        f"unanswered when it closed, last answer "
        f"{t_end - t0 - seconds:+.3f} s after it; latency from due "
        f"time p50 {p50:.3f} ms, p95 {p95:.3f} ms over {len(plan)} "
        f"samples; sender late p95 "
        f"{arrivals.percentile(late_ms, 95):.3f} ms, max "
        f"{late_ms.max():.3f} ms; engine dispatches "
        f"{eng.stats()['per_bucket_dispatches']}; programs compiled "
        f"inside the window: {compiled}")

    # correct: the sampled requests that were answered, against the
    # float64 oracle, on the host and outside the window
    checks = reference.Checks()
    qs, got_i, got_d = [], [], []
    for i in sorted(answers):
        rows = plan[i][1]
        d, idx = answers[i]
        if np.asarray(idx).shape != (rows, k):
            raise RuntimeError(f"request {i}: answer shape "
                               f"{np.asarray(idx).shape}, not {(rows, k)}")
        qs.append(pool[offsets[i]:offsets[i] + rows][keep[i]])
        got_i.append(np.asarray(idx)[keep[i]])
        got_d.append(np.asarray(d)[keep[i]])
    if qs:
        q = np.concatenate(qs)
        t = system.now()
        want_i, want_d = reference.oracle_topk(db, q, k)
        say(f"check: float64 oracle on {q.shape[0]} rows of "
            f"{len(answers)} requests (longest {longest} rows): "
            f"{system.now() - t:.1f} s")
        cmp = reference.compare(np.concatenate(got_i),
                                np.concatenate(got_d), want_i, want_d)
        say(f"check: rows with any index off the oracle's: "
            f"{cmp['mismatched_rows']} of {cmp['rows']} (the exact path "
            f"ranks in float32; held to recall, not to equality)")
        limits = cfg["limits"]
        checks.add("recall", cmp["recall"], limits["recall_min"],
                   at_least=True)
        checks.add("serve_dist_rel_err_max", cmp["dist_rel_err_max"],
                   limits["serve_dist_rel_err_max"])
    checks.add("sampled_requests_answered", len(answers),
               min(len(sample), 1), at_least=True)
    checks.add("compiles_in_window", compiled, 0)

    return Outcome(
        attempted=len(plan), failed=failed,
        end_to_end={"setup_s": setup_s, "serve_p50_ms": p50},
        checks=checks,
        bench={"requests": float(len(plan)), "p95_ms": p95,
               "gen_late_p95_ms": arrivals.percentile(late_ms, 95),
               "completed_per_s": float(np.isfinite(done).sum())
               / (t_end - t0),
               # still unanswered when the window closed, and how long
               # after it the last answer came: a backlog that grows
               # with the window means the rate is not sustained
               "backlog_at_close": float(backlog),
               "last_answer_lag_s": t_end - t0 - seconds},
        registry=registry, resident_bytes=resident)
