#!/usr/bin/env python3
"""The rate sweep of an open-loop cell: one process, one placement, one
window per rate, to find the highest rate the system sustains without a
growing backlog.  Run once when a cell is defined (its traffic file's
``rate_rps`` is then fixed at about four fifths of that rate); no
benchmark run calls it.

    python3 benchmark/ratesweep.py --workload bigann5m.serve --seed 7 \\
        --seconds 10 --rates 50,100,200,400,800

Prints one JSON line per rate: offered and completed requests a second,
latency from the due time (p50, p95), the requests still unanswered when
the window closed and how long after it the last answer came, and how
late the sender ran.  A rate is sustained where the backlog at the close
is a few requests (what is in flight) and the last answer comes within
a few dispatch times; above it both grow with the window's length.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests a second, rising")
    args = ap.parse_args(argv)
    import jax

    import system

    if jax.devices()[0].platform != "tpu":
        print("ratesweep.py needs a TPU", file=sys.stderr)
        return 1
    system.listen_to_compiles()
    harness.say(f"compile cache: {system.enable_compile_cache()}")
    cell = harness.load_cell(os.path.dirname(HERE), args.workload)
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      traced=False, t_found=time.perf_counter(), trace_dir="")
    driver = harness._module(cell.traffic["kind"], "drivers")
    state = driver.setup(ctx)
    for rate in (float(r) for r in args.rates.split(",")):
        out = driver.serve(ctx, *state, dict(cell.traffic, rate_rps=rate))
        print(json.dumps({
            "rate_rps": rate, "seconds": args.seconds,
            "attempted": out.attempted, "failed": out.failed,
            "completed_per_s": out.bench["completed_per_s"],
            "p50_ms": out.end_to_end["serve_p50_ms"],
            "p95_ms": out.bench["p95_ms"],
            "backlog_at_close": out.bench["backlog_at_close"],
            "last_answer_lag_s": out.bench["last_answer_lag_s"],
            "gen_late_p95_ms": out.bench["gen_late_p95_ms"],
            "correct": out.checks.correct}), flush=True)
        time.sleep(1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
