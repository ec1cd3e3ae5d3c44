#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place and computed in a LOWER precision than the configuration states,
at the cell's own size, on the queries a run of that seed compares.  It
has to come out as not correct.  Host arithmetic only (numpy), so it
needs no chip; no benchmark run calls it.

    python3 benchmark/control.py --workload gist1m.sweep --precision f32 \\
        --seeds 11,12,13

Prints, per seed, each number compared beside its limit and whether the
control came out correct (it must not), and last one JSON line with the
smallest of each number over the seeds: what a limit is set below.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402


def compared_queries(cell: harness.Cell, seed: int, seconds: float):
    """The corpus and the queries a run of ``seed`` compares."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    db = datagen.draw(cfg["rows"], n, dim, seed, datagen.STREAM_ROWS)
    driver = harness._module(tr["kind"], "drivers")
    if tr["kind"] == "sweep":
        rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
        queries = datagen.draw(cfg["rows"], rows * n_pool, dim, seed,
                               datagen.STREAM_QUERIES)
        pick_b, pick_r = driver.sample(seed, list(range(n_pool)), rows,
                                       int(tr["check_rows"]))
        return db, np.stack([queries[b * rows + r]
                             for b, r in zip(pick_b, pick_r)])
    pool = datagen.draw(cfg["rows"], int(tr["pool_rows"]), dim, seed,
                        datagen.STREAM_QUERIES)
    plan, offsets, sample, keep = driver.plan_and_sample(
        tr, seed, seconds, pool.shape[0])
    return db, np.concatenate(
        [pool[offsets[i]:offsets[i] + plan[i][1]][keep[i]] for i in sample])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", required=True,
                    choices=reference.PRECISIONS)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=None,
                    help="window the schedule is drawn for (default: "
                    "BENCHMARK.json run_seconds)")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    seconds = args.seconds or float(cell.bench["run_seconds"])
    limits, k = cell.config["limits"], int(cell.config["k"])
    sweep = cell.traffic["kind"] == "sweep"
    worst, any_correct = {}, False
    for seed in (int(s) for s in args.seeds.split(",")):
        db, q = compared_queries(cell, seed, seconds)
        want_i, want_d = reference.oracle_topk(db, q, k)
        got_i, got_d = reference.lowprec_topk(db, q, k, args.precision)
        cmp = reference.compare(got_i, got_d, want_i, want_d)
        checks = reference.Checks()
        if sweep:
            checks.add("mismatched_rows", cmp["mismatched_rows"],
                       limits["mismatched_rows"])
            checks.add("dist_rel_err_max", cmp["dist_rel_err_max"],
                       limits["dist_rel_err_max"])
        else:
            checks.add("recall", cmp["recall"], limits["recall_min"],
                       at_least=True)
            checks.add("serve_dist_rel_err_max", cmp["dist_rel_err_max"],
                       limits["serve_dist_rel_err_max"])
        any_correct = any_correct or checks.correct
        print(f"seed {seed}: {args.precision} control on {cmp['rows']} "
              f"queries: " + "; ".join(
                  f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
                  f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
                  for r in checks.rows)
              + f" -> correct={checks.correct}", flush=True)
        for r in checks.rows:
            v = -r["value"] if r["rule"] == ">=" else r["value"]
            worst[r["check"]] = min(worst.get(r["check"], np.inf), v)
    print(json.dumps({
        "workload": args.workload, "precision": args.precision,
        "control_came_out_correct_on_some_seed": any_correct,
        "closest_to_sound": {k_: (-v if k_ == "recall" else v)
                             for k_, v in worst.items()}}))
    return 1 if any_correct else 0


if __name__ == "__main__":
    sys.exit(main())
