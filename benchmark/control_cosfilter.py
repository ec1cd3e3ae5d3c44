#!/usr/bin/env python3
"""``control_cos.py`` and ``control_filter.py`` for a ``sweep_cos_filter``
cell: the plain reference (``reference_cosfilter.py``) put in the
program's place in each of three BROKEN forms, at the cell's own size,
on the seeded queries a run of that seed compares (the repaired queries
a run adds to its sample are the program's to name, so they are not
here), under the configuration's own ``limits``.  Each has to come out
as not correct, by the limits the configuration's ``controls`` entry
names for it.  Host arithmetic only (numpy), so it needs no chip; no
benchmark run calls it.

- ``post_filter``: the unfiltered float64 top-k with the rows outside
  each query's range dropped and the rest padded: what a post-filter
  gives (at 99 % filtered out about one row of a hundred survives);
- ``f32``: unit rows and one product in float32 over the rows in range;
- ``bf16``: the same rounded to bfloat16.

    python3 benchmark/control_cosfilter.py \\
        --workload openai500k-intfilter.sweep_cos_filter --seeds 11,12,13

Prints, per seed and control, each number compared beside its limit and
which limits broke, and last one JSON line with the smallest of each
number over the seeds (what a limit is set below) and whether every
seed broke what the configuration names.  Exit code 0 only then.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
import reference_cosfilter  # noqa: E402
from reference import Checks  # noqa: E402


def compared_queries(cell: harness.Cell, seed: int):
    """The corpus, its ids, and the seeded queries and ranges a run of
    ``seed`` compares, where the window answered every batch of the pool
    and repaired nothing."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    driver = harness._module(tr["kind"], "drivers")
    db = datagen_mix.draw(cfg["rows"], n, dim, seed, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(
        cfg.get("queries", cfg["rows"]), rows * n_pool, dim, seed,
        datagen.STREAM_QUERIES, of=cfg["rows"])
    pick_b, pick_r = driver.pick(seed, list(range(n_pool)), rows,
                                 len(tr["filter_from"]),
                                 int(tr["check_rows"]))
    ranges = driver.batch_ranges(tr["filter_from"], rows, n)[pick_r]
    return db, driver.row_ids(n), queries[pick_b * rows + pick_r], ranges


def control_answer(control: str, db, ids, q, ranges, k: int):
    if control == "post_filter":
        return reference_cosfilter.post_filter_topk(db, ids, q, ranges, k)
    return reference_cosfilter.lowprec_topk(db, ids, q, ranges, k, control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.traffic["kind"] != "sweep_cos_filter":
        raise SystemExit(f"{args.workload} is no sweep_cos_filter cell")
    named = cell.config["controls"]
    k = int(cell.config["k"])
    closest, as_named = {c: {} for c in named}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        db, ids, q, ranges = compared_queries(cell, seed)
        want_i, want_d = reference_cosfilter.oracle_topk(db, ids, q,
                                                         ranges, k)
        for control, must in named.items():
            got_i, got_d = control_answer(control, db, ids, q, ranges, k)
            cmp = reference_cosfilter.compare(got_i, got_d, want_i, want_d,
                                              ids, ranges)
            checks = Checks()
            for name, limit in cell.config["limits"].items():
                checks.add(name, cmp[name], limit)
            broke = {r["check"] for r in checks.rows if not r["ok"]}
            as_named = as_named and not checks.correct and set(must) <= broke
            print(f"seed {seed}: {control} control on {cmp['rows']} queries "
                  f"({cmp['short_rows']} short, {cmp['empty_rows']} empty by "
                  f"the oracle): " + "; ".join(
                      f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
                      f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
                      for r in checks.rows)
                  + f" -> correct={checks.correct}, broke {sorted(broke)}, "
                    f"the configuration names {sorted(must)}", flush=True)
            for r in checks.rows:
                closest[control][r["check"]] = min(
                    closest[control].get(r["check"], np.inf), r["value"])
    print(json.dumps({
        "workload": args.workload,
        "every_seed_broke_what_the_configuration_names": as_named,
        "closest_to_sound": closest}))
    return 0 if as_named else 1


if __name__ == "__main__":
    sys.exit(main())
