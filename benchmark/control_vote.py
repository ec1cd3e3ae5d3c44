#!/usr/bin/env python3
"""``control_cos.py`` for a classification cell, held to what the
configuration says each control breaks: the plain reference
(``reference_vote.py``) put in the program's place and computed WRONGLY
in one stated way (``reference_vote.CONTROLS``: float32 ranking, weights
and totals with no certificate; the same in bfloat16; the unweighted
majority vote; the right vote at a wrong temperature), at the cell's own
size, on the queries a run of that seed compares, under the
configuration's own ``limits``.  Each has to come out as not correct,
and by the limits the configuration's ``controls`` entry names for it.
Host arithmetic only (numpy), so it needs no chip; no benchmark run
calls it.

    python3 benchmark/control_vote.py --workload imagenet-knn768.sweep_vote \\
        --control f32 --seeds 11,12,13

Prints, per seed, each number compared beside its limit and which limits
broke, and last one JSON line with the smallest of each number over the
seeds (what a limit is set below) and whether every seed broke what the
configuration names.  Exit code 0 only then.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import datagen_labels  # noqa: E402
import harness  # noqa: E402
import reference_vote  # noqa: E402
from reference import Checks  # noqa: E402


def compared_queries(cell: harness.Cell, seed: int):
    """The corpus, its labels and the queries a run of ``seed``
    compares, where the window answered every batch of the pool."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    db, labels = datagen_labels.draw_rows(cfg["rows"], n, dim, seed,
                                          datagen.STREAM_ROWS)
    queries, _ = datagen_labels.draw_queries(
        cfg["rows"], rows * n_pool, dim, seed, datagen.STREAM_QUERIES)
    driver = harness._module(tr["kind"], "drivers")
    pick_b, pick_r = driver.sweep.sample(seed, list(range(n_pool)), rows,
                                         int(tr["check_rows"]))
    return db, labels, queries[pick_b * rows + pick_r]


def broken_limits(cell: harness.Cell, seed: int, how: str) -> Checks:
    """The configuration's limits over the control's answer for the
    queries a run of ``seed`` compares."""
    cfg = cell.config
    db, labels, q = compared_queries(cell, seed)
    args = (db, labels, q, int(cfg["k"]), float(cfg["temperature"]),
            int(cfg["classes"]), int(cfg["classes_out"]))
    want_c, want_t, _ = reference_vote.oracle(*args)
    got_c, got_t = reference_vote.control(*args, how)
    cmp = reference_vote.compare(got_c, got_t, want_c, want_t)
    checks = Checks()
    for name, limit in cfg["limits"].items():
        checks.add(name, cmp[name], limit)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True,
                    choices=reference_vote.CONTROLS)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.config.get("reference") != "vote":
        raise SystemExit(f"{args.workload} is no classification cell: its "
                         f"configuration's reference is "
                         f"{cell.config.get('reference')!r}")
    must = set(cell.config["controls"][args.control])
    closest, as_named = {}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = broken_limits(cell, seed, args.control)
        broke = {r["check"] for r in checks.rows if not r["ok"]}
        as_named = as_named and not checks.correct and must <= broke
        print(f"seed {seed}: {args.control} control: " + "; ".join(
            f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
            f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
            for r in checks.rows)
            + f" -> correct={checks.correct}, broke {sorted(broke)}, "
              f"the configuration names {sorted(must)}", flush=True)
        for r in checks.rows:
            closest[r["check"]] = min(closest.get(r["check"], np.inf),
                                      r["value"])
    print(json.dumps({
        "workload": args.workload, "control": args.control,
        "every_seed_broke_what_the_configuration_names": as_named,
        "closest_to_sound": closest}))
    return 0 if as_named else 1


if __name__ == "__main__":
    sys.exit(main())
