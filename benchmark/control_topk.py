#!/usr/bin/env python3
"""``control.py`` for a ``sweep_topk`` cell: the plain reference
(``reference_<configuration's "reference">.py``) put in the program's
place and computed in a LOWER precision, at the cell's own size and its
own k, on the queries a run of that seed compares, under the
configuration's own ``limits``.  It has to come out as not correct, by
at least the limits the configuration's ``controls`` entry names for
that precision.  Host arithmetic only (numpy), so it needs no chip; no
benchmark run calls it.

    python3 benchmark/control_topk.py --workload knnlm1m.sweep_k1024 \\
        --precision f32 --seeds 11,12

Prints, per seed, each number compared beside its limit and whether the
control came out correct (it must not), and last one JSON line with the
smallest of each number over the seeds: what a limit is set below.
"""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
from reference import Checks  # noqa: E402


def compared_queries(cell: harness.Cell, seed: int):
    """The corpus and the queries a run of ``seed`` compares, where the
    window answered every batch of the pool."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    db = datagen_mix.draw(cfg["rows"], n, dim, seed, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(
        cfg.get("queries", cfg["rows"]), rows * n_pool, dim, seed,
        datagen.STREAM_QUERIES, of=cfg["rows"])
    driver = harness._module(tr["kind"], "drivers")
    pick_b, pick_r = driver.sweep.sample(seed, list(range(n_pool)), rows,
                                         int(tr["check_rows"]))
    return db, np.stack([queries[b * rows + r]
                         for b, r in zip(pick_b, pick_r)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.traffic["kind"] != "sweep_topk":
        raise SystemExit(f"{args.workload} is no sweep_topk cell: "
                         f"control.py and control_ip.py serve the others")
    ref = importlib.import_module(f"reference_{cell.config['reference']}")
    limits, k = cell.config["limits"], int(cell.config["k"])
    named = set(cell.config.get("controls", {}).get(args.precision, ()))
    closest, sound = {}, False
    for seed in (int(s) for s in args.seeds.split(",")):
        db, q = compared_queries(cell, seed)
        want_i, want_d = ref.oracle_topk(db, q, k)
        got_i, got_d = ref.lowprec_topk(db, q, k, args.precision)
        cmp = ref.compare(got_i, got_d, want_i, want_d, db, q)
        checks = Checks()
        for name, limit in limits.items():
            checks.add(name, cmp[name], limit)
        broke = {r["check"] for r in checks.rows if not r["ok"]}
        # not correct, and by every limit the configuration names for it
        sound = sound or checks.correct or not named <= broke
        print(f"seed {seed}: {args.precision} control on {cmp['rows']} "
              f"queries at k={k}: " + "; ".join(
                  f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
                  f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
                  for r in checks.rows)
              + f" -> correct={checks.correct}", flush=True)
        for r in checks.rows:
            closest[r["check"]] = min(closest.get(r["check"], np.inf),
                                      r["value"])
    print(json.dumps({
        "workload": args.workload, "precision": args.precision,
        "control_came_out_sound_on_some_seed": sound,
        "closest_to_sound": closest}))
    return 1 if sound else 0


if __name__ == "__main__":
    sys.exit(main())
