#!/usr/bin/env python3
"""``control_ip.py`` for a cosine cell, held to what the configuration
says each control breaks: the plain reference (``reference_cos.py``) put
in the program's place and computed in a LOWER precision, at the cell's
own size, on the queries a run of that seed compares
(``control_ip.compared_queries``), under the configuration's own
``limits``.  It has to come out as not correct, and by the limits the
configuration's ``controls`` entry names for that precision: a float32
ranking of unit rows swaps near neighbours and keeps its distances
inside the program's own bound, a bfloat16 one loses both.  Host
arithmetic only (numpy), so it needs no chip; no benchmark run calls it.

    python3 benchmark/control_cos.py --workload openai500k.sweep_cos \\
        --precision f32 --seeds 11,12,13

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

import control_ip  # noqa: E402
import harness  # noqa: E402
import reference_cos  # noqa: E402
from reference import Checks  # noqa: E402


def broken_limits(cell: harness.Cell, seed: int, precision: str) -> Checks:
    """The configuration's limits over the control's answer for the
    queries a run of ``seed`` compares."""
    db, q = control_ip.compared_queries(cell, seed)
    k = int(cell.config["k"])
    want_i, want_d = reference_cos.oracle_topk(db, q, k)
    got_i, got_d = reference_cos.lowprec_topk(db, q, k, precision)
    cmp = reference_cos.compare(got_i, got_d, want_i, want_d, db, q)
    checks = Checks()
    for name, limit in cell.config["limits"].items():
        checks.add(name, cmp[name], limit)
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--precision", required=True,
                    choices=reference_cos.PRECISIONS)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.config.get("reference") != "cos":
        raise SystemExit(f"{args.workload} is no cosine cell: its "
                         f"configuration's reference is "
                         f"{cell.config.get('reference')!r}")
    must = set(cell.config["controls"][args.precision])
    closest, as_named = {}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = broken_limits(cell, seed, args.precision)
        broke = {r["check"] for r in checks.rows if not r["ok"]}
        as_named = as_named and not checks.correct and must <= broke
        print(f"seed {seed}: {args.precision} control: " + "; ".join(
            f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
            f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
            for r in checks.rows)
            + f" -> correct={checks.correct}, broke {sorted(broke)}, "
              f"the configuration names {sorted(must)}", flush=True)
        for r in checks.rows:
            closest[r["check"]] = min(closest.get(r["check"], np.inf),
                                      r["value"])
    print(json.dumps({
        "workload": args.workload, "precision": args.precision,
        "every_seed_broke_what_the_configuration_names": as_named,
        "closest_to_sound": closest}))
    return 0 if as_named else 1


if __name__ == "__main__":
    sys.exit(main())
