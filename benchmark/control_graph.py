#!/usr/bin/env python3
"""``control_vote.py`` for a graph-build cell, held to what the
configuration says each control breaks: the plain reference
(``reference_graph.py``) put in the program's place and computed WRONGLY
in one stated way (``reference_graph.CONTROLS``: the k+1 search with the
row itself kept; the row taken out by distance 0, its exact copies with
it; the right exclusion ranked in float32; the same in bfloat16), at the
cell's own size, on the rows a run of that seed compares, under the
configuration's own ``limits``.  Each has to come out as not correct,
and by the limits the configuration's ``controls`` entry names for it.
Host arithmetic only (numpy), so it needs no chip; no benchmark run
calls it.

    python3 benchmark/control_graph.py --workload deep5m-knng.build \\
        --control drop_zero --seeds 11,12

Prints, per seed, each number compared beside its limit and which limits
broke (and, for the float32 and bfloat16 rankings, the distance error
over the checked rows that have NO copy: what the limit is set below
without the copies' help), and last one JSON line with the smallest of
each number over the seeds and whether every seed broke what the
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
import datagen_graph  # noqa: E402
import harness  # noqa: E402
import reference_graph  # noqa: E402
from reference import Checks  # noqa: E402


def compared_rows(cell: harness.Cell, seed: int):
    """The corpus, its pairs of copies and the rows a run of ``seed``
    compares: the driver's own draw of the first call and of the
    sample."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    db, pairs = datagen_graph.draw_rows(cfg["rows"], n, dim, seed,
                                        datagen.STREAM_ROWS)
    driver = harness._module(tr["kind"], "drivers")
    lo = driver.first_row(seed, n, int(tr["block_rows"]))
    at = datagen_graph.check_rows(
        pairs, lo, min(lo + int(tr["call_rows"]), n), int(tr["check_rows"]),
        seed, datagen.STREAM_SAMPLE, float(tr["check_copied_share"]))
    return db, pairs, at


def broken_limits(cell: harness.Cell, seed: int, how: str):
    """``(the configuration's limits over the control's answer for the
    rows a run of ``seed`` compares, its distance error over the checked
    rows that have no copy)``."""
    cfg = cell.config
    db, pairs, at = compared_rows(cell, seed)
    want_i, want_d = reference_graph.oracle_graph(db, at, int(cfg["k"]))
    got_i, got_d = reference_graph.control(db, at, int(cfg["k"]), how)
    cmp = reference_graph.compare(got_i, got_d, want_i, want_d)
    checks = Checks()
    for name, limit in cfg["limits"].items():
        checks.add(name, cmp[name], limit)
    lone = ~np.isin(at, pairs) & (want_d > 0).all(axis=1)
    return checks, reference_graph.compare(
        got_i[lone], got_d[lone], want_i[lone], want_d[lone])[
            "dist_rel_err_max"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True,
                    choices=reference_graph.CONTROLS)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.config.get("reference") != "graph":
        raise SystemExit(f"{args.workload} is no graph-build cell: its "
                         f"configuration's reference is "
                         f"{cell.config.get('reference')!r}")
    must = set(cell.config["controls"][args.control])
    closest, as_named = {}, True
    for seed in (int(s) for s in args.seeds.split(",")):
        checks, lone = broken_limits(cell, seed, args.control)
        broke = {r["check"] for r in checks.rows if not r["ok"]}
        as_named = as_named and not checks.correct and must <= broke
        print(f"seed {seed}: {args.control} control: " + "; ".join(
            f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
            f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
            for r in checks.rows)
            + f"; dist_rel_err_max over the rows with no copy {lone:.6g}"
            + f" -> correct={checks.correct}, broke {sorted(broke)}, "
              f"the configuration names {sorted(must)}", flush=True)
        for r in checks.rows:
            closest[r["check"]] = min(closest.get(r["check"], np.inf),
                                      r["value"])
        closest["dist_rel_err_max_no_copy"] = min(
            closest.get("dist_rel_err_max_no_copy", np.inf), lone)
    print(json.dumps({
        "workload": args.workload, "control": args.control,
        "every_seed_broke_what_the_configuration_names": as_named,
        "closest_to_sound": closest}))
    return 0 if as_named else 1


if __name__ == "__main__":
    sys.exit(main())
