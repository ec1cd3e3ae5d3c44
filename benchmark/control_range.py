#!/usr/bin/env python3
"""``control.py`` for a ``sweep_range`` cell: the plain reference
(``reference_range.py``) put in the program's place in each of its
BROKEN forms (a range answered by the first k alone, an exclusive
boundary, 4-bit rows), at the cell's own size, on the queries a run of
that seed compares, under the configuration's own ``limits``.  Each has
to come out as not correct: every sample holds long queries, and one
batch's boundary queries (a placed row at exactly the radius of one, a
unit past it of the other).  Host arithmetic only (numpy), so it needs
no chip; no benchmark run calls it.

    python3 benchmark/control_range.py --workload ssnpp2m5.sweep_range \\
        --seeds 11,12,13 [--measure]

Prints, per seed and broken form, each number compared beside its limit
and whether the control came out correct, and last one JSON line.
``--measure`` also reads what the generator gave at this size (float32
arithmetic, exact on whole numbers): the share of a batch's queries
with no result, the mean results a query, the long queries' counts, how
many results lie within 2% of the radius and how many at exactly it,
what the boundary queries find, and how far the nearest row of an
unrelated query is.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import datagen_dup  # noqa: E402
import harness  # noqa: E402
import reference_range  # noqa: E402
from reference import CHUNK, Checks  # noqa: E402


def drawn(cell: harness.Cell, seed: int):
    """The corpus, the pool's queries and their kinds of a run of
    ``seed``."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    db = datagen_dup.draw(cfg["rows"], n, dim, seed, datagen.STREAM_ROWS)
    queries, kinds = datagen_dup.draw_queries(
        cfg["rows"], n, dim, seed, rows, n_pool, tr["shares"],
        int(tr["small_max"]), int(tr["heavy_min"]), int(cfg["radius_sq"]),
        int(tr["boundary_pairs"]))
    return db, queries, kinds


def compared_queries(cell: harness.Cell, seed: int, queries, kinds):
    """The queries a run of ``seed`` compares, where the window answered
    every batch of the pool."""
    tr = cell.traffic
    rows, n_pool = int(tr["batch_rows"]), int(tr["pool_batches"])
    driver = harness._module(tr["kind"], "drivers")
    pick_b, pick_r = driver.pick(
        seed, list(range(n_pool)), rows, kinds, int(tr["check_rows"]),
        int(tr["check_heavy_rows"]))
    return queries[pick_b * rows + pick_r]


def measure(db, q, kinds, radius_sq: float) -> dict:
    """What one batch of the generator's queries finds in its rows."""
    counts = np.zeros(q.shape[0], np.int64)
    near = np.zeros(q.shape[0], np.int64)
    at = np.zeros(q.shape[0], np.int64)
    nearest = np.full(q.shape[0], np.inf, np.float32)
    qn = np.einsum("qd,qd->q", q, q)
    for lo in range(0, db.shape[0], CHUNK):
        t = db[lo:lo + CHUNK]
        s = qn[:, None] - np.float32(2.0) * (q @ t.T) \
            + np.einsum("nd,nd->n", t, t)[None, :]
        counts += (s <= radius_sq).sum(axis=1)
        near += ((s <= radius_sq) & (s >= 0.98 * radius_sq)).sum(axis=1)
        at += (s == radius_sq).sum(axis=1)
        np.minimum(nearest, s.min(axis=1), out=nearest)
    heavy = np.sort(counts[kinds == 2])
    return {
        "no_result_share": float((counts == 0).mean()),
        "mean_results": float(counts.mean()),
        "heavy_counts_min_median_max": [
            int(heavy[0]), int(np.median(heavy)), int(heavy[-1])],
        "small_family_mean_results": float(counts[kinds == 1].mean()),
        "results_within_2pct_of_radius": int(near.sum()),
        "results_at_exactly_the_radius": int(at.sum()),
        "boundary_queries_results_at_past": [
            counts[kinds == datagen_dup.AT_RADIUS].tolist(),
            counts[kinds == datagen_dup.PAST_RADIUS].tolist()],
        "unrelated_nearest_sq_min_p01_p50": [
            float(x) for x in np.quantile(
                nearest[kinds == 0], [0.0, 0.01, 0.5])],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--measure", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.traffic["kind"] != "sweep_range":
        raise SystemExit(f"{args.workload} is no sweep_range cell")
    cfg = cell.config
    radius_sq, k = float(cfg["radius_sq"]), int(cfg["k"])
    rows = int(cell.traffic["batch_rows"])
    out, failed_to_break = {}, []
    for seed in (int(s) for s in args.seeds.split(",")):
        db, queries, kinds = drawn(cell, seed)
        sizes, _ = datagen_dup.layout(cfg["rows"], db.shape[0], seed)
        out[seed] = {"families": int(sizes.size),
                     "families_over_heavy_min": int(
                         (sizes > int(cell.traffic["heavy_min"])).sum()),
                     "largest_family": int(sizes[0])}
        if args.measure:
            out[seed].update(measure(db, queries[:rows], kinds[:rows],
                                     radius_sq))
            print(f"seed {seed}: measured {out[seed]}", flush=True)
        q = compared_queries(cell, seed, queries, kinds)
        want = reference_range.oracle_range(db, q, radius_sq)
        at_radius = int((want[2] == radius_sq).sum())
        for broken in reference_range.BROKEN:
            got = reference_range.oracle_range(db, q, radius_sq,
                                               broken=broken, cap=k)
            cmp = reference_range.compare(got, want)
            checks = Checks()
            for name, limit in cfg["limits"].items():
                checks.add(name, cmp[name], limit)
            if checks.correct:
                failed_to_break.append((seed, broken))
            out[seed][broken] = {r["check"]: r["value"] for r in checks.rows}
            print(f"seed {seed}: {broken} control on {cmp['rows']} queries "
                  f"({cmp['results']} results, most {cmp['most_results']}, "
                  f"{at_radius} at the radius): " + "; ".join(
                      f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
                      f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
                      for r in checks.rows)
                  + f" -> correct={checks.correct}", flush=True)
    print(json.dumps({"workload": args.workload, "by_seed": out,
                      "controls_that_came_out_correct": failed_to_break}))
    return 1 if failed_to_break else 0


if __name__ == "__main__":
    sys.exit(main())
