#!/usr/bin/env python3
"""``control.py`` for a ``sweep_filter`` cell: the plain reference
(``reference_filter.py``) put in the program's place in each of five
BROKEN forms, at the cell's own size, on the queries a run of that seed
compares, under the configuration's own ``limits``.  Each has to come
out as not correct.  Host arithmetic only (numpy), so it needs no chip;
no benchmark run calls it.

- ``post10`` / ``post100``: the unfiltered top-10 (top-100), the rows
  that lack a tag dropped and the rest padded: what a post-filter gives;
- ``or``: the rows that hold EITHER tag;
- ``hashed64``: the bags replaced by a 64-bit signature a row (each tag
  sets one of 64 bits by a multiplicative hash), a row passing when its
  signature holds the query's bits: false positives come in;
- ``int4``: rows and queries quantized to 16 levels.

    python3 benchmark/control_filter.py --workload yfcc2m5.sweep_filter \\
        --seeds 11,12,13 [--measure]

Prints, per seed and broken form, each number compared beside its limit
and whether the control came out correct, and last one JSON line.
``--measure`` also reads what the generator gave at this size: pairs a
row, the tags by how many rows hold them, how many a rule of one row in
1,024 / 4,096 / 16,384 keeps as bitmaps and the ids it leaves a query,
the share of two-tag queries, and the match counts by band.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import datagen  # noqa: E402
import datagen_tags  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import reference_filter  # noqa: E402
from reference import Checks  # noqa: E402

BROKEN = ("post10", "post100", "or", "hashed64", "int4")
_HASH = np.uint64(0x9E3779B97F4A7C15)


def _bit(tags) -> np.ndarray:
    """Each tag's one bit of a 64-bit signature."""
    t = np.asarray(tags, np.uint64)
    return np.uint64(1) << ((t * _HASH) >> np.uint64(58))


def drawn(cell: harness.Cell, seed: int):
    """The corpus, its bags, and the pool's queries, tags, match counts
    and bands of a run of ``seed``."""
    cfg, tr = cell.config, cell.traffic
    n, dim = int(cfg["rows_n"]), int(cfg["dim"])
    db, cluster = datagen_tags.draw(cfg["rows"], n, dim, seed,
                                    datagen.STREAM_ROWS)
    indptr, tags = datagen_tags.draw_bags(
        cfg["tags"], int(cfg["rows"]["clusters"]), cluster, seed)
    inverted = datagen_tags.Inverted(indptr, tags,
                                     int(cfg["tags"]["vocabulary"]))
    q, ft, matches, held = datagen_tags.draw_queries(
        cfg["rows"], cfg["queries"], cfg["tags"], inverted, dim, seed,
        int(tr["batch_rows"]), int(tr["pool_batches"]), tr["strata"])
    return db, indptr, tags, inverted, q, ft, matches, held


def broken_answer(form: str, db, indptr, tags, q, ft, k: int):
    """``(indices, distances)`` of the reference broken as ``form``."""
    n = db.shape[0]
    if form in ("post10", "post100"):
        width = 10 if form == "post10" else 100
        top_i, top_d = reference.oracle_topk(db, q, width)
        out_i = np.full((len(q), k), -1, np.int64)
        out_d = np.full((len(q), k), np.inf)
        for row in range(len(q)):
            ok = np.isin(top_i[row], reference_filter.valid_rows(
                indptr, tags, ft[row]))
            keep = np.flatnonzero(ok)[:k]
            out_i[row, :keep.size] = top_i[row][keep]
            out_d[row, :keep.size] = top_d[row][keep]
        return out_i, out_d
    if form == "int4":
        step = np.float32(255.0 / 15.0)
        db = (np.round(db / step) * step).astype(np.float32)
        q = (np.round(q / step) * step).astype(np.float32)
        return reference_filter.oracle_topk(db, indptr, tags, q, ft, k)
    row_of = np.repeat(np.arange(n), np.diff(indptr))
    if form == "hashed64":
        sig = np.zeros(n, np.uint64)
        np.bitwise_or.at(sig, row_of, _bit(tags))
    out_i = np.full((len(q), k), -1, np.int64)
    out_d = np.full((len(q), k), np.inf)
    for row in range(len(q)):
        named = [t for t in ft[row] if t >= 0]
        if form == "or":
            rows = np.unique(np.concatenate([
                reference_filter.valid_rows(indptr, tags, [t])
                for t in named]))
        else:
            want = np.bitwise_or.reduce(_bit(named))
            rows = np.flatnonzero((sig & want) == want)
        diff = db[rows].astype(np.float64) - q[row].astype(np.float64)
        d = np.einsum("nd,nd->n", diff, diff)
        order = np.lexsort((rows, d))[:k]
        out_i[row, :order.size] = rows[order]
        out_d[row, :order.size] = d[order]
    return out_i, out_d


def measure(indptr, tags, inverted, ft, matches, strata) -> dict:
    n = indptr.size - 1
    counts = inverted.counts
    out = {"pairs_a_row": float(tags.size / n),
           "tags_with_a_row": int((counts > 0).sum()),
           "most_frequent_tag_share": float(counts.max() / n),
           "tags_by_rows_1_10_100_1k_10k_100k": [
               int((counts >= lo).sum()) for lo in
               (1, 10, 100, 1000, 10000, 100000)],
           "two_tag_share": float((ft[:, 1] >= 0).mean()),
           "matches_by_band_min_median_max": [], "rule": {}}
    band = datagen_tags.stratum_of(matches, strata)
    for s in range(len(strata)):
        m = np.sort(matches[band == s])
        out["matches_by_band_min_median_max"].append(
            [int(m[0]), int(np.median(m)), int(m[-1])] if m.size else None)
    # what a rule of one row in `share` would keep (the rows padded to
    # the kernel's 16,384-row tile)
    padded = -(-n // 16384) * 16384
    for share in (1024, 4096, 16384):
        least = max(1, padded // share)
        def listed(t):
            held = counts[np.maximum(t, 0)]
            return np.where((t >= 0) & (held < least), held, 0)

        out["rule"][share] = {
            "least_rows": int(least),
            "bitmap_tags": int((counts >= least).sum()),
            "bitmap_bytes": int((counts >= least).sum() * (padded // 8)),
            "list_ids_kept": int(counts[counts < least].sum()),
            "list_ids_a_query": float(
                (listed(ft[:, 0]) + listed(ft[:, 1])).mean())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--measure", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.root, args.workload)
    if cell.traffic["kind"] != "sweep_filter":
        raise SystemExit(f"{args.workload} is no sweep_filter cell")
    cfg, tr = cell.config, cell.traffic
    k, rows, n_pool = int(cfg["k"]), int(tr["batch_rows"]), \
        int(tr["pool_batches"])
    driver = harness._module(tr["kind"], "drivers")
    out, failed_to_break = {}, []
    for seed in (int(s) for s in args.seeds.split(",")):
        db, indptr, tags, inverted, queries, q_tags, matches, held = drawn(
            cell, seed)
        out[seed] = {"held_a_batch": held[0].tolist(),
                     "every_batch_alike": bool((held == held[0]).all())}
        if args.measure:
            out[seed].update(measure(indptr, tags, inverted, q_tags,
                                     matches, tr["strata"]))
            print(f"seed {seed}: measured {out[seed]}", flush=True)
        bands = datagen_tags.stratum_of(matches, tr["strata"])
        pick_b, pick_r = driver.pick(
            seed, list(range(n_pool)), rows, bands, int(tr["check_rows"]),
            int(tr["check_rows_a_band"]))
        at = pick_b * rows + pick_r
        q, ft = queries[at], q_tags[at]
        want_i, want_d = reference_filter.oracle_topk(db, indptr, tags, q,
                                                      ft, k)
        for form in BROKEN:
            got_i, got_d = broken_answer(form, db, indptr, tags, q, ft, k)
            cmp = reference_filter.compare(got_i, got_d, want_i, want_d,
                                           indptr, tags, ft)
            checks = Checks()
            for name, limit in cfg["limits"].items():
                checks.add(name, cmp[name], limit)
            if checks.correct:
                failed_to_break.append((seed, form))
            out[seed][form] = {r["check"]: r["value"] for r in checks.rows}
            print(f"seed {seed}: {form} control on {cmp['rows']} queries "
                  f"({cmp['short_rows']} short, {cmp['empty_rows']} empty by "
                  f"the oracle): " + "; ".join(
                      f"{r['check']}={r['value']:.6g} (limit {r['rule']} "
                      f"{r['limit']:.6g}{'' if r['ok'] else ', OUTSIDE'})"
                      for r in checks.rows)
                  + f" -> correct={checks.correct}", flush=True)
    print(json.dumps({"workload": args.workload, "by_seed": out,
                      "controls_that_came_out_correct": failed_to_break}))
    return 1 if failed_to_break else 0


if __name__ == "__main__":
    sys.exit(main())
