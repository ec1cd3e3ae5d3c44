"""What the two test files of ``openai500k.sweep_cos`` share
(``benchmark/tests/test_cos_cell.py`` and tier-1's
``tests/test_openai_cos.py``): BENCHMARK.json with the three held
per-layer entries of ``data/cos_cell.json`` merged in, a cosine brute
force that ties equal rows to the bit, and the broken timed path that is
the parent's (the host ranking by the float32 unit rows)."""

import json
import os

import numpy as np

import tinyroot

CELL = "openai500k.sweep_cos"
with open(os.path.join(tinyroot.HERE, "data", "cos_cell.json")) as _f:
    HELD = json.load(_f)["per_layer"]
NEW = [e["name"] for e in HELD]


def merged_bench() -> dict:
    """BENCHMARK.json as it will read once the held entries are in it."""
    bench = tinyroot.load_bench()
    held = {e["name"]: e for e in HELD}
    bench["per_layer"] = [held.pop(m["name"], m) for m in bench["per_layer"]]
    bench["per_layer"] += list(held.values())
    return bench


def brute(db, q, k):
    """(indices, cosine distances) of the (distance, index) top-k by a
    direct float64 argsort, the convention spelled out: a zero norm on
    either side is cosine 0.  einsum's own loop: every (query, row) sum
    in the same order, so equal rows tie to the bit (a BLAS product
    need not)."""
    d64, q64 = db.astype(np.float64), q.astype(np.float64)
    den = (np.sqrt((q64 * q64).sum(-1))[:, None]
           * np.sqrt((d64 * d64).sum(-1))[None, :])
    cos = np.zeros_like(den)
    np.divide(np.einsum("qd,nd->qn", q64, d64), den, out=cos, where=den > 0)
    c = 1.0 - cos
    idx = np.broadcast_to(np.arange(db.shape[0]), c.shape)
    order = np.lexsort((idx, c), axis=-1)[:, :k]
    return order, np.take_along_axis(c, order, axis=1)


def rank_by_the_unit_rows(monkeypatch) -> None:
    """The host ranks by the float64 squared distance of the float32
    UNIT rows (halved), as the parent did, wherever it ranks."""
    from knn_tpu.ops import certified, refine
    from knn_tpu.parallel import sharded as sh

    real_members, real_refine = refine._score_members, refine.refine_exact
    real_scan = certified.host_exact_knn

    def unit(x):
        return sh._unit_rows(x)[0]

    def members(db_np, queries_np, cand, rows, metric, out, norms=None):
        at = real_members(unit(db_np[cand]), unit(queries_np),
                          np.arange(cand.size), rows, "l2", out)
        out *= 0.5
        return at

    def refine_l2(db, queries, cand_idx, k, metric="l2", norms=None):
        d, i = real_refine(unit(db), unit(queries), cand_idx, k, "l2")
        return 0.5 * d, i

    def scan(db, q, k, metric="l2", norms=None, **kw):
        d, i = real_scan(unit(db), unit(q), k, **kw)
        return 0.5 * d, i

    monkeypatch.setattr(refine, "_score_members", members)
    monkeypatch.setattr(refine, "refine_exact", refine_l2)
    monkeypatch.setattr(certified, "refine_exact", refine_l2)
    monkeypatch.setattr(certified, "host_exact_knn", scan)
