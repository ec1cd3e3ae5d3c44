#!/usr/bin/env python
"""Proof that the system starts on the chip: drive the main path once, in
ONE process, through the entry points a user calls, at the full width of
the SIFT1M, GIST1M and GloVe shapes, on seeded random data,
and check what comes out against a float64 brute-force oracle computed
here in numpy.

    python chip_smoke.py              # one chip: SIFT sweep + serving,
                                      # GIST and GloVe certified batches
    python chip_smoke.py --chips 4    # one 4-chip host: db- and
                                      # query-sharded meshes vs one chip

It needs a TPU whose device kind the repo knows (analysis.vmem
.VMEM_BYTES_BY_KIND) and exits non-zero without one — there is no CPU branch.
Every wall time it prints is a bring-up timing (compiles included, one
run each), not a metric.  Any failed check raises; the last line of
stdout is the JSON verdict, printed only when every leg passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings

import numpy as np

#: the three reference datasets at their published shapes
SIFT = dict(n=1_000_000, dim=128, k=100, metric="l2")
GIST = dict(n=1_000_000, dim=960, k=100, metric="l2")
GLOVE = dict(n=1_183_514, dim=300, k=50, metric="cosine")
NQ = 4096
#: the exact (non-Pallas) path streams the database in
#: row tiles of this size; left at None it would materialize the whole
#: [4096, 1M] f32 distance block (16 GB) on a 16 GB chip
TRAIN_TILE = 131_072
#: queries checked against the oracle (the f64 scan is host time)
ORACLE_SWEEP = 256
ORACLE_WIDTH = 64

_T0 = time.perf_counter()
#: jax.monitoring tallies, so each leg can say how long it spent in XLA
#: compiles and whether the persistent cache answered
_COMPILE = {"backend_compile_s": 0.0, "compiles": 0, "cache_hits": 0,
            "cache_misses": 0}


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def _listen_to_compiles(jax) -> None:
    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["backend_compile_s"] += float(duration)
            _COMPILE["compiles"] += 1

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILE["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class Leg:
    """Times one leg and reports the compile share of it."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = dict(_COMPILE)
        say(f"--- {self.name}")
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            d = {k: _COMPILE[k] - self.c0[k] for k in _COMPILE}
            say(f"PASS {self.name}: {time.perf_counter() - self.t0:.1f} s "
                f"wall (bring-up timing), of which XLA compile "
                f"{d['backend_compile_s']:.1f} s over {d['compiles']} "
                f"programs; persistent cache {d['cache_hits']} hits / "
                f"{d['cache_misses']} misses")


def make_data(n: int, dim: int, nq: int):
    """Seed 0, uniform [0, 128), filled in row
    chunks so the float64 draw never holds more than a chunk — the
    stream, and so every value, is the same as one big draw."""
    rng = np.random.default_rng(0)

    def fill(rows):
        out = np.empty((rows, dim), np.float32)
        for lo in range(0, rows, 65_536):
            hi = min(lo + 65_536, rows)
            out[lo:hi] = rng.random(size=(hi - lo, dim)) * 128.0
        return out

    return fill(n), fill(nq)


def unit_rows(x: np.ndarray) -> np.ndarray:
    """Rows scaled to unit length: float64 norms, float32 result — the
    problem ShardedKNN(metric="cosine") states it solves exactly (L2 on
    the f32-normalized rows), so the oracle ranks the same rows."""
    n = np.linalg.norm(x.astype(np.float64), axis=-1, keepdims=True)
    return (x / np.maximum(n, 1e-300)).astype(np.float32)


def oracle_topk(db: np.ndarray, q: np.ndarray, k: int) -> np.ndarray:
    """Exact lexicographic (squared-L2 distance, index) top-k in float64
    numpy: a chunked expanded-form scan keeps k+32 candidates per query,
    then those are re-scored by direct difference and ordered."""
    q64 = q.astype(np.float64)
    keep = k + 32
    cand_s = np.empty((q.shape[0], 0))
    cand_i = np.empty((q.shape[0], 0), np.int64)
    for lo in range(0, db.shape[0], 65_536):
        t = db[lo:lo + 65_536].astype(np.float64)
        s = np.einsum("nd,nd->n", t, t)[None, :] - 2.0 * (q64 @ t.T)
        cand_s = np.concatenate([cand_s, s], axis=1)
        cand_i = np.concatenate(
            [cand_i, np.broadcast_to(np.arange(lo, lo + t.shape[0]),
                                     s.shape)], axis=1)
        sel = np.argpartition(cand_s, keep - 1, axis=1)[:, :keep]
        cand_s = np.take_along_axis(cand_s, sel, axis=1)
        cand_i = np.take_along_axis(cand_i, sel, axis=1)
    diff = q64[:, None, :] - db[cand_i].astype(np.float64)
    d = np.einsum("qcd,qcd->qc", diff, diff)
    order = np.lexsort((cand_i, d), axis=-1)[:, :k]
    return np.take_along_axis(cand_i, order, axis=1)


def check_equal(name: str, got: np.ndarray, want: np.ndarray) -> None:
    got = np.asarray(got)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = (np.flatnonzero((got != want).any(axis=1))
               if got.shape == want.shape else "shape")
        raise AssertionError(
            f"{name}: indices differ from the reference "
            f"(shape {got.shape} vs {want.shape}; rows {bad})")
    say(f"  {name}: {got.shape[0]} queries x {got.shape[1]} indices "
        f"equal the reference")


def recall(got: np.ndarray, want: np.ndarray) -> float:
    """Mean per-query overlap of two [Q, k] index arrays, as sets."""
    return float(np.mean([len(set(a) & set(b)) / want.shape[1]
                          for a, b in zip(np.asarray(got), want)]))


def certified_batch(prog, q, *, expect_q: int, label: str):
    """One search_certified(selector="pallas") call at library defaults,
    with the checks every leg makes of its stats."""
    t0 = time.perf_counter()
    d, i, stats = prog.search_certified(q, selector="pallas")
    wall = time.perf_counter() - t0
    knobs = stats["pallas_knobs"]
    if stats["tuning"]["source"] != "default":
        raise AssertionError(
            f"{label}: knobs came from {stats['tuning']['source']!r} "
            f"({stats['tuning'].get('cache_path')}), not the library defaults")
    if knobs["interpret"] is not False:
        raise AssertionError(f"{label}: kernel ran in interpret mode")
    if stats["certified"] + stats["fallback_queries"] != expect_q:
        raise AssertionError(f"{label}: certified + fallback != {expect_q}")
    if d.shape != i.shape or not np.isfinite(d).all():
        raise AssertionError(f"{label}: distances not finite / misshapen")
    say(f"  {label}: {wall:.2f} s; interpret: {knobs['interpret']}; knobs "
        f"from {stats['tuning']['source']}: kernel={knobs['kernel']} "
        f"block_q={knobs['block_q']} tile_n={knobs['tile_n']} "
        f"precision={knobs['precision']} "
        f"final_select={knobs['final_select']}; certified "
        f"{stats['certified']} + fallback {stats['fallback_queries']} = "
        f"{expect_q}; rank-corrected {stats['rank_corrected_queries']}")
    return i


def place(jax, db, mesh, cfg, **kw):
    from knn_tpu.parallel import ShardedKNN

    t0 = time.perf_counter()
    prog = ShardedKNN(db, mesh=mesh, k=cfg["k"], metric=cfg["metric"],
                      train_tile=TRAIN_TILE, **kw)
    jax.block_until_ready(prog._tp)
    say(f"  placed {db.shape[0]:,} x {db.shape[1]} on mesh "
        f"{dict(mesh.shape)}: {time.perf_counter() - t0:.1f} s")
    return prog


# --- one chip ---------------------------------------------------------------
def sweep_and_serving_legs(jax, mesh) -> None:
    from knn_tpu.serving import QueryQueue, ServingEngine

    with Leg("sweep leg: SIFT 1M x 128, k=100, 4,096 queries"):
        t0 = time.perf_counter()
        db, q = make_data(SIFT["n"], SIFT["dim"], NQ)
        say(f"  data from seed 0: {time.perf_counter() - t0:.1f} s")
        prog = place(jax, db, mesh, SIFT)
        i = certified_batch(prog, q, expect_q=NQ,
                            label="certified (first call, compiles)")
        i2 = certified_batch(prog, q, expect_q=NQ,
                             label="certified (second call)")
        t0 = time.perf_counter()
        oracle = oracle_topk(db, q[:ORACLE_SWEEP], SIFT["k"])
        say(f"  float64 oracle, {ORACLE_SWEEP} queries: "
            f"{time.perf_counter() - t0:.1f} s")
        check_equal("certified vs oracle", i[:ORACLE_SWEEP], oracle)
        check_equal("second call vs first", i2, i)
        # the exact (XLA top-k) path ranks f32 distances: neighbours at
        # an f32 near-tie may swap or cross the k boundary, so it is held
        # to recall against the oracle, and to the certified indices
        t0 = time.perf_counter()
        ds, is_ = prog.search(q)
        ds, is_ = np.asarray(ds), np.asarray(is_)
        say(f"  search() first call (compiles): "
            f"{time.perf_counter() - t0:.2f} s")
        if is_.shape != (NQ, SIFT["k"]) or not np.isfinite(ds).all():
            raise AssertionError("search(): misshapen or non-finite")
        r = recall(is_[:ORACLE_SWEEP], oracle)
        say(f"  search() recall@{SIFT['k']} vs oracle: {r:.6f}")
        if r < 0.999:
            raise AssertionError(f"search() recall {r} < 0.999")

    with Leg("serving leg: ServingEngine + QueryQueue on the same "
             "placement"):
        eng = ServingEngine(prog, buckets=(8, 64, 1024))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            t0 = time.perf_counter()
            counts = eng.warmup()
            say(f"  warmup: {counts} executables, "
                f"{time.perf_counter() - t0:.1f} s")
        donation = [str(w.message) for w in caught
                    if "donat" in str(w.message).lower()]
        if donation:
            raise AssertionError(f"buffer donation warnings: {donation}")
        warm = eng.stats()
        say(f"  per-bucket compiles after warm-up: "
            f"{warm['per_bucket_compiles']}")
        lo = 0
        with QueryQueue(eng, max_wait_ms=2.0) as queue:
            for size in (1, 3, 64, 700):
                d, idx = queue.submit(q[lo:lo + size]).result(timeout=300)
                if idx.shape != (size, SIFT["k"]) or \
                        not np.isfinite(d).all():
                    raise AssertionError(f"request of {size}: bad shape")
                # the engine dispatches the exact f32 path at another
                # batch shape: same recall bar as search(); whether it
                # also reproduces search()'s ORDER bit for bit across
                # batch shapes is reported, not required
                hi = min(lo + size, ORACLE_SWEEP)
                r = recall(idx[:hi - lo], oracle[lo:hi])
                same = np.array_equal(idx, is_[lo:lo + size])
                say(f"  request of {size:3d} rows: recall@{SIFT['k']} vs "
                    f"oracle {r:.6f} over {hi - lo} rows; indices equal "
                    f"search()'s: {same}")
                if r < 0.999:
                    raise AssertionError(f"request of {size}: recall {r}")
                lo += size
        done = eng.stats()
        say(f"  per-bucket dispatches: {done['per_bucket_dispatches']}; "
            f"compiles {done['compile_count']} "
            f"(after warm-up {warm['compile_count']})")
        if done["compile_count"] != warm["compile_count"]:
            raise AssertionError("a request compiled after warm-up")
        if done["errors_total"]:
            raise AssertionError(f"{done['errors_total']} serving errors")


def width_leg(jax, mesh, name: str, cfg: dict) -> None:
    with Leg(f"width leg: {name} {cfg['n']:,} x {cfg['dim']} "
             f"{cfg['metric']}, k={cfg['k']}, one certified batch"):
        t0 = time.perf_counter()
        db, q = make_data(cfg["n"], cfg["dim"], NQ)
        say(f"  data from seed 0: {time.perf_counter() - t0:.1f} s")
        prog = place(jax, db, mesh, cfg)
        i = certified_batch(prog, q, expect_q=NQ,
                            label="certified (first call, compiles)")
        t0 = time.perf_counter()
        if cfg["metric"] == "cosine":
            oracle = oracle_topk(unit_rows(db), unit_rows(q[:ORACLE_WIDTH]),
                                 cfg["k"])
        else:
            oracle = oracle_topk(db, q[:ORACLE_WIDTH], cfg["k"])
        say(f"  float64 oracle, {ORACLE_WIDTH} queries: "
            f"{time.perf_counter() - t0:.1f} s")
        check_equal("certified vs oracle", i[:ORACLE_WIDTH], oracle)


def filtered_leg(jax, mesh) -> None:
    """One filtered certified batch (PR 40): SIFT-shaped rows with a bag
    of tags a row, one or two tags a query, against a float64 scan of
    each query's valid rows; a tag a tenth of the rows hold keeps a
    bitmap, the rare ones lists, and some queries have no valid row."""
    cfg = dict(SIFT, k=10)
    with Leg(f"filtered leg: {cfg['n']:,} x {cfg['dim']} l2, k=10, a bag "
             f"of tags a row, one certified batch under filter_tags"):
        db, q = make_data(cfg["n"], cfg["dim"], NQ)
        rng = np.random.default_rng(40)
        n, vocabulary = cfg["n"], 50_000
        p = np.arange(1, vocabulary + 1) ** -0.8
        tags = rng.choice(vocabulary, size=6 * n, p=p / p.sum())
        keys = np.unique(np.repeat(np.arange(n, dtype=np.int64), 6) << 32
                         | tags)
        indptr = np.concatenate([[0], np.cumsum(np.bincount(
            keys >> 32, minlength=n))])
        tags = (keys & 0xFFFFFFFF).astype(np.int32)
        ft = np.stack([tags[indptr[rng.integers(0, n, NQ)]],
                       rng.integers(0, vocabulary, NQ)], axis=1)
        ft[::2, 1] = -1
        prog = place(jax, db, mesh, cfg, row_tags=(indptr, tags))
        t0 = time.perf_counter()
        d, i, stats = prog.search_certified(q, selector="pallas",
                                            filter_tags=ft)
        told = stats["filter"]
        say(f"  filtered (first call: places the tag index, compiles): "
            f"{time.perf_counter() - t0:.2f} s; {told}; index "
            f"{prog._tag_index_cache['stats']}")
        if stats["pallas_knobs"]["interpret"] is not False:
            raise AssertionError("filtered: kernel ran in interpret mode")
        if not (told["bitmap_lookups"] and told["list_lookups"]
                and told["empty"] and told["short"]):
            raise AssertionError(f"filtered: the batch lacks a form: {told}")
        row_of = np.repeat(np.arange(n), np.diff(indptr))
        for at in range(ORACLE_WIDTH):
            ok = np.ones(n, bool)
            for t in ft[at]:
                if t >= 0:
                    has = np.zeros(n, bool)
                    has[row_of[tags == t]] = True
                    ok &= has
            rows = np.flatnonzero(ok)
            dist = ((db[rows].astype(np.float64) - q[at]) ** 2).sum(-1)
            want = rows[np.lexsort((rows, dist))][:10]
            want = np.concatenate([want, np.full(10 - want.size, -1)])
            if not np.array_equal(i[at], want):
                raise AssertionError(
                    f"filtered: query {at} tags {ft[at]}: got {i[at]}, "
                    f"the valid rows' float64 scan gives {want}")
        say(f"  {ORACLE_WIDTH} queries equal the float64 scan of their "
            f"valid rows")


# --- four chips --------------------------------------------------------------
def check_placement(prog, mesh, rows_per_shard: int) -> None:
    """Nothing piled on device 0: the db shards and the replicated
    labels each sit on every mesh device."""
    want = {d.id for d in mesh.devices.ravel()}
    for what, arr, shape in (
            ("db shards", prog._tp, (rows_per_shard, prog._tp.shape[1])),
            ("labels", prog._labels, prog._labels.shape)):
        shards = arr.addressable_shards
        devs = {s.device.id for s in shards}
        shapes = {tuple(s.data.shape) for s in shards}
        if devs != want or shapes != {tuple(shape)}:
            raise AssertionError(
                f"{what}: on devices {sorted(devs)} with shard shapes "
                f"{shapes}; expected {sorted(want)} x {tuple(shape)}")
        say(f"  {what}: {len(shards)} shards of {tuple(shape)} on devices "
            f"{sorted(devs)}")


def four_chip_legs(jax) -> None:
    from knn_tpu.parallel import make_mesh

    devs = jax.devices()[:4]
    one = make_mesh(1, 1, devices=devs[:1])
    t0 = time.perf_counter()
    db4, q = make_data(4 * SIFT["n"], SIFT["dim"], NQ)
    say(f"data from seed 0 (4M x 128): {time.perf_counter() - t0:.1f} s")

    def labels(n):
        return dict(labels=(np.arange(n) % 10).astype(np.int32),
                    num_classes=10)

    for name, db, meshes in (
            ("1M rows", db4[:SIFT["n"]], ((4, 1), (2, 2))),
            ("4M rows (1M per chip)", db4, ((1, 4),))):
        with Leg(f"one-chip reference, {name}"):
            ref = certified_batch(place(jax, db, one, SIFT), q,
                                  expect_q=NQ, label="certified on 1x1")
        for qs, ds in meshes:
            for merge in ("ring", "allgather"):
                with Leg(f"mesh {qs}x{ds} merge={merge}, {name}"):
                    mesh = make_mesh(qs, ds, devices=devs)
                    prog = place(jax, db, mesh, SIFT, merge=merge,
                                 **labels(db.shape[0]))
                    check_placement(prog, mesh, db.shape[0] // ds)
                    i = certified_batch(prog, q, expect_q=NQ,
                                        label=f"certified on {qs}x{ds}")
                    check_equal(f"{qs}x{ds} {merge} vs one chip", i, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    say(f"jax {jax.__version__}; platform {dev.platform}; device kind "
        f"{dev.device_kind}; {count} device(s)")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU: JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind}).  It has no CPU "
                 f"branch — the CPU tests are `pytest tests/`.")
    if args.chips > count:
        sys.exit(f"chip_smoke.py --chips {args.chips}: only {count} "
                 f"device(s) found")

    from knn_tpu.analysis.vmem import VMEM_BYTES_BY_KIND
    from knn_tpu.parallel import make_mesh
    from knn_tpu.utils.compat import enable_compile_cache

    if dev.device_kind not in VMEM_BYTES_BY_KIND:
        sys.exit(f"chip_smoke.py: device kind {dev.device_kind!r} is not "
                 f"in analysis.vmem.VMEM_BYTES_BY_KIND")
    _listen_to_compiles(jax)
    say(f"compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        four_chip_legs(jax)
    else:
        mesh = make_mesh(1, 1, devices=jax.devices()[:1])
        sweep_and_serving_legs(jax, mesh)
        width_leg(jax, mesh, "GIST", GIST)
        width_leg(jax, mesh, "GloVe", GLOVE)
        filtered_leg(jax, mesh)

    say(f"all legs passed; XLA compile {_COMPILE['backend_compile_s']:.1f} "
        f"s over {_COMPILE['compiles']} programs, persistent cache "
        f"{_COMPILE['cache_hits']} hits / {_COMPILE['cache_misses']} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
