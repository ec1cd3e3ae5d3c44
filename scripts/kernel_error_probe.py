#!/usr/bin/env python3
"""The kernel's rows on the chip: is their low half what it says, and is
the kernel's score inside ``kernel_tolerance``?  (PR 43, ROADMAP A17.)

Run on the chip (a CPU run proves nothing here: the CPU rounds the cast):

    chiprun -- python3 scripts/kernel_error_probe.py

65,536 unit rows and 256 unit queries of ``openai500k``'s law (1,536
columns).  Prints (1) the rows' bf16 halves under jit by the cast's own
round trip (``x - f32(bf16(x))``), as ``_split_rows`` makes them (against
``lax.reduce_precision``), and behind an optimization barrier, each
against numpy's split (max |tl|, the residual, equality to the bit); on
the v5e the first reads max |tl| 0: inside one fusion the compiler keeps
``f32(bf16(x))`` at ``x``; (2) the compiled tiled kernel's score error
(kernel score less the float64 score of the placed values) with the
in-program operands, with ``row_operands`` and with numpy-made halves,
beside ``kernel_tolerance``.  PR 43 read 1.0e-4 std and 4.7e-4 at most
in-program (3.9 times the tolerance: the in-call split was the round
trip until PR 49, so it should now read as the other two) and 2.6e-7 /
1.1e-6 for the other two.  The tile is cut as ``ops.pallas_knn.row_blocking`` cuts it
(four steps of 4,096 rows at this width since PR 46); ``--row-block N``
hands the kernel another block, to time or to check a cut the rule does
not make.
"""

import argparse
import functools
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(1, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

import datagen  # noqa: E402
import datagen_mix  # noqa: E402
import harness  # noqa: E402
from knn_tpu.ops import pallas_knn as pk  # noqa: E402
from knn_tpu.parallel import sharded as sh  # noqa: E402

SEED, ROWS, QUERIES = 4300000502, 65536, 256


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--row-block", type=int,
                    help="the rows of a tile one grid step multiplies "
                    "(None: the kernel's own reading of the shape)")
    row_block = ap.parse_args().row_block
    cfg = harness.load_cell(ROOT, "openai500k.sweep_cos").config
    dim = int(cfg["dim"])
    db = datagen_mix.draw(cfg["rows"], ROWS, dim, SEED, datagen.STREAM_ROWS)
    queries = datagen_mix.draw(cfg["rows"], QUERIES, dim, SEED,
                               datagen.STREAM_QUERIES, of=cfg["rows"])
    unit_t, unit_q = sh._unit_rows(db)[0], sh._unit_rows(queries)[0]
    t_dev = jnp.asarray(unit_t)
    bf = ml_dtypes.bfloat16
    th_np = unit_t.astype(bf)
    tl_np = (unit_t - th_np.astype(np.float32)).astype(bf)
    print("numpy: max|tl|", float(np.abs(tl_np.astype(np.float32)).max()),
          flush=True)

    def show(tag, th, tl):
        th, tl = np.asarray(th), np.asarray(tl)
        res = unit_t - th.astype(np.float32) - tl.astype(np.float32)
        print(f"{tag:44s} max|tl| "
              f"{float(np.abs(tl.astype(np.float32)).max()):.3e} "
              f"max residual {np.abs(res).max():.3e} "
              f"th==numpy {bool((th == th_np).all())} "
              f"tl==numpy {bool((tl == tl_np).all())}", flush=True)

    def round_trip(x):
        th = x.astype(jnp.bfloat16)
        return th, (x - th.astype(jnp.float32)).astype(jnp.bfloat16)

    show("the cast's own round trip, jitted", *jax.jit(round_trip)(t_dev))
    show("_split_rows", *jax.jit(functools.partial(
        pk._split_rows, with_lo=True))(t_dev))

    def barrier(x):
        th = lax.optimization_barrier(x.astype(jnp.bfloat16))
        return th, (x - th.astype(jnp.float32)).astype(jnp.bfloat16)

    show("optimization_barrier on th", *jax.jit(barrier)(t_dev))
    back = jax.jit(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32))(t_dev)
    print("a jitted f32(bf16(x)) == x everywhere:",
          bool((back == t_dev).all()), flush=True)

    def kernel_error(prepared, tag):
        run = jax.jit(lambda q, t, *p: pk._bin_candidates(
            q, t, block_q=256, tile_n=pk.TILE_N, survivors=None,
            precision="bf16x3", interpret=False, terms="hh+hl+lh",
            db_prepared=p or None, row_block=row_block))
        cd, ci, _ = (np.asarray(x) for x in run(
            jnp.asarray(unit_q), t_dev, *prepared))
        errs = []
        for r in range(0, QUERIES, 16):
            ok = ci[r] < ROWS
            t = unit_t[ci[r][ok]].astype(np.float64)
            errs.append(cd[r][ok] - ((t * t).sum(-1) - 2.0 * (
                t @ unit_q[r].astype(np.float64))))
        e = np.concatenate(errs)
        print(f"{tag:44s} kernel score error std {e.std():.3e} "
              f"min {e.min():.3e} max {e.max():.3e}", flush=True)
        return float(np.abs(e).max())

    tol = float(pk.kernel_tolerance(unit_q, unit_t).min())
    print("kernel_tolerance", tol, flush=True)
    kernel_error((), "in-program operands")
    ops = jax.jit(functools.partial(
        pk.row_operands, tile_n=pk.TILE_N, with_lo=True))(t_dev)
    show("row_operands", ops[0], ops[1])
    worst = kernel_error(tuple(ops), "row_operands")
    kernel_error((jnp.asarray(th_np), jnp.asarray(tl_np), ops[2]),
                 "numpy-made th, tl")
    # the resident operands are what the repaired cells run
    return 0 if worst < tol else 1


if __name__ == "__main__":
    sys.exit(main())
