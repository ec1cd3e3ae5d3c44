"""How the kernel cuts a row tile (``analysis.vmem.dim_chunking`` and
``analysis.vmem.row_blocking``, asked by the kernel through
``ops.pallas_knn``'s functions of those names).  The tiled kernel never
cuts a tile's COLUMNS: every grid step is one product over the whole
padded width.  A tile whose row blocks fit VMEM at that width is ONE
grid step (no scratch, the bin-select in the matmul's own step); a wider
one is cut by ROWS (PR 46), the bin-select's running arrays carried from
step to step.  The other two kernels keep ``DIM_CHUNK`` columns.

- the rules' table: what is one step, what is cut and how, and what the
  rules read;
- the tiled kernel at 201 columns (one 256-column chunk) against a
  float64 oracle and against the same launch held to two chunks;
- a tile cut by rows gives the uncut tile's candidates bit for bit:
  every precision, both grid orders, with validity words, and in the
  traced program one scratch of the carried state and no score tile;
- the rule is the tiled kernel's: ``"streaming"`` and ``"fused"`` keep
  128-column chunks at every width (one wide chunk overruns them where
  the tiled kernel has room), and the three strategies stay
  bitwise-equal wherever they cut the columns alike, every chunked
  precision;
- ``search_certified(metric="dot")`` at 201 placed columns on 1 and 4
  CPU shards: the oracle's indices, and what it reports (``dim_chunk``,
  ``dim_chunks``, ``row_block``, ``row_steps``, the counter) is what the
  program's kernel was handed: the grid traced from the program, ragged
  batches and shards included;
- the traced program: the grid's third axis and the scratch.
"""

import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.analysis import vmem
from knn_tpu.obs import names as mn
from knn_tpu.ops import pallas_knn as pk
from knn_tpu.parallel import ShardedKNN, make_mesh

TILE = 2 * pk.BIN_W
MIB = vmem.MIB


# --- the rule ---------------------------------------------------------------
@pytest.mark.parametrize("dim,tile_n,block_q,precision,terms,want", [
    (128, 16384, 256, "bf16x3", "hh+hl+lh", (16384, 1)),  # both BIGANN cells
    (96, 16384, 256, "bf16x3", "hh", (16384, 1)),
    (201, 16384, 256, "bf16x3", "hh+hl+lh", (16384, 1)),  # text2image2m5
    (256, 16384, 256, "bf16x3", "hh+hl+lh", (16384, 1)),  # ssnpp2m5, yfcc2m5
    (300, 16384, 256, "bf16x3", "hh+hl+lh", (16384, 1)),  # GloVe
    (512, 16384, 256, "bf16x3", "hh+hl+lh", (16384, 1)),
    (640, 16384, 256, "bf16x3", "hh+hl+lh", (4096, 4)),
    (960, 16384, 256, "bf16x3", "hh+hl+lh", (4096, 4)),   # gist1m
    (960, 16384, 128, "bf16x3", "hh+hl+lh", (4096, 4)),
    (1536, 16384, 256, "bf16x3", "hh+hl+lh", (4096, 4)),  # openai500k
    (3072, 16384, 256, "bf16x3", "hh+hl+lh", (2048, 8)),
    # one row stream in place of two: half the row blocks
    (960, 16384, 256, "bf16x3", "hh", (16384, 1)),
    (640, 16384, 256, "bf16x3", "hh+lh", (16384, 1)),
    (2048, 16384, 256, "bf16x3", "hh", (4096, 4)),
    # the same width under a tile whose whole blocks do not fit
    (256, 65536, 256, "bf16x3", "hh+hl+lh", (4096, 16)),
    (512, 32768, 256, "bf16x3", "hh+hl+lh", (4096, 8)),
    # small launches are one step whatever the width
    (960, TILE, 8, "bf16x3", "hh+hl+lh", (TILE, 1)),
    # the other arms by their own block arithmetic
    (256, 16384, 256, "int8", "hh+hl+lh", (16384, 1)),
    (960, 16384, 256, "int8", "hh+hl+lh", (16384, 1)),    # 1 B a value
    (256, 16384, 256, "highest", "hh+hl+lh", (16384, 1)),
    (960, 16384, 256, "highest", "hh+hl+lh", (4096, 4)),  # 4 B a value
    (384, 16384, 256, "bf16x3f", "hh+hl+lh", (16384, 1)),
    (512, 16384, 256, "bf16x3f", "hh+hl+lh", (4096, 4)),  # 6 B a value
    (960, 16384, 256, "pq", "hh+hl+lh", (16384, 1)),      # codes, not rows
])
def test_the_rule_reads_the_shape(dim, tile_n, block_q, precision, terms,
                                  want):
    got = pk.row_blocking(dim, tile_n=tile_n, block_q=block_q,
                          precision=precision, terms=terms)
    assert got == want
    row_block, row_steps = got
    assert row_block % pk.BIN_W == 0 and row_block * row_steps == tile_n
    assert row_steps == 1 or row_block <= vmem.ROW_BLOCK_MAX
    padded = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK
    # the tiled kernel's step multiplies the whole padded width
    assert pk.dim_chunking(dim, precision=precision) == (padded, 1)
    # a masked launch's step holds whole word blocks or a part of one
    masked = pk.row_blocking(dim, tile_n=tile_n, block_q=block_q,
                             precision=precision, terms=terms, masked=True)
    assert masked[0] * masked[1] == tile_n and masked[0] <= row_block
    assert masked[1] == 1 or not (masked[0] % vmem.MASK_WORD_ROWS
                                  and vmem.MASK_WORD_ROWS % masked[0])
    # the other two strategies keep the padding grain and whole tiles,
    # whatever fits
    for kernel in ("streaming", "fused"):
        assert pk.dim_chunking(dim, precision=precision, kernel=kernel) == (
            (padded, 1) if precision == "pq"
            else (pk.DIM_CHUNK, padded // pk.DIM_CHUNK))
        assert pk.row_blocking(
            dim, tile_n=tile_n, block_q=block_q, precision=precision,
            terms=terms, kernel=kernel) == (tile_n, 1)


def test_the_rule_keeps_an_eighth_to_spare():
    """One step only where the modeled need plus ``limit_bytes``' eighth
    fits the budget: at exactly 9/8 of the need it does, a byte under it
    does not; and the need is the tiled model's at one step.  The block
    of a cut tile fits the same way, and is the largest that does."""
    geo = dict(tile_n=16384, block_q=256)
    need = vmem.launch_estimate(n=2_500_000, d=201, k=10, **geo)
    assert need["geometry"]["dim_chunk"] == 256
    assert need["geometry"]["dim_chunks"] == 1
    assert (need["geometry"]["row_block"],
            need["geometry"]["row_steps"]) == (16384, 1)
    assert need["breakdown"]["accum_scratch"] == 0
    assert need["breakdown"]["select_state"] == 0
    assert need["breakdown"]["db_blocks_x2"] == 32 * MIB
    assert need["breakdown"]["score_tiles"] == 32 * MIB
    total = need["total_bytes"]
    assert total == 66.75 * MIB
    edge = total + total // 8
    assert vmem.row_blocking(256, budget_bytes=edge, **geo) == (16384, 1)
    assert vmem.row_blocking(256, budget_bytes=edge - 1, **geo) == (4096, 4)
    # a chip of 16 MiB keeps no whole tile at this width, and its block
    # is the largest whose need and an eighth fit
    small = vmem.budget_for("TPU v3")
    assert vmem.row_blocking(256, budget_bytes=small, **geo) == (2048, 8)
    v3 = vmem.launch_estimate(n=2_500_000, d=201, k=10, budget_bytes=small,
                              **geo)
    assert (v3["geometry"]["dim_chunks"], v3["geometry"]["row_block"],
            v3["geometry"]["row_steps"]) == (1, 2048, 8)
    assert v3["breakdown"]["accum_scratch"] == 0
    assert v3["breakdown"]["select_state"] == 640 * 1024
    assert v3["total_bytes"] + v3["total_bytes"] // 8 <= small
    twice = dict(v3["breakdown"], db_blocks_x2=2 * v3["breakdown"][
        "db_blocks_x2"], aux_x2=2 * v3["breakdown"]["aux_x2"],
        score_tiles=2 * v3["breakdown"]["score_tiles"])
    assert sum(twice.values()) * 9 // 8 > small
    # where not even one group fits, one group: the kernel's own budget
    # check then refuses the launch
    assert vmem.row_blocking(256, budget_bytes=MIB, **geo) == (128, 128)
    # None is the target device's
    assert vmem.row_blocking(1024, **geo) == vmem.row_blocking(
        1024, budget_bytes=vmem.budget_for(vmem.TARGET_DEVICE_KIND), **geo)
    with pytest.raises(ValueError, match="multiple"):
        vmem.row_blocking(201, **geo)
    with pytest.raises(ValueError, match="multiple"):
        vmem.dim_chunking(201)


@pytest.mark.parametrize("shape,n,d,block,mib,state", [
    ("gist1m", 1_000_000, 960, 4096, 52.125, 640 * 1024),
    ("openai500k", 500_000, 1536, 4096, 73.125, 640 * 1024),
])
def test_a_cut_tile_is_priced_by_its_row_block(shape, n, d, block, mib,
                                               state):
    """``gist1m``'s and ``openai500k``'s geometry: one dim chunk, four
    steps of 4,096 rows, the select's state in place of the 16 MiB
    accumulator (eight 128-column chunks, 82.5 MiB, until PR 46)."""
    est = vmem.launch_estimate(n=n, d=d, k=100, block_q=256)
    padded = -(-d // 128) * 128
    assert est["geometry"]["dim_chunk"] == padded
    assert est["geometry"]["dim_chunks"] == 1
    assert (est["geometry"]["row_block"],
            est["geometry"]["row_steps"]) == (block, 16384 // block)
    assert est["total_bytes"] == mib * MIB
    assert est["breakdown"]["accum_scratch"] == 0
    assert est["breakdown"]["select_state"] == state
    assert est["breakdown"]["db_blocks_x2"] == 2 * 2 * block * padded * 2
    assert est["breakdown"]["query_x2"] == 2 * 256 * padded * 4


# --- the kernel ---------------------------------------------------------------
def kernel_scores64(q, db, ci):
    """``|t|^2 - 2 q.t`` in float64 of the rows ``ci`` names."""
    q64, db64 = q.astype(np.float64), db.astype(np.float64)
    full = (db64 ** 2).sum(-1)[None, :] - 2.0 * q64 @ db64.T
    return np.take_along_axis(full, np.minimum(ci, db.shape[0] - 1), axis=1)


def run_kernel(q, db, **kw):
    kw.setdefault("precision", "bf16x3")
    kw.setdefault("survivors", 2)
    kw.setdefault("tile_n", TILE)
    return [np.asarray(x) for x in pk._bin_candidates(
        jnp.asarray(q), jnp.asarray(db), block_q=8, interpret=True, **kw)]


@pytest.mark.parametrize("dim", [201, 300])
def test_one_wide_chunk_is_the_oracles_and_the_two_chunk_launchs(rng, dim):
    db = rng.normal(size=(3 * pk.BIN_W + 41, dim)).astype(np.float32)
    q = rng.normal(size=(11, dim)).astype(np.float32)
    padded = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK
    assert pk.dim_chunking(dim, precision="bf16x3") == (padded, 1)
    assert pk.row_blocking(dim, tile_n=TILE, block_q=8,
                           precision="bf16x3") == (TILE, 1)
    n_q = q.shape[0]  # the launch pads the batch to its query block
    # one survivor a bin: a tile of two lane-rows then has a bound
    cd, ci, bounds = (x[:n_q] for x in run_kernel(q, db, survivors=1))
    real = ci < db.shape[0]
    assert real.sum() > n_q * pk.BIN_W  # a tile's worth and more
    tol = pk.kernel_tolerance(q, db)[:, None]
    want = kernel_scores64(q, db, ci)
    assert (np.abs(cd - want)[real] <= np.broadcast_to(
        tol, cd.shape)[real]).all()
    assert np.isfinite(bounds).any()
    cd2, ci2, bounds2 = (x[:n_q] for x in run_kernel(
        q, db, survivors=1, dim_chunk=pk.DIM_CHUNK))
    np.testing.assert_array_equal(ci2, ci)
    assert (np.abs(cd2 - cd)[real] <= np.broadcast_to(
        tol, cd.shape)[real]).all()
    fin = np.isfinite(bounds)
    np.testing.assert_array_equal(np.isfinite(bounds2), fin)
    assert (np.abs(bounds2 - bounds)[fin] <= np.broadcast_to(
        tol[:, :1], bounds.shape)[fin]).all()


@pytest.mark.parametrize("dim_chunk", [None, 128, 256])
@pytest.mark.parametrize("precision", ["bf16x3", "bf16x3f", "highest",
                                       "int8"])
def test_the_strategies_are_equal_where_the_chunking_is(rng, precision,
                                                        dim_chunk):
    """Bitwise across ``kernel`` at 512 padded columns wherever the
    three cut the columns alike.  Left to the rule (None) the tiled
    kernel runs one chunk and the other two four: the tiled launch
    equals the others handed ITS width, and the others equal the tiled
    launch held to theirs.  Two and four chunks handed to all three
    hold the multi-chunk bodies, the tiled kernel's reachable by no
    other way since PR 46.  Where the tiled kernel sums a width in one
    product that the others add up by chunks, indices agree and scores
    to ``kernel_tolerance`` (the next test)."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    widths = [dim_chunk]
    if dim_chunk is None:
        assert pk.dim_chunking(500, precision=precision) == (512, 1)
        widths = [512, None]
    tiled = run_kernel(q, db, precision=precision, dim_chunk=dim_chunk)
    assert np.isfinite(tiled[0]).any()
    for width in widths:
        if width is None:  # the others' own reading: the padding grain
            tiled = run_kernel(q, db, precision=precision,
                               dim_chunk=pk.DIM_CHUNK)
        for kernel in ("streaming", "fused"):
            for want, got in zip(tiled, run_kernel(
                    q, db, precision=precision, dim_chunk=width,
                    kernel=kernel)):
                np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cut", [{}, {"dim_chunk": 128},
                                 {"row_block": pk.BIN_W}])
def test_the_grid_orders_cut_the_rows_alike(rng, cut):
    """``db_major`` changes the order of the grid's steps and nothing
    else, at one step a tile, with the accumulator between four chunks
    and with the select's state between two row blocks."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    for want, got in zip(
            run_kernel(q, db, **cut),
            run_kernel(q, db, grid_order="db_major", **cut)):
        np.testing.assert_array_equal(got, want)


# --- a tile cut by rows -------------------------------------------------------
WIDE = 8 * pk.BIN_W   # a tile of eight groups: blocks of 1, 2 and 4


def wide_case(rng, dim=384, tiles=3):
    # the last tile holds 100 rows: bins of padding rows alone
    db = rng.normal(size=((tiles - 1) * WIDE + 100,
                          dim)).astype(np.float32) * 3
    q = rng.normal(size=(11, dim)).astype(np.float32) * 3
    return q, db


@pytest.mark.parametrize("grid_order", ["query_major", "db_major"])
@pytest.mark.parametrize("row_block", [128, 256, 512])
def test_a_tile_cut_by_rows_is_the_uncut_tiles(rng, row_block, grid_order):
    """384 columns, tiles of 1,024 rows walked in blocks of one, two and
    four groups: ``cand_d``, ``cand_i`` and ``bounds`` are the one-step
    launch's in shape and bit for bit (a step's product is whole and the
    network inserts the tile's groups in the tile's order), the
    padded last tile's bins included (the masked test below holds the
    sentinels of empty bins); and they are the float64 oracle's within
    ``kernel_tolerance``."""
    q, db = wide_case(rng)
    want = run_kernel(q, db, tile_n=WIDE, survivors=2)
    got = run_kernel(q, db, tile_n=WIDE, survivors=2, row_block=row_block,
                     grid_order=grid_order)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    cd, ci, bounds = (x[:q.shape[0]] for x in got)
    assert cd.shape == (11, 3 * 2 * pk.BIN_W)
    assert bounds.shape == (11, 3 * pk.BIN_W)
    real = ci < db.shape[0]
    # the last tile's padding rows surface far away, never as a real row
    assert (~real).any() and (cd[~real] > 1e30).all()
    assert ((ci == np.iinfo(np.int32).max) == np.isinf(cd)).all()
    tol = np.broadcast_to(pk.kernel_tolerance(q, db)[:, None], cd.shape)
    assert (np.abs(cd - kernel_scores64(q, db, ci))[real] <= tol[real]).all()
    # the bins are the tile's: lane b of every group of the 1,024 rows
    assert (ci[real] % pk.BIN_W == np.broadcast_to(
        np.tile(np.arange(pk.BIN_W), 6), ci.shape)[real]).all()


@pytest.mark.parametrize("precision,terms", [
    ("bf16x3", "hh"), ("bf16x3", "hh+lh"), ("bf16x3f", "hh+hl+lh"),
    ("highest", "hh+hl+lh"), ("int8", "hh+hl+lh")])
def test_every_arm_cuts_its_tile_by_rows(rng, precision, terms):
    q, db = wide_case(rng)
    if terms != "hh+hl+lh":  # rows (and batch) that ARE their bf16 cast
        db = np.round(db * 8)
        q = np.round(q * 8) if terms == "hh" else q
    for want, got in zip(
            run_kernel(q, db, tile_n=WIDE, precision=precision, terms=terms),
            run_kernel(q, db, tile_n=WIDE, precision=precision, terms=terms,
                       row_block=256)):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tile_n,row_block", [
    (WIDE, 128), (WIDE, 256), (WIDE, 512),   # parts of one word block
    (3 * pk.BIN_W, 128),                     # a tile of three groups
    (8192, 4096),                            # whole word blocks a step
    (8192, 1024)])                           # ... and parts of two
def test_a_masked_tile_cut_by_rows_is_the_uncut_tiles(rng, tile_n,
                                                      row_block):
    """The same under per-query validity words, at row blocks under
    4,096 rows (a step reads the one word block its groups share,
    shifted to its first group) and of 4,096 (whole word blocks at the
    words' own bits)."""
    dim = 384 if tile_n < 8192 else 40
    db = rng.normal(size=(2 * tile_n + 300, dim)).astype(np.float32)
    q = rng.normal(size=(9, dim)).astype(np.float32)
    valid = rng.random((9, db.shape[0])) < 0.3
    valid[0] = False               # nothing valid
    valid[1, : tile_n] = True      # a tile wholly valid
    words = jnp.asarray(pk.pack_valid_words(valid, tile_n))
    want = run_kernel(q, db, tile_n=tile_n, valid_words=words)
    assert np.isfinite(want[0]).any() and not np.isfinite(want[0][0]).any()
    for grid_order in ("query_major", "db_major"):
        for a, b in zip(run_kernel(q, db, tile_n=tile_n, valid_words=words,
                                   row_block=row_block,
                                   grid_order=grid_order), want):
            np.testing.assert_array_equal(a, b)
    ci = want[1][:9]
    real = ci < db.shape[0]
    assert valid[np.nonzero(real)[0], ci[real]].all()


@pytest.mark.parametrize("kw", [
    {"row_block": 100}, {"row_block": 384},          # groups; divides
    {"row_block": 128, "dim_chunk": 128},            # one cut a launch
    {"row_block": 128, "kernel": "streaming"},
    {"row_block": 128, "kernel": "fused"},
    {"row_block": 128, "precision": "pq"}])          # codes, not rows
def test_a_row_block_that_does_not_cut_the_tile_is_refused(kw):
    db = jnp.zeros((WIDE, 500), jnp.float32)
    with pytest.raises(ValueError, match="row_block"):
        pk._bin_candidates(db[:8], db, block_q=8, tile_n=WIDE,
                           survivors=2, interpret=True,
                           **{"precision": "bf16x3", **kw})


def test_a_masked_row_block_keeps_to_the_words():
    db = jnp.zeros((12 * pk.BIN_W, 256), jnp.float32)
    words = jnp.zeros((8, pk.valid_words_per_tile(12 * pk.BIN_W)), jnp.int32)
    with pytest.raises(ValueError, match="validity words"):
        pk._bin_candidates(db[:8], db, block_q=8, tile_n=12 * pk.BIN_W,
                           survivors=2, precision="bf16x3", interpret=True,
                           row_block=3 * pk.BIN_W, valid_words=words)


def test_int8_sums_whole_numbers_whatever_the_chunking(rng):
    """The int8 arm accumulates int32: exact, so every chunking of one
    launch gives the same bits."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    one = run_kernel(q, db, precision="int8")
    for cut in ({"dim_chunk": 128}, {"dim_chunk": 256},
                {"row_block": pk.BIN_W}):
        for want, got in zip(one, run_kernel(q, db, precision="int8",
                                             **cut)):
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dim_chunk", [100, 384, 1024])
def test_a_chunk_that_does_not_cut_the_width_is_refused(dim_chunk):
    db = jnp.zeros((TILE, 500), jnp.float32)
    with pytest.raises(ValueError, match="dim_chunk"):
        pk._bin_candidates(db[:8], db, block_q=8, tile_n=TILE,
                           survivors=2, precision="bf16x3",
                           interpret=True, dim_chunk=dim_chunk)


# --- the traced program ---------------------------------------------------
def kernel_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from kernel_calls(sub)


@pytest.mark.parametrize("dim,dim_chunk,chunks,block_w", [
    (201, None, 1, 256), (201, 128, 2, 128), (128, None, 1, 128),
    (500, None, 1, 512), (500, 256, 2, 256)])
def test_one_chunk_is_one_grid_step_and_no_scratch(dim, dim_chunk, chunks,
                                                   block_w):
    q = jax.ShapeDtypeStruct((16, dim), jnp.float32)
    db = jax.ShapeDtypeStruct((3 * TILE, dim), jnp.float32)
    traced = jax.make_jaxpr(functools.partial(
        pk._bin_candidates, block_q=8, tile_n=TILE, survivors=2,
        precision="bf16x3", interpret=True, dim_chunk=dim_chunk))(q, db)
    call, = kernel_calls(traced.jaxpr)
    mapping = call.params["grid_mapping"]
    assert tuple(mapping.grid) == (2, 3, chunks)
    assert mapping.num_scratch_operands == (0 if chunks == 1 else 1)
    rows = [v.aval for v in call.invars if v.aval.dtype == jnp.bfloat16]
    assert len(rows) == 2
    assert row_blocks(mapping) == [(TILE, block_w)] * 2
    # three products a chunk, whatever its width
    assert sum(e.primitive.name == "dot_general"
               for e in call.params["jaxpr"].eqns) == 3


def row_blocks(mapping):
    """The block shapes of a kernel call's bf16 (row part) operands."""
    return [tuple(int(getattr(x, "block_size", x)) for x in bm.block_shape)
            for bm in mapping.block_mappings
            if bm.array_aval.dtype == jnp.bfloat16]


def scratch_shapes(call):
    """(shape, dtype) of a kernel call's scratch operands: the last
    arguments of its body."""
    n = call.params["grid_mapping"].num_scratch_operands
    return [(tuple(v.aval.shape), v.aval.dtype)
            for v in call.params["jaxpr"].invars[-n:]] if n else []


@pytest.mark.parametrize("dim,row_block,masked", [
    (500, 128, False), (500, 512, False), (201, 256, True)])
def test_a_cut_tile_carries_the_selects_state_and_no_score_tile(
        dim, row_block, masked):
    """The lowered call of a tile of 1,024 rows cut by rows: the grid's
    third axis walks the row blocks, the row parts' blocks are one block
    at the whole padded width, the query block's index does not move
    with the tile or the step, and the ONE scratch is the select's
    running arrays (2 x 2 + 1 of ``[block_q, 128]``), not a
    ``[block_q, tile_n]`` partial product."""
    padded = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK
    q = jax.ShapeDtypeStruct((16, dim), jnp.float32)
    db = jax.ShapeDtypeStruct((3 * WIDE, dim), jnp.float32)
    words = jax.ShapeDtypeStruct(
        (16, 3 * pk.valid_words_per_tile(WIDE)), jnp.int32)
    traced = jax.make_jaxpr(functools.partial(
        pk._bin_candidates, block_q=8, tile_n=WIDE, survivors=2,
        precision="bf16x3", interpret=True, row_block=row_block))(
        q, db, **({"valid_words": words} if masked else {}))
    call, = kernel_calls(traced.jaxpr)
    mapping = call.params["grid_mapping"]
    steps = WIDE // row_block
    assert tuple(mapping.grid) == (2, 3, steps)
    assert row_blocks(mapping) == [(row_block, padded)] * 2
    assert scratch_shapes(call) == [((5, 8, pk.BIN_W), jnp.float32)]
    # three products a step, each over the whole width
    dots = [e for e in call.params["jaxpr"].eqns
            if e.primitive.name == "dot_general"]
    assert [tuple(e.outvars[0].aval.shape) for e in dots] == [
        (8, row_block)] * 3
    assert {e.invars[0].aval.shape[1] for e in dots} == {padded}
    # the query block's index reads the query block's axis alone
    q_map = mapping.block_mappings[0].index_map_jaxpr.jaxpr
    used = [v for e in q_map.eqns for v in e.invars] + list(q_map.outvars)
    assert not any(v is q_map.invars[1] or v is q_map.invars[2]
                   for v in used)
    assert any(v is q_map.invars[0] for v in used)


def kernel_ops(jaxpr):
    n = 0
    for eqn in jaxpr.eqns:
        n += 1 + sum(kernel_ops(sub)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


@pytest.mark.parametrize("dim,steps,parents_ops", [
    (960, 4, 1722), (1536, 4, 1722)])
def test_the_cut_kernels_trace_is_no_larger_than_the_parents(dim, steps,
                                                             parents_ops):
    """At ``gist1m``'s and ``openai500k``'s shapes a step unrolls 32
    groups where the tile unrolled 128: the kernel body binds a third of
    the operations it did (1,722 on PR 44's tree, counted by this
    function there), and the trace's size is ``first batch``'s seconds
    (root PERF.md, PR 29)."""
    q = jax.ShapeDtypeStruct((4096, dim), jnp.float32)
    db = jax.ShapeDtypeStruct((1_000_000, dim), jnp.float32)
    traced = jax.make_jaxpr(functools.partial(
        pk._bin_candidates, block_q=256, tile_n=pk.TILE_N, survivors=2,
        precision="bf16x3", interpret=True))(q, db)
    call, = kernel_calls(traced.jaxpr)
    assert tuple(call.params["grid_mapping"].grid) == (16, 62, steps)
    assert scratch_shapes(call) == [((5, 256, pk.BIN_W), jnp.float32)]
    assert kernel_ops(call.params["jaxpr"]) <= parents_ops // 3


@pytest.mark.parametrize("dim,terms,masked,parents_ops", [
    (256, "hh+hl+lh", False, 1708),   # text2image2m5
    (128, "hh", False, 1700),         # both BIGANN cells
    (256, "hh", True, 2342)])         # yfcc2m5: validity words
def test_the_one_step_kernels_trace_keeps_its_size(dim, terms, masked,
                                                   parents_ops):
    """The five cells whose tile is one grid step trace the kernel body
    they traced on PR 44's tree (counted by ``kernel_ops`` there): an
    edit that unrolls more into every cell's trace is seconds of every
    process's ``first batch`` (root PERF.md, PRs 29 and 46), and fails
    here before it reaches the chip."""
    q = jax.ShapeDtypeStruct((4096, dim), jnp.float32)
    db = jax.ShapeDtypeStruct((1_000_000, dim), jnp.float32)
    words = jax.ShapeDtypeStruct(
        (4096, 62 * pk.valid_words_per_tile(pk.TILE_N)), jnp.int32)
    traced = jax.make_jaxpr(functools.partial(
        pk._bin_candidates, block_q=256, tile_n=pk.TILE_N, survivors=None,
        precision="bf16x3", interpret=True, terms=terms))(
        q, db, **({"valid_words": words} if masked else {}))
    call, = kernel_calls(traced.jaxpr)
    assert tuple(call.params["grid_mapping"].grid) == (16, 62, 1)
    assert scratch_shapes(call) == []
    assert kernel_ops(call.params["jaxpr"]) <= parents_ops


def nested_code(code, *names):
    """The code object of ``names`` nested in ``code``, outermost
    first."""
    for name in names:
        code, = [c for c in code.co_consts
                 if getattr(c, "co_name", None) == name]
    return code


def frame_slots(code):
    """A frame's size but for its fixed header, in pointers, as
    ``scripts/frame_sizes.py`` counts it for two trees."""
    spec = importlib.util.spec_from_file_location(
        "frame_sizes", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "frame_sizes.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return sum(module.slots(code))


def trace_stack():
    """name -> code of this repo's functions on the Python stack while
    a cell's kernel body is traced, from ``search_certified`` down to
    the bin-select's unrolled loop."""
    from knn_tpu.parallel import sharded

    # the function under a jit or a cache
    unjit = lambda f: f.__wrapped__.__code__  # noqa: E731
    kernel = pk._kernel.__code__
    return {
        "search_certified": ShardedKNN.search_certified.__code__,
        "_certify_pallas": ShardedKNN._certify_pallas.__code__,
        "_retry_transient": sharded._retry_transient.__code__,
        "spmd": nested_code(
            unjit(sharded._pallas_certified_program), "spmd"),
        "local_certified_candidates": unjit(pk.local_certified_candidates),
        "local_coarse_candidates": unjit(pk.local_coarse_candidates),
        "_bin_candidates": unjit(pk._bin_candidates),
        "_kernel": kernel,
        "write": nested_code(kernel, "write"),
        "_emit_select_grouped": pk._emit_select_grouped.__code__,
        "_emit_select_grouped_scores":
            pk._emit_select_grouped_scores.__code__,
        # a cut cell's stack: between ``_bin_candidates`` and the
        # kernel, and under it in the emitters' place
        "_row_call": pk._row_call.__code__,
        "_row_step": pk._row_step.__code__,
    }


@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="frame sizes are CPython 3.12's")
@pytest.mark.parametrize("name,slots", [
    ("search_certified", 107), ("_certify_pallas", 57),
    ("_retry_transient", 15), ("spmd", 59),
    ("local_certified_candidates", 41), ("local_coarse_candidates", 43),
    ("_bin_candidates", 91), ("_kernel", 48), ("write", 25),
    ("_emit_select_grouped", 14), ("_emit_select_grouped_scores", 38),
    ("_row_call", 53), ("_row_step", 44)])
def test_the_trace_stacks_frames_keep_their_size(name, slots):
    """No rule of the program: a tripwire.  CPython keeps its frames in
    16 KiB chunks, and where a chunk ends between the bin-select's
    unrolled loop and the ``lax`` binds under it every bind maps and
    unmaps one: 0.8 to 1.5 s of a cell's ``first batch``, most of its
    ``setup_s`` bound, drawn anew by ANY change to the summed frame
    sizes of the stack above (root PERF.md section 6, PRs 29 and 46;
    PR 45 was refused for it in a cell whose program it did not
    change).  PR 46 left every frame here at PR 44's size, so its
    one-step cells kept PR 44's draw.  An edit that moves one is not
    wrong: read ``set-up: first batch`` and ``setup_s`` of warm 10 s
    runs on the chip, parent against change, in every cell (the gate in
    PERF.md section 6, PR 46), and record the new size here with the
    readings."""
    assert frame_slots(trace_stack()[name]) == slots, (
        f"{name}'s frame changed size: run the first-batch gate on the "
        f"chip (root PERF.md section 6, PR 46) before recording it")


# --- end to end ---------------------------------------------------------------
@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def chunk_batches(label="chunks"):
    """``knn_tpu_kernel_dim_chunks_total`` by one of its labels."""
    series = obs.snapshot().get(
        mn.KERNEL_DIM_CHUNKS, {"series": []})["series"]
    return {s["labels"][label]: s["value"] for s in series}


@pytest.mark.parametrize("shards", [1, 4])
def test_inner_product_at_201_columns_runs_one_chunk(fresh_registry, rng,
                                                     shards):
    """``text2image2m5``'s shape in small: 200 columns and the appended
    norm column, padded to 256 and multiplied as one chunk."""
    db = rng.normal(size=(shards * 700, 200)).astype(np.float32)
    db *= rng.lognormal(0.0, 0.2, size=(db.shape[0], 1)).astype(np.float32)
    q = rng.normal(size=(9, 200)).astype(np.float32)
    prog = ShardedKNN(
        db, mesh=make_mesh(1, shards, devices=jax.devices()[:shards]),
        k=10, metric="dot")
    # placed in whole lane tiles; the host's copy keeps the 201 given
    assert (prog._tp.shape[1], prog._host_train().shape[1]) == (256, 201)
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        batch_size=3)
    scores = -(q.astype(np.float64) @ db.astype(np.float64).T)
    want = np.lexsort((np.broadcast_to(np.arange(db.shape[0]), scores.shape),
                       scores), axis=1)[:, :10]
    np.testing.assert_array_equal(i, want)
    np.testing.assert_allclose(
        d, np.take_along_axis(scores, want, axis=1), rtol=0, atol=1e-12)
    assert (stats["dim_chunk"], stats["dim_chunks"]) == (256, 1)
    assert (stats["row_block"], stats["row_steps"]) == (TILE, 1)
    assert stats["pallas_knobs"]["dim_chunk"] == 256
    assert stats["pallas_knobs"]["dim_chunks"] == 1
    assert stats["pallas_knobs"]["row_steps"] == 1
    assert stats["tuning"]["source"] == "default"
    assert chunk_batches() == {"1": 3}
    assert chunk_batches("row_steps") == {"1": 3}
    call, = [e for e in obs.get_event_log().recent()
             if e.get("span") == "certified.call"]
    assert (call["dim_chunk"], call["dim_chunks"]) == (256, 1)
    assert (call["row_block"], call["row_steps"]) == (TILE, 1)
    assert (call["terms"], call["mxu_passes"]) == ("hh+hl+lh", 3)


def program_grids(placed, q, **setup):
    """The kernel grids of the program ``_pallas_setup`` builds for
    batches of ``q``'s rows, traced (not run) on ``placed``'s mesh."""
    prog, _, _, _ = placed._pallas_setup(28, **setup)
    qp, _ = placed._place_queries(q)
    traced = jax.make_jaxpr(prog)(
        qp, placed._tp, *placed._pallas_operands(setup["precision"]))
    return [tuple(c.params["grid_mapping"].grid)
            for c in kernel_calls(traced.jaxpr)
            if len(c.params["grid_mapping"].grid) == 3]


@pytest.mark.parametrize("shards,dim,tile_n,batch,kernel,want", [
    # GIST's width under a small tile collapses; ragged batches of 5
    (1, 960, TILE, 5, "tiled", (1024, 1)),
    (4, 960, TILE, 5, "tiled", (1024, 1)),
    # a query mesh axis: 3 rows a shard, padded to the 8-row block
    ((2, 2), 300, TILE, 6, "tiled", (384, 1)),
    # the other strategies keep 128 columns where the tiled one collapses
    (1, 300, TILE, 8, "streaming", (128, 3)),
    (4, 300, TILE, 8, "fused", (128, 3)),
])
def test_the_event_says_what_the_kernel_was_given(fresh_registry, rng,
                                                  shards, dim, tile_n,
                                                  batch, kernel, want):
    """What ``search_certified`` reports is no second reading of the
    shape: ``_pallas_setup`` resolves the cut once, hands the program's
    kernel the row block and keeps both for the report (the columns are
    a function of the width, the precision and the kernel alone, which
    the kernel reads to the same answer).  The tiled program
    traced for the same batches has that many steps on its grid's third
    axis (the other two strategies loop over the chunks in their body:
    their row buffers are that wide)."""
    q_shards, db_shards = shards if isinstance(shards, tuple) else (1, shards)
    db = rng.normal(size=(db_shards * 600, dim)).astype(np.float32)
    q = rng.normal(size=(13, dim)).astype(np.float32)
    placed = ShardedKNN(
        db, k=5, mesh=make_mesh(
            q_shards, db_shards,
            devices=jax.devices()[:q_shards * db_shards]))
    d, i, stats = placed.search_certified(
        q, selector="pallas", tile_n=tile_n, batch_size=batch,
        kernel=kernel)
    d2 = ((q.astype(np.float64)[:, None, :]
           - db.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(
        i, np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d2.shape),
                       d2), axis=1)[:, :5])
    assert (stats["dim_chunk"], stats["dim_chunks"]) == want
    assert (stats["pallas_knobs"]["dim_chunk"],
            stats["pallas_knobs"]["dim_chunks"]) == want
    assert chunk_batches() == {str(want[1]): -(-13 // batch)}
    call, = [e for e in obs.get_event_log().recent()
             if e.get("span") == "certified.call"]
    assert (call["dim_chunk"], call["dim_chunks"]) == want
    # these tiles fit whole at every width here: one step each
    assert (stats["row_block"], stats["row_steps"]) == (tile_n, 1)
    assert (call["row_block"], call["row_steps"]) == (tile_n, 1)
    knobs = {kk: v for kk, v in stats["pallas_knobs"].items()
             if kk not in ("interpret", "terms", "mxu_passes", "dim_chunk",
                           "dim_chunks", "row_block", "row_steps",
                           "final_select_stage", "select_merge_short",
                           "operands", "sub_batch", "batches",
                           "survivor_depth")}
    if kernel == "tiled":
        grids = program_grids(placed, q[:batch], batch_rows=batch,
                              terms=stats["terms"], **knobs)
        assert [g[2] for g in grids] == [want[1]]
    # the block handed down reaches the kernel as given: a program built
    # with another runs another, whatever the shape would have said
    from knn_tpu.parallel.sharded import _pallas_certified_program
    rows = placed._operands_cache  # the resident form the call resolved
    assert rows["key"][0] == tile_n
    forced = _pallas_certified_program(
        placed.mesh, 20, 5, placed.merge, tile_n, "bf16x3",
        n_train=placed.n_train, kernel="tiled", interpret=True,
        row_block=pk.BIN_W, resident_parts=len(rows["parts"]) - 1)
    qp, _ = placed._place_queries(q[:batch])
    traced = jax.make_jaxpr(forced)(qp, placed._tp,
                                    *placed._pallas_operands("bf16x3"))
    call = next(kernel_calls(traced.jaxpr))
    assert call.params["grid_mapping"].grid[2] == tile_n // pk.BIN_W


@pytest.mark.parametrize("shards", [1, 4])
def test_the_event_says_which_form_ran(fresh_registry, rng, monkeypatch,
                                       shards):
    """GIST's width on a chip of 16 MiB of VMEM (the rule is handed a
    v3's budget: a v5e's cuts no tile small enough to interpret): tiles
    of 2,048 rows do not fit whole, so setup resolves two steps of
    1,024, hands the program's kernel that block and reports it; the
    answers are the float64 oracle's, and the program traced for the
    same batches walks two row blocks a tile at one dim chunk."""
    monkeypatch.setattr(pk, "_vmem_device_kind", lambda: "TPU v3")
    tile_n, want = 2048, (1024, 2)
    db = rng.normal(size=(shards * 2100, 960)).astype(np.float32)
    q = rng.normal(size=(13, 960)).astype(np.float32)
    placed = ShardedKNN(
        db, k=5, mesh=make_mesh(1, shards, devices=jax.devices()[:shards]))
    d, i, stats = placed.search_certified(
        q, selector="pallas", tile_n=tile_n, batch_size=5)
    d2 = ((q.astype(np.float64)[:, None, :]
           - db.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(
        i, np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d2.shape),
                       d2), axis=1)[:, :5])
    assert (stats["dim_chunk"], stats["dim_chunks"]) == (1024, 1)
    assert (stats["row_block"], stats["row_steps"]) == want
    assert (stats["pallas_knobs"]["row_block"],
            stats["pallas_knobs"]["row_steps"]) == want
    assert stats["tuning"]["source"] == "default"
    assert chunk_batches() == {"1": 3}
    assert chunk_batches("row_steps") == {"2": 3}
    call, = [e for e in obs.get_event_log().recent()
             if e.get("span") == "certified.call"]
    assert (call["dim_chunk"], call["dim_chunks"]) == (1024, 1)
    assert (call["row_block"], call["row_steps"]) == want
    knobs = {kk: v for kk, v in stats["pallas_knobs"].items()
             if kk in ("tile_n", "precision", "kernel", "survivors",
                       "block_q", "grid_order", "final_select",
                       "final_recall_target")}
    assert program_grids(placed, q[:5], batch_rows=5, terms=stats["terms"],
                         **knobs) == [(1, 2, 2)]
    # a masked program's step keeps to its word blocks: 1,024 rows are
    # a part of one
    placed._pallas_setup(28, batch_rows=5, terms=stats["terms"],
                         masked=True, **knobs)
    assert placed._row_blocking == want


def test_setup_resolves_the_cut_for_the_batch_it_is_told(rng):
    """At the cells' tile, rows of 640 columns are cut by rows under a
    full query block and are one step under eight queries; left untold,
    setup prices a full block (never a larger block than a batch could
    fit); the width is one chunk under the tiled kernel either way."""
    db = rng.normal(size=(600, 640)).astype(np.float32)
    big = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    big._shard_rows = lambda: 1_000_000

    def resolved(**kw):
        # the program is built lazily: nothing is traced at this size
        big._pallas_setup(28, None, "bf16x3", block_q=256, **kw)
        return big._dim_chunking, big._row_blocking

    assert resolved(batch_rows=4096) == ((640, 1), (4096, 4))
    assert resolved() == ((640, 1), (4096, 4))
    assert resolved(batch_rows=8) == ((640, 1), (16384, 1))
    assert resolved(batch_rows=4096, terms="hh") == ((640, 1), (16384, 1))
    assert resolved(batch_rows=4096, masked=True) == ((640, 1), (4096, 4))
    assert resolved(batch_rows=8, kernel="streaming") == (
        (128, 5), (16384, 1))
