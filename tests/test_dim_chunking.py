"""A row tile whose whole padded width fits VMEM is ONE dim chunk
(``analysis.vmem.dim_chunking``, asked by the kernel through
``ops.pallas_knn.dim_chunking``): one grid step a tile, no accumulator
scratch, the bin-select in the matmul's own step.  Wider rows are cut
into ``DIM_CHUNK`` columns as they always were.

- the rule's table: what collapses, what does not, and what it reads;
- the tiled kernel at 201 columns (one 256-column chunk) against a
  float64 oracle and against the same launch held to two chunks;
- the rule is the tiled kernel's: ``"streaming"`` and ``"fused"`` keep
  128-column chunks at every width (one wide chunk overruns them where
  the tiled kernel has room), and the three strategies stay
  bitwise-equal wherever they cut the rows alike, every chunked
  precision;
- ``search_certified(metric="dot")`` at 201 placed columns on 1 and 4
  CPU shards: the oracle's indices, and what it reports (``dim_chunk``,
  ``dim_chunks``, the counter) is what the program's kernel was handed:
  the grid traced from the program, ragged batches and shards included;
- the traced program: the grid's third axis and the scratch.
"""

import functools

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
    (128, 16384, 256, "bf16x3", "hh+hl+lh", (128, 1)),   # both BIGANN cells
    (96, 16384, 256, "bf16x3", "hh", (128, 1)),
    (201, 16384, 256, "bf16x3", "hh+hl+lh", (256, 1)),   # text2image2m5
    (256, 16384, 256, "bf16x3", "hh+hl+lh", (256, 1)),
    (300, 16384, 256, "bf16x3", "hh+hl+lh", (384, 1)),   # GloVe
    (512, 16384, 256, "bf16x3", "hh+hl+lh", (512, 1)),
    (640, 16384, 256, "bf16x3", "hh+hl+lh", (128, 5)),
    (960, 16384, 256, "bf16x3", "hh+hl+lh", (128, 8)),   # gist1m
    (960, 16384, 128, "bf16x3", "hh+hl+lh", (128, 8)),
    # one row stream in place of two: half the row blocks
    (960, 16384, 256, "bf16x3", "hh", (1024, 1)),
    (640, 16384, 256, "bf16x3", "hh+lh", (640, 1)),
    # the same width under a tile whose one-chunk blocks do not fit
    (256, 65536, 256, "bf16x3", "hh+hl+lh", (128, 2)),
    (512, 32768, 256, "bf16x3", "hh+hl+lh", (128, 4)),
    # small launches collapse whatever the width
    (960, TILE, 8, "bf16x3", "hh+hl+lh", (1024, 1)),
    # the other chunked arms by their own block arithmetic
    (256, 16384, 256, "int8", "hh+hl+lh", (256, 1)),
    (960, 16384, 256, "int8", "hh+hl+lh", (1024, 1)),    # 1 B a value
    (256, 16384, 256, "highest", "hh+hl+lh", (256, 1)),
    (960, 16384, 256, "highest", "hh+hl+lh", (128, 8)),  # 4 B a value
    (384, 16384, 256, "bf16x3f", "hh+hl+lh", (384, 1)),
    (512, 16384, 256, "bf16x3f", "hh+hl+lh", (128, 4)),  # 6 B a value
    (960, 16384, 256, "pq", "hh+hl+lh", (1024, 1)),      # no chunk loop
])
def test_the_rule_reads_the_shape(dim, tile_n, block_q, precision, terms,
                                  want):
    got = pk.dim_chunking(dim, tile_n=tile_n, block_q=block_q,
                          precision=precision, terms=terms)
    assert got == want
    chunk_w, nd = got
    assert chunk_w % pk.DIM_CHUNK == 0
    padded = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK
    assert chunk_w * nd == padded
    # the other two strategies keep the padding grain, whatever fits
    for kernel in ("streaming", "fused"):
        assert pk.dim_chunking(
            dim, tile_n=tile_n, block_q=block_q, precision=precision,
            terms=terms, kernel=kernel) == (
            (padded, 1) if precision == "pq"
            else (pk.DIM_CHUNK, padded // pk.DIM_CHUNK))


def test_the_rule_keeps_an_eighth_to_spare():
    """One chunk only where the modeled need plus ``limit_bytes``' eighth
    fits the budget: at exactly 9/8 of the need it does, a byte under it
    does not; and the need is the tiled model's at ``nd`` = 1."""
    geo = dict(tile_n=16384, block_q=256)
    need = vmem.launch_estimate(n=2_500_000, d=201, k=10, **geo)
    assert need["geometry"]["dim_chunk"] == 256
    assert need["geometry"]["dim_chunks"] == 1
    assert need["breakdown"]["accum_scratch"] == 0
    assert need["breakdown"]["db_blocks_x2"] == 32 * MIB
    assert need["breakdown"]["score_tiles"] == 32 * MIB
    total = need["total_bytes"]
    assert total == 66.75 * MIB
    edge = total + total // 8
    assert vmem.dim_chunking(256, budget_bytes=edge, **geo) == (256, 1)
    assert vmem.dim_chunking(256, budget_bytes=edge - 1, **geo) == (128, 2)
    # a chip of 16 MiB collapses nothing at this tile
    small = vmem.budget_for("TPU v3")
    assert vmem.dim_chunking(256, budget_bytes=small, **geo) == (128, 2)
    v3 = vmem.launch_estimate(n=2_500_000, d=201, k=10, budget_bytes=small,
                              **geo)
    assert v3["geometry"]["dim_chunks"] == 2
    assert v3["breakdown"]["accum_scratch"] == 16 * MIB
    # None is the target device's
    assert vmem.dim_chunking(256, **geo) == vmem.dim_chunking(
        256, budget_bytes=vmem.budget_for(vmem.TARGET_DEVICE_KIND), **geo)
    with pytest.raises(ValueError, match="multiple"):
        vmem.dim_chunking(201, **geo)


def test_gist_is_priced_as_before():
    """``gist1m``'s geometry is the one the model was fitted at: eight
    128-column chunks, 82.5 MiB, the accumulator and three live tiles."""
    est = vmem.launch_estimate(n=1_000_000, d=960, k=100, block_q=256)
    assert est["geometry"]["dim_chunk"] == 128
    assert est["geometry"]["dim_chunks"] == 8
    assert est["total_bytes"] == 82.5 * MIB
    assert est["breakdown"]["score_tiles"] == 48 * MIB


# --- the kernel ---------------------------------------------------------------
def kernel_scores64(q, db, ci):
    """``|t|^2 - 2 q.t`` in float64 of the rows ``ci`` names."""
    q64, db64 = q.astype(np.float64), db.astype(np.float64)
    full = (db64 ** 2).sum(-1)[None, :] - 2.0 * q64 @ db64.T
    return np.take_along_axis(full, np.minimum(ci, db.shape[0] - 1), axis=1)


def run_kernel(q, db, **kw):
    kw.setdefault("precision", "bf16x3")
    kw.setdefault("survivors", 2)
    return [np.asarray(x) for x in pk._bin_candidates(
        jnp.asarray(q), jnp.asarray(db), block_q=8, tile_n=TILE,
        interpret=True, **kw)]


@pytest.mark.parametrize("dim", [201, 300])
def test_one_wide_chunk_is_the_oracles_and_the_two_chunk_launchs(rng, dim):
    db = rng.normal(size=(3 * pk.BIN_W + 41, dim)).astype(np.float32)
    q = rng.normal(size=(11, dim)).astype(np.float32)
    padded = -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK
    assert pk.dim_chunking(dim, tile_n=TILE, block_q=8,
                           precision="bf16x3") == (padded, 1)
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
    three cut the rows alike.  Left to the rule (None) the tiled kernel
    runs one chunk and the other two four: the tiled launch equals the
    others handed ITS width, and the others equal the tiled launch held
    to theirs.  Two and four chunks handed to all three hold the
    multi-chunk bodies, which no small shape reaches by the rule."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    widths = [dim_chunk]
    if dim_chunk is None:
        assert pk.dim_chunking(500, tile_n=TILE, block_q=8,
                               precision=precision) == (512, 1)
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


@pytest.mark.parametrize("dim_chunk", [None, 128])
def test_the_grid_orders_cut_the_rows_alike(rng, dim_chunk):
    """``db_major`` changes the order of the grid's steps and nothing
    else, at one chunk and with the accumulator between four."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    for want, got in zip(
            run_kernel(q, db, dim_chunk=dim_chunk),
            run_kernel(q, db, dim_chunk=dim_chunk, grid_order="db_major")):
        np.testing.assert_array_equal(got, want)


def test_int8_sums_whole_numbers_whatever_the_chunking(rng):
    """The int8 arm accumulates int32: exact, so every chunking of one
    launch gives the same bits."""
    db = rng.normal(size=(3 * pk.BIN_W + 41, 500)).astype(np.float32) * 10
    q = rng.normal(size=(11, 500)).astype(np.float32) * 10
    one = run_kernel(q, db, precision="int8")
    for dim_chunk in (128, 256):
        for want, got in zip(one, run_kernel(q, db, precision="int8",
                                             dim_chunk=dim_chunk)):
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
    blocks = [bm.block_shape for bm in mapping.block_mappings
              if bm.array_aval.dtype == jnp.bfloat16]
    assert [tuple(int(getattr(x, "block_size", x)) for x in b)
            for b in blocks] == [(TILE, block_w)] * 2
    # three products a chunk, whatever its width
    assert sum(e.primitive.name == "dot_general"
               for e in call.params["jaxpr"].eqns) == 3


# --- end to end ---------------------------------------------------------------
@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def chunk_batches():
    """``knn_tpu_kernel_dim_chunks_total`` by its ``chunks`` label."""
    series = obs.snapshot().get(
        mn.KERNEL_DIM_CHUNKS, {"series": []})["series"]
    return {s["labels"]["chunks"]: s["value"] for s in series}


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
    assert stats["pallas_knobs"]["dim_chunk"] == 256
    assert stats["pallas_knobs"]["dim_chunks"] == 1
    assert stats["tuning"]["source"] == "default"
    assert chunk_batches() == {"1": 3}
    call, = [e for e in obs.get_event_log().recent()
             if e.get("span") == "certified.call"]
    assert (call["dim_chunk"], call["dim_chunks"]) == (256, 1)
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
    shape: ``_pallas_setup`` resolves the width once, hands it to the
    program's kernel and keeps it for the report.  The tiled program
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
    knobs = {kk: v for kk, v in stats["pallas_knobs"].items()
             if kk not in ("interpret", "terms", "mxu_passes", "dim_chunk",
                           "dim_chunks", "final_select_stage", "operands",
                           "sub_batch", "batches")}
    if kernel == "tiled":
        grids = program_grids(placed, q[:batch], batch_rows=batch,
                              terms=stats["terms"], **knobs)
        assert [g[2] for g in grids] == [want[1]]
    # the width handed down reaches the kernel as given: a program built
    # with another runs another, whatever the shape would have said
    from knn_tpu.parallel.sharded import _pallas_certified_program
    rows = placed._operands_cache  # the resident form the call resolved
    assert rows["key"][0] == tile_n
    forced = _pallas_certified_program(
        placed.mesh, 20, 5, placed.merge, tile_n, "bf16x3",
        n_train=placed.n_train, kernel="tiled", interpret=True,
        dim_chunk=pk.DIM_CHUNK, resident_parts=len(rows["parts"]) - 1)
    qp, _ = placed._place_queries(q[:batch])
    traced = jax.make_jaxpr(forced)(qp, placed._tp,
                                    *placed._pallas_operands("bf16x3"))
    call = next(kernel_calls(traced.jaxpr))
    assert call.params["grid_mapping"].grid[2] == want[0] * want[1] // 128


def test_setup_resolves_the_width_for_the_batch_it_is_told(rng):
    """At the cells' tile, rows of 640 columns do not collapse under a
    full query block and do under eight queries; left untold, setup
    prices a full block (never a wider chunk than a batch could fit)."""
    db = rng.normal(size=(600, 640)).astype(np.float32)
    big = ShardedKNN(db, mesh=make_mesh(1, 1), k=5)
    big._shard_rows = lambda: 1_000_000

    def resolved(**kw):
        # the program is built lazily: nothing is traced at this size
        big._pallas_setup(28, None, "bf16x3", block_q=256, **kw)
        return big._dim_chunking

    assert resolved(batch_rows=4096) == (128, 5)
    assert resolved() == (128, 5)
    assert resolved(batch_rows=8) == (640, 1)
    assert resolved(batch_rows=4096, terms="hh") == (640, 1)
    assert resolved(batch_rows=8, kernel="streaming") == (128, 5)
