"""Exact inner-product search (``text2image2m5``): ``ShardedKNN(...,
metric="dot")`` through ``search_certified``, held to the contract l2
has: the indices equal float64 brute force in (-q.t, index) order over
the float32 rows and queries as given.  On the CPU, the kernel
interpreted, at sizes a test can hold:

- the system against the plain reference (``benchmark/reference_ip.py``)
  on seeded ``datagen_mix`` rows, on one device and db-sharded over four;
- a built corpus the float32-augmented problem gets wrong: pairs of rows
  whose inner products with a query differ by less than ``M * 2^-24``,
  beside a row of far larger norm; ties broken by index;
- the reference itself, its controls and ``compare`` on negative scores;
  ``datagen_mix`` whatever the thread count;
- the span, the counter, the call's event and the placement event;
- the cell ``text2image2m5.sweep_ip`` through the whole benchmark
  harness, traced and not, and broken timed paths coming out
  ``correct: false``.
"""

import json
import os
import re
import sys
import time

import jax
import numpy as np
import pytest

from knn_tpu import obs
from knn_tpu.obs import names as mn
from knn_tpu.ops import refine
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.parallel import sharded as sh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
for _p in (BENCH_DIR, os.path.join(BENCH_DIR, "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import datagen  # noqa: E402  (benchmark/)
import datagen_mix  # noqa: E402
import harness  # noqa: E402
import lastline  # noqa: E402
import reference  # noqa: E402
import reference_ip  # noqa: E402
import tinyroot  # noqa: E402  (benchmark/tests/)

CELL = "text2image2m5.sweep_ip"
K = 10
TILE = 512


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "text2image2m5.json")


def mesh(db_shards: int = 1):
    return make_mesh(1, db_shards, devices=jax.devices()[:db_shards])


def brute(db, q, k=K):
    """(-q.t, index) top-k by a direct float64 argsort."""
    s = -(q.astype(np.float64) @ db.astype(np.float64).T)
    idx = np.broadcast_to(np.arange(db.shape[0]), s.shape)
    order = np.lexsort((idx, s), axis=-1)[:, :k]
    return order, np.take_along_axis(s, order, axis=1)


def mix(n, n_q, dim=32, seed=2**31 + 31):
    rows = dict(CONFIG["rows"], clusters=64)
    db = datagen_mix.draw(rows, n, dim, seed, datagen.STREAM_ROWS)
    q = datagen_mix.draw(CONFIG["queries"], n_q, dim, seed,
                         datagen.STREAM_QUERIES, of=rows)
    return db, q


# --- the system against the plain reference ---------------------------------
@pytest.mark.parametrize("selector", ["pallas", "approx"])
@pytest.mark.parametrize("shards,n", [(1, 3000), (4, 4099)])
def test_certified_dot_equals_the_inner_product_oracle(shards, n, selector):
    db, q = mix(n, 48)
    want_i, want_s = reference_ip.oracle_topk(db, q, K)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="dot")
    kw = {"tile_n": TILE} if selector == "pallas" else {}
    d, i, stats = prog.search_certified(q, selector=selector, **kw)
    np.testing.assert_array_equal(i, want_i)
    # the stated bound: (dim + 1) * 2^-54 of |q|^2 + M
    bound = (db.shape[1] + 1) * 2.0 ** -54 * reference_ip.score_scale(db, q)
    assert (np.abs(d - want_s) <= bound[:, None]).all()
    assert stats["certified"] + stats["fallback_queries"] == q.shape[0]
    assert i.max() < n  # no padding row in any answer
    # what the caller passed is untouched, and norms spread
    assert db.shape[1] == 32 and prog.dim_in == 32
    norms = (db.astype(np.float64) ** 2).sum(-1)
    assert norms.max() > 4 * norms.min()


# --- the built case: near-ties under a row of far larger norm ---------------
DIM = 200
BIG = 1024.0  # the one long row: M = 2^20, so M * 2^-24 = 2^-4


def built_corpus(seed=31):
    """Rows and three queries.  Row 0 is ``BIG`` along axis 0 and sets M.
    Along axis 1 six PAIRS of rows have inner products 100 - p/4 and that
    plus a gap of 2^-12 ... 2^-9 with query 0 (far under M * 2^-24 =
    2^-4), the later row of a pair ahead or behind by the parity of p;
    every other row scores 50 or less, so the top-10 has a provable
    boundary and the near-ties are the host's tie-run repair to settle.
    Along axis 2 a ladder of 60 such rows 1/8 apart leaves query 1 no
    boundary inside the analysis window: the certificate fails and the
    repair settles it.  Query 2 is their sum with some noise.  The other
    coordinates are noise of very different sizes, so the rows' norms,
    and with them the rounding of the appended column, differ."""
    rng = np.random.default_rng([seed, 1])
    n = 1500
    db = (rng.standard_normal((n, DIM)) * rng.choice(
        [0.01, 1.0, 4.0], size=(n, 1))).astype(np.float32)
    db[:, :3] = 0.0
    db[0] = 0.0
    db[0, 0] = BIG
    gaps = 2.0 ** -rng.integers(9, 13, size=64)
    at = 10
    for p in range(6):
        first, second = at + 2 * p, at + 2 * p + 1
        base = 100.0 - p / 4
        db[first, 1] = base
        db[second, 1] = base + (gaps[p] if p % 2 else -gaps[p])
    for r in range(60):
        row = 100 + r
        db[row, 2] = 90.0 - r / 8 + (gaps[r % 64] if r % 3 == 0 else 0.0)
    rest = np.ones(n, bool)
    rest[[0, *range(at, at + 12), *range(100, 160)]] = False
    db[rest, 1:3] = rng.uniform(-50.0, 50.0, size=(int(rest.sum()), 2))
    q = np.zeros((3, DIM), np.float32)
    q[0, 1] = 1.0
    q[1, 2] = 1.0
    q[2, 1:3] = 1.0
    q[2, 3:] = 0.001 * rng.standard_normal(DIM - 3)
    return db, q


def test_the_built_case_is_what_it_says():
    db, q = built_corpus()
    m = float((db.astype(np.float64) ** 2).sum(-1).max())
    assert m == BIG ** 2
    _, want_s = brute(db, q, 12)
    gaps = np.diff(want_s[0])
    # six pairs, each closer than M * 2^-24, and in a known float64 order
    assert (gaps[0::2] > 0).all() and (gaps[0::2] < m * 2.0 ** -24).all()
    assert (gaps[1::2] > 0.2).all()
    norms = (db[10:22].astype(np.float64) ** 2).sum(-1)
    assert len(set(np.sqrt(m - norms).astype(np.float32))) > 6


@pytest.mark.parametrize("selector", ["pallas", "approx", "exact"])
@pytest.mark.parametrize("shards", [1, 4])
def test_near_ties_under_a_long_row_come_back_in_float64_order(
        shards, selector):
    """The parent's tree fails this: it ranked by the float64 difference
    of the AUGMENTED rows, whose appended column sqrt(M - |t|^2) is
    rounded to float32 (off by up to 2^-4 in the squared distance here),
    so pairs 2^-12 apart came back in the rounding's order, certified."""
    db, q = built_corpus()
    want_i, want_s = brute(db, q)
    ref_i, ref_s = reference_ip.oracle_topk(db, q, K)
    np.testing.assert_array_equal(ref_i, want_i)
    prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="dot")
    kw = {"tile_n": TILE} if selector == "pallas" else {}
    d, i, stats = prog.search_certified(q, selector=selector, **kw)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_allclose(d, want_s, rtol=0, atol=2.0 ** -30)
    if selector == "pallas":
        # query 0 is settled by the tie-run repair, query 1 by the
        # certificate's failing (no boundary inside the window)
        assert stats["rank_corrected_queries"] >= 1
        assert stats["fallback_queries"] >= 1


def test_ranking_by_the_augmented_rows_gets_the_built_case_wrong(
        monkeypatch):
    """The same call with the host ranking by the augmented difference
    (what the parent did): the answer is no longer the oracle's."""
    db, q = built_corpus()
    want_i, _ = brute(db, q)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="dot")
    _break_ranking(monkeypatch)
    _, i, _ = prog.search_certified(q, selector="pallas", tile_n=TILE)
    assert (i != want_i).any()


def test_equal_inner_products_are_broken_by_index():
    db, q = mix(2000, 16)
    db[40] *= 4.0 / np.linalg.norm(db[40])
    db[700:706] = db[40]  # seven copies of one long row
    db[1500] = 2.0 * db[41]  # and a row that beats its own source
    q[:8] = db[40] + 0.05 * q[:8]
    want_i, want_s = brute(db, q)
    assert (np.diff(want_s[:8], axis=1) == 0).any()
    for shards in (1, 4):
        prog = ShardedKNN(db, mesh=mesh(shards), k=K, metric="dot")
        d, i, _ = prog.search_certified(q, selector="pallas", tile_n=TILE)
        np.testing.assert_array_equal(i, want_i)
        np.testing.assert_array_equal(d[:, 1:] >= d[:, :-1], True)


def test_placement_takes_norms_in_blocks_and_makes_one_copy():
    db, _ = mix(20_000, 4, dim=16)
    out, shift, placed_max = sh._augment_dot(db)
    norms = (db.astype(np.float64) ** 2).sum(-1)
    assert shift == norms.max()  # blocks of 8,192 rows, the same float64 sums
    assert out.dtype == np.float32 and out.shape == (20_000, 17)
    np.testing.assert_array_equal(out[:, :16], db)
    np.testing.assert_array_equal(
        out[:, 16], np.sqrt(shift - norms).astype(np.float32))
    # the residual the certificate allows for, and the slack that bounds it
    resid = norms + out[:, 16].astype(np.float64) ** 2 - shift
    assert np.abs(resid).max() <= 2.0 ** -23 * shift * (1 + 2.0 ** -20)
    assert 2 * np.abs(resid).max() < sh.DOT_AUG_SLACK * shift
    assert placed_max == (norms + out[:, 16].astype(np.float64) ** 2).max()
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="dot")
    assert prog._db_norm_max() == placed_max and not prog._rows_lo_zero
    assert prog._pair_slack() == sh.DOT_AUG_SLACK * shift


# --- the final select's bin-merge at this cell's geometry, compiled ----------
@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described and not attached (the TPU's compiler
    is installed here): what interpret mode cannot refuse, it can."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows_n,m,geometry", [
    (2_500_000, 38, (5, 62, 2560)),     # text2image2m5: k=10
    (5_000_000, 128, (17, 36, 8704)),   # both BIGANN cells: k=100
    (10_000_000, 128, (17, 72, 8704)),
    (5_000_000, 38, (5, 123, 2560)),    # deep5m-knng: k=10
    (1_281_167, 48, (7, 23, 3584))])    # imagenet-knn768: k=20
def test_the_bin_merge_compiles_at_the_cells_widths(one_chip, rows_n, m,
                                                    geometry):
    """On the chip the merge kernel's blocks overran Mosaic's 16 MiB of
    scoped VMEM at this cell's 62 lane-rows a merge bin (PR 31, chip call
    59): the whole program compiled deviceless carries this kernel
    interpreted, so it is compiled here alone.  Since PR 52 the last
    group's block hangs over the arrays' end where the groups do not
    tile the width (4 lane-rows of 310 here, 3 of 615 and of 161 in the
    last two cases), and the compiled module holds no ``pad``."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    width = -(-rows_n // pk.TILE_N) * 2 * pk.BIN_W
    assert pk.select_merge_geometry(width, m) == geometry
    fn = jax.jit(lambda cd, ci: pk._select_merge(
        cd, ci, *geometry[:2], interpret=False))
    text = fn.lower(
        jax.ShapeDtypeStruct((4096, width), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096, width), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert "select_merge" in text and " pad(" not in text


@pytest.mark.parametrize("width,m", [
    (8_704, 128),     # both BIGANN cells and ssnpp2m5, after the bin-merge
    (15_872, 128),    # gist1m: the kernel's own candidates
    (2_560, 38)])     # text2image2m5, after the bin-merge
def test_the_final_select_stage_compiles_at_the_cells_widths(
        one_chip, monkeypatch, width, m):
    """The final top-(m+2) as one Pallas call (PR 35), compiled for a
    described v5e in the blocks ``final_select_geometry`` gives: Mosaic
    takes it at the scoped-VMEM limit the model asks for
    (``analysis.vmem.final_select_bytes`` plus an eighth) and refuses it
    under the buffers the model says the call declares, so the model
    neither lets through what cannot fit nor counts what is not there.
    (This file holds the described chip: the stage's own tests are
    tests/test_final_select.py.)"""
    import jax.numpy as jnp

    from knn_tpu.analysis import vmem
    from knn_tpu.ops import pallas_knn as pk

    block_q = pk.final_select_geometry(width, m)
    assert block_q == vmem.FINAL_SELECT_BLOCK_Q
    avals = (
        jax.ShapeDtypeStruct((4096, width), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((4096, width), jnp.int32, sharding=one_chip))

    def compile_stage():
        return jax.jit(lambda cd, ci: pk._select_final(
            cd, ci, m, block_q, interpret=False)).lower(*avals).compile()

    text = compile_stage().as_text()
    assert "%select_final" in text
    # nothing of it can be read as the range completion's work
    assert not [ln for ln in text.splitlines()
                if re.search(r"\bu32\[[0-9]+,", ln)]
    declared = vmem.final_select_bytes(block_q, width, m + 2)
    declared = declared["inputs_x2"] + declared["key_scratch"]
    monkeypatch.setattr(pk, "_final_select_vmem_limit",
                        lambda *a: declared // 2)
    jax.clear_caches()
    with pytest.raises(Exception, match="(?i)vmem"):
        compile_stage()


@pytest.mark.parametrize("dim,terms", [
    (128, "hh"),            # both BIGANN cells: byte rows, th alone
    (256, "hh"),            # ssnpp2m5
    (201, "hh+hl+lh")])     # text2image2m5: float rows, th and tl
def test_the_kernel_takes_resident_row_operands_at_the_cells_widths(
        one_chip, dim, terms):
    """The kernel launch handed the placement's row operands as
    arguments (PR 39), compiled for a described v5e at the cells' widths
    and two row tiles: Mosaic takes the bf16 halves as they arrive, and
    nothing outside the kernel casts or reduces an array of the rows'
    size (the interpreted tests cannot say what the compiler makes of
    operands that are parameters)."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    rows = 2 * pk.TILE_N - 100
    rows_p, dim_p = 2 * pk.TILE_N, -(-dim // pk.DIM_CHUNK) * pk.DIM_CHUNK

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    halves = 1 + ("hl" in terms)
    prepared = (*[aval((rows_p, dim_p), jnp.bfloat16)] * halves,
                aval((rows_p,), jnp.float32))
    text = pk._bin_candidates.lower(
        aval((4096, dim), jnp.float32), aval((rows, dim), jnp.float32),
        block_q=256, tile_n=pk.TILE_N, survivors=None,
        precision="bf16x3", interpret=False, terms=terms, dim_chunk=dim_p,
        db_prepared=prepared).compile().as_text()
    assert "%_bin_candidates" in text
    # (at two tiles an operand is small enough for the compiler to move
    # it to VMEM whole, an asynchronous copy: no cast and no reduction)
    made = [ln for ln in text.splitlines()
            if re.match(rf"\s*%\S+ = (bf16|f32)\[{rows_p}(,{dim_p})?\]", ln)
            and not re.search(r" (parameter|copy-start|copy-done)\(", ln)]
    assert made == []


def test_the_resident_low_half_is_a_rounding_the_compiler_may_not_skip(
        one_chip):
    """``row_operands`` compiled for a described v5e (PR 43): the rows'
    low half is taken against ``reduce-precision``, not against the
    cast's own round trip, which the TPU compiler keeps in float32
    inside one fusion (on the chip that low half read all zero, and the
    kernel's score was off by more than its tolerance at 1,536 unit
    columns).  Since PR 49 the in-call split is the same text
    (``_split_rows`` has one form); the round trip, spelled out here,
    still compiles to no rounding at all."""
    import functools

    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    rows = jax.ShapeDtypeStruct((pk.TILE_N, 1536), jnp.float32,
                                sharding=one_chip)
    text = jax.jit(functools.partial(
        pk.row_operands, tile_n=pk.TILE_N, with_lo=True)).lower(
        rows).compile().as_text()
    assert "reduce-precision(" in text
    in_call = jax.jit(lambda x: pk._split_rows(x, True)).lower(
        rows).compile().as_text()
    assert "reduce-precision(" in in_call

    def round_trip(x):
        th = x.astype(jnp.bfloat16)
        return th, (x - th.astype(jnp.float32)).astype(jnp.bfloat16)

    old = jax.jit(round_trip).lower(rows).compile().as_text()
    assert "reduce-precision(" not in old


# --- PR 40: the validity mask at yfcc2m5's shape, compiled ---------------------
CELL_ROWS, CELL_DIM, CELL_QUERIES = 2_500_000, 192, 4096


def test_the_masked_kernel_compiles_at_the_filter_cells_shape(one_chip):
    """The kernel with a batch's validity words as one more operand
    (``yfcc2m5.sweep_filter``: one 256-column chunk, resident row
    operands, 512 words a query a tile), for a described v5e: what
    Mosaic makes of the word blocks, interpret mode cannot say."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    rows_p = -(-CELL_ROWS // pk.TILE_N) * pk.TILE_N
    words = rows_p // pk.TILE_N * pk.valid_words_per_tile(pk.TILE_N)
    assert words == rows_p // 32 == 78_336

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pk._bin_candidates.lower(
        aval((CELL_QUERIES, CELL_DIM), jnp.float32),
        aval((CELL_ROWS, CELL_DIM), jnp.float32),
        block_q=256, tile_n=pk.TILE_N, survivors=None, precision="bf16x3",
        interpret=False, terms="hh", dim_chunk=256,
        db_prepared=(aval((rows_p, 256), jnp.bfloat16),
                     aval((rows_p,), jnp.float32)),
        valid_words=aval((CELL_QUERIES, words), jnp.int32),
    ).compile().as_text()
    assert "%_bin_candidates" in text


def test_the_mask_program_compiles_at_the_filter_cells_shape(one_chip):
    """``filter_mask`` (ops.tagfilter.mask_words: scalar-prefetched
    slots and list lengths, the listed ids as SMEM blocks, a dynamic
    trip count, a dynamic sublane row a bit) at the cell's shape and the
    rule's own list capacity."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk
    from knn_tpu.ops import tagfilter

    rows_p = -(-CELL_ROWS // pk.TILE_N) * pk.TILE_N
    cap = tagfilter.list_capacity(rows_p)
    assert (tagfilter.bitmap_min_rows(rows_p), cap) == (612, 640)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vocabulary, maps, ids = 200_386, 4_700, 12_400_000
    fn = jax.jit(lambda ft, slots, bitmaps, ptr, rows: tagfilter.mask_words(
        ft, slots, bitmaps, ptr, rows, tile_n=pk.TILE_N, list_cap=cap,
        interpret=False))
    text = fn.lower(
        aval((CELL_QUERIES, 2), jnp.int32), aval((vocabulary,), jnp.int32),
        aval((tagfilter.SLOT_TAGS + maps, -(-rows_p // 32 // 128 // 8) * 8,
              128), jnp.int32),
        aval((vocabulary + 1,), jnp.int32), aval((ids + cap,), jnp.int32),
    ).compile().as_text()
    assert "%filter_mask" in text


def test_the_masked_kernel_compiles_cut_by_rows_at_the_range_cells_shape(
        one_chip):
    """The masked kernel where the row tile is cut in four steps
    (``openai500k-intfilter.sweep_cos_filter``: 1,536 columns, both
    resident halves, a sub-batch's 1,024 queries): a step's block of the
    words is a quarter of a tile's, which the one-step filter cell never
    asks of Mosaic."""
    import jax.numpy as jnp

    from knn_tpu.ops import pallas_knn as pk

    rows, dim, queries = 500_000, 1536, 1024
    rows_p = -(-rows // pk.TILE_N) * pk.TILE_N
    words = rows_p // pk.TILE_N * pk.valid_words_per_tile(pk.TILE_N)
    block, steps = pk.row_blocking(
        dim, tile_n=pk.TILE_N, block_q=256, precision="bf16x3",
        kernel="tiled", terms="hh+hl+lh", survivors=None, masked=True)
    assert (block, steps, words) == (4096, 4, 31 * 512)

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = pk._bin_candidates.lower(
        aval((queries, dim), jnp.float32), aval((rows, dim), jnp.float32),
        block_q=256, tile_n=pk.TILE_N, survivors=None, precision="bf16x3",
        interpret=False, terms="hh+hl+lh", row_block=block,
        db_prepared=(aval((rows_p, dim), jnp.bfloat16),
                     aval((rows_p, dim), jnp.bfloat16),
                     aval((rows_p,), jnp.float32)),
        valid_words=aval((queries, words), jnp.int32),
    ).compile().as_text()
    assert "%_bin_candidates" in text


@pytest.mark.parametrize("queries", [1024, 64])
def test_the_range_maker_compiles_at_the_range_cells_shape(one_chip,
                                                           queries):
    """``range_mask`` (ops.tagfilter.range_words: a dynamic sublane
    offset a query sub-block, a sublane broadcast a bit) for a
    sub-batch's 1,024 queries and for the repair's block of 64, over
    the cell's 124 word rows."""
    import jax.numpy as jnp

    from knn_tpu.ops import tagfilter

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda b, a: tagfilter.range_words(
        b, a, interpret=False)).lower(
        aval((queries, 2), jnp.int32), aval((124 * 32, 128), jnp.int32),
    ).compile()
    assert "%range_mask" in compiled.as_text()
    # the words and nothing of their size beside them
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == queries * 124 * 128 * 4
    assert memory.temp_size_in_bytes < 2 * queries * 128 * 4 + (1 << 16)


def _users_of_the_rows(text, rows, width):
    """``(opcode, elements of its largest output, the line)`` of every
    instruction of a compiled module that takes the placed rows (the
    parameter ``f32[rows, width]`` of the entry computation) as an
    operand, and the layout the compiler gave that parameter."""
    text = text[text.index("\nENTRY "):]
    text = text[:text.index("\n}")]
    (name, layout), = re.findall(
        rf"(%[\w.-]+) = f32\[{rows},{width}\](\{{[^}}]*\}}) parameter\(",
        text)
    users = []
    for ln in text.splitlines():
        if not re.search(rf"[(, ]{re.escape(name)}[,)]", ln):
            continue
        shapes, opcode = re.search(r"= (.*?) ([a-z][\w-]*)\(", ln).groups()
        users.append((opcode, max(
            int(np.prod([int(x) for x in dims.split(",")]))
            for dims in re.findall(r"\[([\d,]+)\]", shapes)), ln.strip()))
    return layout, users


@pytest.mark.parametrize("program,rows,given", [
    ("certified", 2_500_000, 201),   # text2image2m5: 200 + the norm column
    ("reselect", 1_000_000, 960)])   # gist1m: the repair's exact re-select
def test_no_call_copies_the_placed_rows(one_chip, program, rows, given):
    """The regression guard the chip cannot be in tier-1 (PR 44): rows
    of 201 or 960 columns lie column-major on a v5e, and every program
    that reads them row-major starts with a ``copy`` of all of them
    (7.2 ms of a 116 ms batch at ``text2image2m5``, 12.2 and 2 x 6.1 at
    ``gist1m``, the ledger's PR 43 lines); placed in whole lane tiles
    (``analysis.widths.lane_tiled``, what ``ShardedKNN`` places at) the
    parameter is row-major and nothing writes an array of its size: the
    certified program's one reader is the rescore's gather, and the
    re-select's is its scan, which reads one ``train_tile`` window a
    step from the rows where they lie (a pad of the ROWS to whole tiles
    was a copy of all of them: 12.8 ms a batch at ``gist1m``, the
    ledger's PR 49 lines), so its temporaries are a tile's distances
    and no array of rows at all."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from knn_tpu.analysis.widths import lane_tiled
    from knn_tpu.ops import pallas_knn as pk
    from knn_tpu.parallel.mesh import DB_AXIS, QUERY_AXIS

    (device,) = one_chip.device_set
    m = Mesh(np.asarray([device]).reshape(1, 1), (QUERY_AXIS, DB_AXIS))

    def aval(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(m, P(*spec)))

    def compiled(width):
        if program == "reselect":
            prog = sh._knn_program(m, 256, "l2", "ring", rows, 131072, None,
                                   "exact", dcn_merge=None)
            tail, queries = (), 16
        else:
            rows_p = -(-rows // pk.TILE_N) * pk.TILE_N
            prog = sh._pallas_certified_program(
                m, K + 28, K, "ring", pk.TILE_N, "bf16x3", n_train=rows,
                interpret=False, augmented=True, include_distances=False,
                row_block=pk.TILE_N, resident_parts=2)
            tail = (aval((), jnp.float32),
                    *[aval((rows_p, 256), jnp.bfloat16, DB_AXIS)] * 2,
                    aval((rows_p,), jnp.float32, DB_AXIS),
                    aval((), jnp.float32))
            queries = 4096
        return prog.lower(aval((queries, width), jnp.float32, QUERY_AXIS),
                          aval((rows, width), jnp.float32, DB_AXIS),
                          *tail).compile()

    # as given: column-major, and a copy of all of it before anything
    # the certified program does (the re-select's scan reads either
    # layout in place, and hands its loop the parameter in a tuple)
    layout, users = _users_of_the_rows(compiled(given).as_text(), rows, given)
    assert layout.startswith("{0,1")
    assert [op for op, size, _ in users if size >= rows * given] == [
        "copy" if program == "certified" else "tuple"]
    # as placed
    width = lane_tiled(given)
    placed = compiled(width)
    layout, users = _users_of_the_rows(placed.as_text(), rows, width)
    assert layout.startswith("{1,0")
    assert not [ln for op, _, ln in users if op.startswith("copy")]
    assert "pad" not in [op for op, _, _ in users]
    if program == "certified":
        assert not [ln for op, size, ln in users if size >= rows * width]
        assert [op for op, _, _ in users] == ["fusion"]  # the gather
    else:
        # the rows enter the scan's loop as they are, and no step sets a
        # window of them aside: 16 queries' distances to a tile, not the
        # 537 MB of a tile's rows (nor the 4.3 GB of all, padded)
        assert [op for op, _, _ in users] == ["tuple"]
        assert (placed.memory_analysis().temp_size_in_bytes
                < 131072 * width * 4 // 8)


# --- the plain reference ------------------------------------------------------
def test_the_oracle_is_a_float64_argsort():
    db, q = mix(70_000, 24, dim=24)  # two blocks of rows
    want_i, want_s = brute(db, q)
    got_i, got_s = reference_ip.oracle_topk(db, q, K)
    np.testing.assert_array_equal(got_i, want_i)
    np.testing.assert_allclose(got_s, want_s, rtol=0, atol=1e-14)
    cmp = reference_ip.compare(got_i, got_s, want_i, want_s, db, q)
    assert cmp["mismatched_rows"] == 0 and cmp["recall"] == 1.0
    assert cmp["score_err_max"] <= 2.0 ** -50


def test_compare_on_negative_scores():
    db, q = mix(3000, 8)
    want_i, want_s = brute(db, q)
    assert (want_s < 0).all()  # where reference.compare's division fails
    scale = reference_ip.score_scale(db, q)
    np.testing.assert_allclose(
        scale, (q.astype(np.float64) ** 2).sum(-1)
        + (db.astype(np.float64) ** 2).sum(-1).max(), rtol=1e-15)
    off = want_s.copy()
    off[3, 4] += 1e-3 * scale[3]
    cmp = reference_ip.compare(want_i, off, want_i, want_s, db, q)
    assert cmp["mismatched_rows"] == 0
    assert cmp["score_err_max"] == pytest.approx(1e-3, rel=1e-6)
    swapped = want_i.copy()
    swapped[5, [0, 1]] = swapped[5, [1, 0]]
    cmp = reference_ip.compare(swapped, want_s, want_i, want_s, db, q)
    assert (cmp["mismatched_rows"], cmp["score_err_max"]) == (1, 0.0)
    assert cmp["recall"] == 1.0
    bad = want_s.copy()
    bad[0, 0] = np.nan
    assert reference_ip.compare(want_i, bad, want_i, want_s, db, q)[
        "score_err_max"] == np.inf
    with pytest.raises(ValueError, match="shapes"):
        reference_ip.compare(want_i[:, :5], want_s, want_i, want_s, db, q)


@pytest.mark.parametrize("precision,breaks", [
    ("f32", {"score_err_max"}),
    ("bf16", {"score_err_max", "mismatched_rows"})])
def test_the_controls_fail_the_comparison(precision, breaks):
    """The reference in the program's place, one precision down, under
    the configuration's own limits: float32 is told from the contract by
    its scores (on this corpus its ranking of a few dozen queries is
    float64's), bfloat16 by both."""
    db, q = mix(30_000, 32, dim=200)
    want_i, want_s = reference_ip.oracle_topk(db, q, K)
    got_i, got_s = reference_ip.lowprec_topk(db, q, K, precision)
    cmp = reference_ip.compare(got_i, got_s, want_i, want_s, db, q)
    checks = reference.Checks()
    for name, limit in CONFIG["limits"].items():
        checks.add(name, cmp[name], limit)
    assert checks.correct is False
    assert {r["check"] for r in checks.rows if not r["ok"]} == breaks
    with pytest.raises(ValueError, match="precision"):
        reference_ip.lowprec_topk(db, q, K, "int4")


# --- the generator -------------------------------------------------------------
def test_datagen_mix_gives_the_same_values_whatever_the_thread_count(
        monkeypatch):
    n, dim, seed = 3 * datagen.CHUNK_ROWS + 17, 8, 2**31 + 99
    rows = dict(CONFIG["rows"], clusters=32)
    many = (datagen_mix.draw(rows, n, dim, seed, datagen.STREAM_ROWS),
            datagen_mix.draw(CONFIG["queries"], n, dim, seed,
                             datagen.STREAM_QUERIES, of=rows))
    monkeypatch.setattr(datagen_mix.os, "cpu_count", lambda: 1)
    one = (datagen_mix.draw(rows, n, dim, seed, datagen.STREAM_ROWS),
           datagen_mix.draw(CONFIG["queries"], n, dim, seed,
                            datagen.STREAM_QUERIES, of=rows))
    for a, b in zip(many, one):
        assert a.dtype == np.float32 and a.shape == (n, dim)
        np.testing.assert_array_equal(a, b)
    other = datagen_mix.draw(rows, n, dim, seed + 1, datagen.STREAM_ROWS)
    assert not np.array_equal(other, many[0])
    # any other distribution is datagen's own
    np.testing.assert_array_equal(
        datagen_mix.draw({"dist": "uint8"}, 100, dim, seed, 0),
        datagen.draw({"dist": "uint8"}, 100, dim, seed, 0))
    with pytest.raises(ValueError, match="offset_mix"):
        datagen_mix.draw(CONFIG["queries"], 10, dim, seed, 1)


def test_datagen_mix_draws_what_the_configuration_says():
    n, dim, seed = 200_000, 64, 5
    spec = dict(CONFIG["rows"], clusters=256)
    db = datagen_mix.draw(spec, n, dim, seed, datagen.STREAM_ROWS)
    cen = datagen_mix.centres(seed, 256, dim)
    unit = cen / np.linalg.norm(cen, axis=1, keepdims=True)
    nearest = np.argmax(
        (db[:20_000] / np.linalg.norm(db[:20_000], axis=1, keepdims=True))
        @ unit.T, axis=1)
    sizes = np.bincount(nearest, minlength=256)
    # Zipf(1): the first cluster holds 1 / H_256 = 16% of the rows, the
    # last a 256th of that; row order says nothing of the cluster
    assert 0.12 < sizes[0] / 20_000 < 0.21 and sizes[0] > 20 * sizes[200:].mean()
    assert abs(np.corrcoef(np.arange(20_000), nearest)[0, 1]) < 0.05
    norms = np.linalg.norm(db, axis=1)
    assert 0.15 < np.std(np.log(norms)) < 0.25  # log-normal(0, 0.2) scales
    q = datagen_mix.draw(CONFIG["queries"], 4096, dim, seed,
                         datagen.STREAM_QUERIES, of=spec)
    # queries choose a cluster uniformly and sit off the rows' set: less
    # of a query than of a row lies along its nearest centre
    q_near = np.bincount(np.argmax(q @ unit.T, axis=1), minlength=256)
    assert q_near.max() < 4 * q_near.mean()
    cos_q = np.max((q / np.linalg.norm(q, axis=1, keepdims=True)) @ unit.T, 1)
    cos_t = np.max((db[:4096] / norms[:4096, None]) @ unit.T, axis=1)
    assert np.median(cos_q) < np.median(cos_t) - 0.2


# --- the span, the counter and the events -----------------------------------
@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


def series(name):
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in obs.snapshot().get(name, {"series": []})["series"]}


def test_the_metric_rides_the_call_and_its_own_span(fresh_registry):
    db, q = mix(3000, 24)
    prog = ShardedKNN(db, mesh=mesh(), k=K, metric="dot")
    (placed,) = [e for e in obs.get_event_log().recent()
                 if e.get("name") == "placement.dot_augment"]
    assert (placed["rows"], placed["dim"]) == (3000, 32)
    assert placed["shift"] == prog._dot_shift and placed["seconds"] > 0
    prog.search_certified(q, selector="pallas", tile_n=TILE, batch_size=8)
    prog.search_certified(q, selector="pallas", tile_n=TILE,
                          return_distances=False)
    ShardedKNN(db, mesh=mesh(), k=K).search_certified(
        q, selector="pallas", tile_n=TILE)
    assert series(mn.CERTIFIED_METRIC_QUERIES) == {
        (("metric", "dot"),): 48.0, (("metric", "l2"),): 24.0}
    spans = [e for e in obs.get_event_log().recent() if e.get("span")]
    calls = [e for e in spans if e["span"] == "certified.call"]
    assert [c["metric"] for c in calls] == ["dot", "dot", "l2"]
    # one metric_map span a dot call, child of the call, both sides on it
    maps = [e for e in spans if e["span"] == "certified.metric_map"]
    assert [m["trace_id"] for m in maps] == [c["trace_id"] for c in calls[:2]]
    for m in maps:
        assert (m["parent"], m["metric"]) == ("certified.call", "dot")
        assert m["dur_s"] == pytest.approx(m["before_s"] + m["after_s"],
                                           abs=2e-6)
    assert maps[0]["after_s"] > 0 and maps[1]["after_s"] == 0
    hist = series(mn.SPAN_SECONDS)[(("span", "certified.metric_map"),)]
    assert hist["count"] == 2
    # the device's distance block is not fetched for an inner-product call
    # (a call's record: the sum over its sub-batches, 3 of 8 rows here)
    d2h = [e["d2h_bytes"] for e in spans if e["span"] == "certified.d2h"]
    assert len(d2h) == 3 and d2h[0] < d2h[-1]  # no distance columns


# --- the cell through the benchmark's harness --------------------------------
BENCH = tinyroot.load_bench()
TINY_TRAFFIC = tinyroot.TINY_TRAFFIC["sweep"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tinyroot``'s copy, with this cell's traffic file cut as it cuts
    ``sweep``'s (it shrinks by file name and knows two)."""
    root = tinyroot.make(str(tmp_path_factory.mktemp("bench_t2i")))
    path = os.path.join(root, "benchmark", "traffic", "sweep_ip.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(TINY_TRAFFIC)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


@pytest.fixture
def cpu_memory_reading(monkeypatch):
    # the CPU backend reports no memory; the validator refuses 0
    real = harness.device_info
    monkeypatch.setattr(harness, "device_info",
                        lambda resident: real(resident or 1))


def run_cell(root, traced: bool, seed=2**31 + 31) -> dict:
    lines = []
    parsed = harness.run_cell(root, CELL, seed, 1.5, traced,
                              time.perf_counter(), emit=lines.append)
    assert lastline.validate(lines[0], BENCH, CELL, traced) == parsed
    return parsed


STAGES = {"dispatch_ms", "device_wait_ms", "d2h_ms", "unpack_ms",
          "rank_correct_ms", "repair_ms"}


@pytest.mark.parametrize("traced", [False, True])
def test_the_cell_runs_through_the_harness(root, cpu_memory_reading, traced):
    cell = harness.load_cell(root, CELL)
    assert cell.traffic["kind"] == "sweep_ip" and cell.chips == 1
    out = run_cell(root, traced)
    assert out["correct"] is True and out["failed"] == 0
    want = {m["name"] for m in lastline.required_metrics(BENCH, CELL, traced)}
    assert set(out["metrics"]) == want
    if traced:
        # these are there; a later PR may list more for the cell
        assert want >= STAGES | {
            "kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
            "rank_corrected_pct", "idle_pct.sweep", "metric_map_ms"}
        for name in STAGES | {"metric_map_ms"}:
            assert out["metrics"][name]["value"] > 0, name
    else:
        assert want == {"sweep_qps", "setup_s"}


def _break_ranking(monkeypatch):
    """The host ranks by the float64 difference of the augmented float32
    rows, as the parent did, wherever it ranks."""
    real_members, real_refine = refine._score_members, refine.refine_exact

    def members(db_np, queries_np, cand, rows, metric, out, norms=None):
        return real_members(db_np, queries_np, cand, rows, "l2", out)

    def refine_l2(db, queries, cand_idx, k, metric="l2", norms=None):
        return real_refine(db, queries, cand_idx, k, "l2")

    from knn_tpu.ops import certified

    monkeypatch.setattr(refine, "_score_members", members)
    monkeypatch.setattr(refine, "refine_exact", refine_l2)
    monkeypatch.setattr(certified, "refine_exact", refine_l2)
    monkeypatch.setattr(
        certified, "host_exact_knn",
        lambda db, q, k, metric="l2", norms=None,
        _real=certified.host_exact_knn:
        _real(db, q, k))


def _built_draw(monkeypatch):
    """The built corpus in the generator's place, its queries tiled over
    the pool."""
    db, q = built_corpus()

    def draw(spec, n, dim, seed, stream, of=None):
        if stream == datagen.STREAM_ROWS:
            return db
        return np.resize(q, (n, dim)).copy()

    monkeypatch.setattr(datagen_mix, "draw", draw)


def test_the_built_case_through_the_harness_is_correct(
        root, cpu_memory_reading, monkeypatch):
    _built_draw(monkeypatch)
    assert run_cell(root, False)["correct"] is True


def test_a_repair_that_ranks_by_the_augmented_rows_is_not_correct(
        root, cpu_memory_reading, monkeypatch):
    """The broken timed path: every answer's near-ties come back in the
    appended column's rounding order, and the comparison has to say so."""
    _built_draw(monkeypatch)
    _break_ranking(monkeypatch)
    assert run_cell(root, False)["correct"] is False


def test_scores_mapped_back_from_the_float32_distance_are_not_correct(
        root, cpu_memory_reading, monkeypatch):
    """The parent's score map in the program's place: (d - |q|^2 - M) / 2
    of a float32 squared distance in the augmented space."""
    def back_map(db_np, queries_np, idx, metric):
        rows = db_np[idx].astype(np.float32)
        d32 = ((queries_np[:, None, :] - rows) ** 2).sum(-1, dtype=np.float32)
        m = float((db_np[:, :-1].astype(np.float64) ** 2).sum(-1).max())
        qn = (queries_np.astype(np.float64) ** 2).sum(-1)
        return (d32.astype(np.float64) - qn[:, None] - m) / 2

    monkeypatch.setattr(refine, "exact_scores", back_map)
    out = run_cell(root, False)
    assert out["correct"] is False


# --- the cell's data files ---------------------------------------------------
def test_the_configuration_is_the_source_cut_in_rows_only():
    bench = _json("BENCHMARK.json")
    (entry,) = [c for c in bench["configs"] if c["name"] == "text2image2m5"]
    assert entry["file"] == "benchmark/configs/text2image2m5.json"
    assert entry["reduced"] == ["rows_n"] == list(
        CONFIG["reduced_from_source"])
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert (CONFIG["rows_n"], CONFIG["dim"], CONFIG["metric"],
            CONFIG["k"]) == (2_500_000, 200, "dot", 10)
    assert CONFIG["limits"] == {"mismatched_rows": 0,
                                "score_err_max": 2.0 ** -40}
    # the limit sits between the guarantee's bound and a float32 score
    assert (CONFIG["dim"] + 1) * 2.0 ** -54 * 64 < 2.0 ** -40 < 1e-8 / 64
    assert CONFIG["require"] == _json(
        "benchmark", "configs", "bigann5m.json")["require"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "text2image2m5", "sweep_ip", 1)
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == [
        "bigann20m-x4.sweep"]
    traffic = _json("benchmark", "traffic", "sweep_ip.json")
    sweep = _json("benchmark", "traffic", "sweep.json")
    assert {k: traffic[k] for k in sweep if k not in ("kind", "what")} == {
        k: sweep[k] for k in sweep if k not in ("kind", "what")}
    listed = {m["name"]: m for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert set(listed) >= STAGES | {
        "kernel_ms", "pallas_knn_roofline", "tail_ms", "fallback_pct",
        "rank_corrected_pct", "idle_pct.sweep", "metric_map_ms"}
    for name in STAGES | {"metric_map_ms"}:
        # the stage metrics are shared with the range cell since PR 34
        # (other cells may join them); the metric's own is read here
        assert set(listed[name]["workloads"]) >= {CELL} | (
            {"ssnpp2m5.sweep_range"} if name in STAGES else set())
        assert listed[name]["moves"] == "sweep_qps"
    stages = {e["name"]: e for e in _json(
        "benchmark", "tests", "data", "sweep_stages_cell.json")["per_layer"]}
    for name in STAGES:
        assert {k: v for k, v in listed[name].items() if k != "workloads"} \
            == {k: v for k, v in stages[name].items() if k != "workloads"}
    (qps,) = [m for m in bench["end_to_end"] if m["name"] == "sweep_qps"]
    assert CELL in qps["workloads"]
