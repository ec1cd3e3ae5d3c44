"""The exact final select as one Pallas stage
(``ops.pallas_knn.final_select_geometry``, ``_select_final``): the
top-(m+2) and the index gather after it, where the shape is one the
stage was timed at.

- the stage, interpreted, against ``lax.top_k(-cd, m + 2)`` and
  ``take_along_axis``: the selected SET and the (m+2)-th score bit for
  bit, at the widths and depths the benchmark's cells have and on the
  inputs that break a careless select (ties across lane-rows and merge
  groups, both zeros, +inf padding, fewer than m+2 finite scores, a lane
  crowded past the compaction's slots, a batch off the block grid);
- ``local_select_rescore`` with the stage against the same function
  with XLA's ops in its place: all three outputs, bit for bit;
- engagement from shapes alone;
- what a certified call says ran (event, stats, counter).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from knn_tpu import obs
from knn_tpu.analysis import vmem
from knn_tpu.obs import names as mn
from knn_tpu.ops import pallas_knn as pk
from knn_tpu.parallel import ShardedKNN, make_mesh

I32MAX = np.iinfo(np.int32).max
N_ROWS = 5_000_000


@functools.lru_cache(maxsize=None)
def xla_select(m: int):
    """What the stage replaces, as the parent's program has it."""
    def select(cd, ci):
        neg, sel = lax.top_k(-cd, m + 2)
        return (jnp.take_along_axis(ci, sel, axis=-1)[:, : m + 1],
                (-neg)[:, m + 1])

    return jax.jit(select)


@functools.lru_cache(maxsize=None)
def stage(m: int, block_q: int):
    return jax.jit(functools.partial(
        pk._select_final, m=m, block_q=block_q, interpret=True))


def candidates(n_q: int, width: int, seed, span: int = 1 << 23):
    """Whole-number scores drawn from ``span`` values (a byte corpus's
    kernel scores are whole numbers: ties are its daily bread), every
    row index at most once a query."""
    rng = np.random.default_rng([35, width, *np.atleast_1d(seed)])
    cd = rng.integers(0, span, (n_q, width)).astype(np.float32)
    ci = (rng.integers(0, N_ROWS // width, (n_q, width)) * width
          + rng.permuted(np.broadcast_to(np.arange(width), (n_q, width)),
                         axis=1)).astype(np.int32)
    return cd, ci


def pad(cd, ci, where):
    cd[where] = np.inf
    ci[where] = I32MAX


def assert_same_select(cd, ci, m, block_q=vmem.FINAL_SELECT_BLOCK_Q):
    """The stage's set (in any order) and exclusion value are XLA's."""
    cd, ci = jnp.asarray(cd), jnp.asarray(ci)
    got_i, got_e = (np.asarray(x) for x in stage(m, block_q)(cd, ci))
    want_i, want_e = (np.asarray(x) for x in xla_select(m)(cd, ci))
    assert got_i.shape == want_i.shape == (cd.shape[0], m + 1)
    np.testing.assert_array_equal(np.sort(got_i, axis=1),
                                  np.sort(want_i, axis=1))
    # bit for bit: -0 and +0 are different exclusion values
    np.testing.assert_array_equal(got_e.view(np.int32),
                                  want_e.view(np.int32))


# the cells' final selects: both BIGANN cells and ssnpp2m5 (merged),
# gist1m (the kernel's own candidates), text2image2m5 (merged, k = 10)
CELL_SHAPES = ((8_704, 128), (15_872, 128), (2_560, 38))


@pytest.mark.parametrize("span", (1 << 23, 3_000),
                         ids=("spread", "tied"))
@pytest.mark.parametrize("width,m", CELL_SHAPES + ((2_560, 128),
                                                   (8_704, 38)))
def test_the_stage_selects_what_top_k_selects(width, m, span):
    """13 queries in blocks of 8: the last block is five rows deep.  At
    3,000 distinct scores every query has ties at its (m+2)-th."""
    cd, ci = candidates(13, width, m, span)
    assert_same_select(cd, ci, m, block_q=8)


def test_a_batch_off_the_block_grid_at_the_default_block():
    cd, ci = candidates(vmem.FINAL_SELECT_BLOCK_Q + 3, 2_560, 0)
    assert_same_select(cd, ci, 38)


@pytest.mark.parametrize("width,m", CELL_SHAPES)
def test_equal_scores_take_the_earlier_column(width, m):
    """Runs of one score that straddle lane-rows and, on a merged array,
    merge groups (512 columns each): the select keeps the earlier
    columns of the run, to the column."""
    cd, ci = candidates(8, width, 1)
    cd += 10.0
    for r, (start, length) in enumerate((
            (100, m + 40),        # the whole select inside one run
            (500, 30),            # across the first merge group's end
            (width - 20, 20),     # the last columns
            (127, 2), (0, 1))):
        cd[r, start:start + length] = 5.0
        cd[r, (start + 3 * length) % width] = 1.0   # one clear winner
    # equal everywhere: the first m+1 columns are the answer
    cd[5] = 7.0
    # two values, the smaller in every other lane-row
    cd[6] = 9.0
    cd[6].reshape(-1, pk.BIN_W)[::2] = 8.0
    assert_same_select(cd, ci, m, block_q=8)
    got_i, _ = stage(m, 8)(jnp.asarray(cd), jnp.asarray(ci))
    np.testing.assert_array_equal(np.sort(np.asarray(got_i)[5]),
                                  np.sort(ci[5, : m + 1]))


@pytest.mark.parametrize("width,m", CELL_SHAPES)
def test_both_zeros_are_two_scores(width, m):
    """``lax.top_k`` orders by the floats' total order: -0 before +0.
    The (m+2)-th score is one or the other, to the bit."""
    cd, ci = candidates(8, width, 2)
    cd += 1.0
    for r in range(8):
        zeros = np.random.default_rng(r).permutation(width)[: m + 30]
        cd[r, zeros] = 0.0
        cd[r, zeros[: (m + 30) * r // 8]] = -0.0
    assert_same_select(cd, ci, m, block_q=8)


@pytest.mark.parametrize("width,m", CELL_SHAPES)
def test_padding_is_taken_only_when_the_finite_run_out(width, m):
    """+inf with the sentinel index, as the kernel and the bin-merge pad:
    a query with m+2 finite scores or more never selects one; with fewer
    it selects sentinels and its exclusion value is +inf."""
    cd, ci = candidates(8, width, 3)
    rng = np.random.default_rng(3)
    finite = (width, m + 2, m + 1, m, 1, 0, width // 2, m + 3)
    for r, n in enumerate(finite):
        pad(cd[r], ci[r], rng.permutation(width)[n:])
    assert_same_select(cd, ci, m, block_q=8)
    got_i, got_e = (np.asarray(x) for x in stage(m, 8)(
        jnp.asarray(cd), jnp.asarray(ci)))
    for r, n in enumerate(finite):
        assert (got_i[r] == I32MAX).sum() == max(0, m + 1 - n)
        assert np.isinf(got_e[r]) == (n < m + 2)


@pytest.mark.parametrize("crowd", (9, 17, 68))
def test_a_crowded_lane_takes_more_passes(crowd):
    """More of a query's best in one lane than the compaction's slots
    (rows 128 apart share a kernel lane): the pass runs again above the
    last index the lane kept, and nothing is lost or written twice."""
    width, m = 8_704, 128
    assert crowd > pk.FINAL_SELECT_SLOTS
    cd, ci = candidates(8, width, crowd)
    cd += 100.0
    for r in range(8):
        lane = 11 * r
        cd[r, lane::pk.BIN_W][:crowd] = np.arange(crowd, 0, -1)
    assert_same_select(cd, ci, m, block_q=8)


# --- the whole of stage 2, against itself with XLA's select ------------------
@functools.lru_cache(maxsize=None)
def xla_stage():
    """``local_select_rescore`` as it is where the rule refuses: the same
    function traced with the geometry helper answering None."""
    def fn(q, t, cd, ci, bounds, m):
        real = pk.final_select_geometry
        pk.final_select_geometry = lambda width, m: None
        try:
            return pk.local_select_rescore.__wrapped__(
                q, t, cd, ci, bounds, m)
        finally:
            pk.final_select_geometry = real

    return jax.jit(fn, static_argnames=("m",))


@pytest.mark.parametrize("width,m,merged", (
    (20_224, 128, True),      # 79 tiles of 256 rows: merged to 8,704
    (15_872, 128, False),     # gist1m: the kernel's candidates as they are
    (1_024, 38, False)))
def test_stage_two_is_bit_equal_with_either_select(width, m, merged):
    """``d32``, ``lidx`` and ``lb`` leave ``local_select_rescore`` the
    same with the Pallas stage as with ``lax.top_k`` and the gather."""
    n_q, rows, dim = 11, 80_000, 8
    rng = np.random.default_rng([35, width])
    cd = rng.integers(0, 50_000, (n_q, width)).astype(np.float32)
    ci = np.stack([rng.permutation(rows + 200)[:width]
                   for _ in range(n_q)]).astype(np.int32)   # some >= rows
    pad(cd, ci, rng.random((n_q, width)) < 0.05)
    bounds = rng.integers(0, 100_000, (n_q, width // 2)).astype(np.float32)
    q = rng.integers(0, 256, (n_q, dim)).astype(np.float32)
    t = rng.integers(0, 256, (rows, dim)).astype(np.float32)
    assert (pk.select_merge_geometry(width, m) is not None) == merged
    got = pk.local_select_rescore(q, t, cd, ci, bounds, m)
    want = xla_stage()(q, t, cd, ci, bounds, m)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --- engagement, from shapes alone -------------------------------------------
def equations(jaxpr):
    """Every equation under a jaxpr, outermost first; a ``pallas_call``
    is one equation (its kernel is not entered)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def traced_selects(width: int, m: int, **kw):
    """Names of what could be the top-(m+2) in the traced stage, in
    order; nothing is computed."""
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        functools.partial(pk.local_select_rescore, m=m, **kw))(
        sds((64, 16), jnp.float32), sds((1000, 16), jnp.float32),
        sds((64, width), jnp.float32), sds((64, width), jnp.int32),
        sds((64, width // 2), jnp.float32))
    return [eqn.params["name"] if eqn.primitive.name == "pallas_call"
            else eqn.primitive.name for eqn in equations(jaxpr.jaxpr)
            if eqn.primitive.name in ("pallas_call", "top_k", "approx_top_k")]


@pytest.mark.parametrize("width,m", CELL_SHAPES)
def test_the_cells_shapes_engage(width, m):
    assert pk.final_select_geometry(width, m) == vmem.FINAL_SELECT_BLOCK_Q
    assert traced_selects(width, m) == ["select_final"]


def kernel_equations(width: int, m: int) -> int:
    """Equations in the stage's traced kernel body, nested loops'
    bodies included."""
    jaxpr = jax.make_jaxpr(functools.partial(
        pk._select_final, m=m, block_q=64, interpret=False))(
        jax.ShapeDtypeStruct((4096, width), jnp.float32),
        jax.ShapeDtypeStruct((4096, width), jnp.int32))
    call, = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return sum(1 for _ in equations(call.params["jaxpr"]))


def test_the_trace_does_not_grow_with_the_width():
    """The loops over the lane-rows are in the program, not unrolled in
    its trace: every process's first call pays for the trace and the
    lowering, and `setup_s` is bounded (root PERF.md, PR 35: the same
    kernel with its 68 lane-rows unrolled in Python cost 12 s of a
    17.5 s set-up on the chip's host)."""
    narrow, wide = kernel_equations(2_560, 128), kernel_equations(15_872, 128)
    assert narrow == wide < 1_000


def test_a_merged_bigann_shard_runs_both_pallas_stages():
    assert traced_selects(78_336, 128) == ["select_merge", "select_final"]


@pytest.mark.parametrize("width,m,why", (
    # R1's k = 2,048 selection: m+2 = 2,078 slots, 260 merge groups
    (260 * 512, 2_076, "k = 2,048"),
    (15_872, 2_076, "k = 2,048, unmerged"),
    (16_384, 128, "(m+2) x width over what was timed"),
    (8_704, 255, "m+2 over two vregs of output lanes"),
    (8_704 + 64, 128, "off the lane grid")))
def test_other_shapes_keep_xlas_select(width, m, why):
    assert pk.final_select_geometry(width, m) is None, why
    if width % pk.BIN_W == 0:
        assert traced_selects(width, m)[-1] == "top_k", why


def test_the_approximate_final_select_is_not_touched():
    assert traced_selects(8_704, 128, final_select="approx") == [
        "approx_top_k"]


def test_the_bound_is_the_largest_shape_timed():
    assert 15_872 * 130 <= pk.FINAL_SELECT_MAX_WORK < 16_384 * 130
    assert pk.final_select_geometry(15_872, 128) is not None


@pytest.mark.parametrize("width,keep,budget_mib,want", (
    (8_704, 130, 128, vmem.FINAL_SELECT_BLOCK_Q),
    (8_704, 130, 8, 32), (8_704, 130, 4, 16), (8_704, 130, 2, 8),
    (8_704, 130, 1, None),
    (699_008, 3, 128, 8),          # the widest the work bound admits
    (699_008, 3, 64, None)))
def test_the_vmem_model_sizes_the_block(width, keep, budget_mib, want):
    assert vmem.final_select_block_q(
        width, keep, budget_mib * vmem.MIB) == want
    if want is not None:
        need = sum(vmem.final_select_bytes(want, width, keep).values())
        assert need + need // 8 <= budget_mib * vmem.MIB
        assert need > 5 * want * width * 4   # four input buffers, one scratch


# --- what a certified call says ran ------------------------------------------
def stage_batches():
    series = obs.snapshot().get(
        mn.FINAL_SELECT_CALLS, {"series": []})["series"]
    return {stage: sum(s["value"] for s in series
                       if s["labels"] == {"stage": stage})
            for stage in ("pallas", "xla")}


@pytest.mark.parametrize("final_select,k,want", (
    ("exact", 5, "pallas"), ("approx", 5, "xla"),
    ("exact", 300, "xla")))        # m+2 = 330: past two output vregs
def test_the_call_says_which_stage_ran(final_select, k, want):
    rng = np.random.default_rng(35)
    db = rng.integers(0, 256, (3000, 16)).astype(np.float32)
    q = rng.integers(0, 256, (9, 16)).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_mesh(1, 1, devices=jax.devices()[:1]),
                      k=k)
    before = stage_batches()
    d, i, stats = prog.search_certified(
        q, selector="pallas", tile_n=256, batch_size=4,
        final_select=final_select)
    d64 = ((q[:, None, :].astype(np.float64) - db[None].astype(np.float64))
           ** 2).sum(-1)
    np.testing.assert_array_equal(i, np.lexsort(
        (np.broadcast_to(np.arange(db.shape[0]), d64.shape), d64),
        axis=1)[:, :k])
    assert stats["final_select_stage"] == want
    assert stats["pallas_knobs"]["final_select_stage"] == want
    call = [e for e in obs.get_event_log().recent()
            if e.get("span") == "certified.call"][-1]
    assert call["final_select_stage"] == want
    after = stage_batches()
    other = "xla" if want == "pallas" else "pallas"
    assert after[want] - before[want] == 3       # 9 queries in batches of 4
    assert after[other] == before[other]
