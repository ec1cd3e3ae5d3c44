"""The final select's bin-merge (``ops.pallas_knn.select_merge_geometry``,
``_select_merge``): between the kernel's candidates and the top-(m+2),
where the candidate array is at least twice the merged width.

- the merged select on synthetic ``cd`` / ``ci`` / ``bounds`` handed
  straight to ``local_select_rescore`` (no kernel), against the select
  over the candidates as they are;
- engagement from shapes alone (nothing computed);
- the merge reading ``cd`` / ``ci`` where the kernel wrote them (PR 52):
  a last group short of its grid, masked by index, against the arrays
  padded by hand to the group grid, and what the call reports of it
  (``select_merge_short``);
- an engaging corpus end to end through ``ShardedKNN.search_certified``
  on one CPU device and on a (1, 4) mesh, the kernel interpreted, with a
  collision built in that only the repair can answer.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import program_digest  # noqa: E402  (tests/)

from knn_tpu import obs  # noqa: E402
from knn_tpu.obs import names as mn  # noqa: E402
from knn_tpu.ops import pallas_knn as pk  # noqa: E402
from knn_tpu.parallel import ShardedKNN, make_mesh  # noqa: E402

I32MAX = np.iinfo(np.int32).max
M = 128                       # k=100 + margin 28: m + 2 = 130 slots
GROUPS = 17                   # ceil(130 / 8)
MERGED = GROUPS * pk.SELECT_MERGE_SURVIVORS * pk.BIN_W   # 8,704
N_ROWS, DIM, N_Q = 80_000, 8, 6
#: candidate widths: the threshold itself; one lane-row over it (137
#: lane-rows in groups of 9: the last real group holds 2, the 17th
#: none); 79 tiles of 256; 335 lane-rows (the last group 15 of 20); 306
#: tiles of 16,384 rows (17 groups of 36, no padding)
WIDTHS = (17_408, 17_536, 20_224, 42_880, 78_336)


@functools.lru_cache(maxsize=None)
def unmerged_stage():
    """``local_select_rescore`` as it is where the merge is bypassed: the
    same function traced with the geometry helper answering None."""
    def stage(q, t, cd, ci, bounds, m):
        real = pk.select_merge_geometry
        pk.select_merge_geometry = lambda width, m: None
        try:
            return pk.local_select_rescore.__wrapped__(
                q, t, cd, ci, bounds, m)
        finally:
            pk.select_merge_geometry = real

    return jax.jit(stage, static_argnames=("m",))


def candidates(width: int, seed: int = 0):
    """Kernel-shaped inputs: distinct scores, every db row at most once a
    query, bounds above every score (so ``lb`` is the select's doing)."""
    rng = np.random.default_rng([28, width, seed])
    cd = rng.permutation(N_Q * width).reshape(N_Q, width).astype(np.float32)
    ci = np.stack([rng.permutation(N_ROWS)[:width]
                   for _ in range(N_Q)]).astype(np.int32)
    bounds = np.full((N_Q, width // 2), 4.0 * N_Q * width, np.float32)
    q = rng.integers(0, 256, (N_Q, DIM)).astype(np.float32)
    t = rng.integers(0, 256, (N_ROWS, DIM)).astype(np.float32)
    return q, t, cd, ci, bounds


def both(q, t, cd, ci, bounds):
    merged = pk.local_select_rescore(q, t, cd, ci, bounds, M)
    plain = unmerged_stage()(q, t, cd, ci, bounds, M)
    return ([np.asarray(x) for x in merged], [np.asarray(x) for x in plain])


def score_of_selected(cd, ci, idx):
    """Kernel score of each selected row (+inf for the sentinel), by
    looking its index up among the query's candidates."""
    out = np.full(idx.shape, np.inf, np.float32)
    for r in range(idx.shape[0]):
        where = {int(i): c for c, i in enumerate(ci[r]) if i != I32MAX}
        for c, i in enumerate(idx[r]):
            if i != I32MAX:
                out[r, c] = cd[r, where[int(i)]]
    return out


def check_sound(cd, ci, idx, lb):
    """Every candidate not selected scores at least ``lb``."""
    for r in range(cd.shape[0]):
        left = ~np.isin(ci[r], idx[r][idx[r] != I32MAX]) & np.isfinite(cd[r])
        if left.any():
            assert cd[r][left].min() >= lb[r], r


@pytest.mark.parametrize("width", WIDTHS)
def test_merged_select_is_sound_and_equals_the_plain_select(width):
    q, t, cd, ci, bounds = candidates(width)
    assert pk.select_merge_geometry(width, M) is not None
    (d_m, i_m, lb_m), (d_p, i_p, lb_p) = both(q, t, cd, ci, bounds)
    assert (lb_m <= lb_p).all()
    check_sound(cd, ci, i_m, lb_m)
    # wherever the bound clears the (m+1)-th selected score nothing was
    # dropped on the way, and the answer is the plain select's bitwise
    clear = lb_m > score_of_selected(cd, ci, i_m).max(axis=1)
    assert clear.any()
    np.testing.assert_array_equal(i_m[clear], i_p[clear])
    np.testing.assert_array_equal(d_m[clear], d_p[clear])
    # distinct scores spread at random: no merge bin holds five of the
    # best 130 here, so every query is clear and the bound is the plain one
    assert clear.all()
    np.testing.assert_array_equal(lb_m, lb_p)


@pytest.mark.parametrize("planted", (5, 6, 9))
@pytest.mark.parametrize("width", (42_880, 78_336))
def test_five_of_the_best_in_one_merge_bin_lower_the_bound(width, planted):
    """Detected, not wrong: the bin keeps four, the fifth becomes its
    bound, and ``lb`` falls to it."""
    q, t, cd, ci, bounds = candidates(width, seed=planted)
    groups, rows, _ = pk.select_merge_geometry(width, M)
    lane, first = 37, (groups - 1) * rows  # the last (shorter) group
    assert width // pk.BIN_W - first >= planted
    cols = (np.arange(first, first + planted) * pk.BIN_W + lane)
    # the query's `planted` best scores, negative so nothing else is near
    cd[:, cols] = -np.arange(planted, 0, -1, dtype=np.float32)[None] * 10
    (d_m, i_m, lb_m), (_, i_p, lb_p) = both(q, t, cd, ci, bounds)
    fifth = np.sort(cd[:, cols], axis=1)[:, 4]
    np.testing.assert_array_equal(lb_m, fifth)
    assert (lb_p > fifth).all()           # the plain select keeps them all
    check_sound(cd, ci, i_m, lb_m)
    kept = np.sort(cols[np.argsort(cd[0, cols])[:4]])
    for r in range(N_Q):
        assert np.isin(ci[r, kept], i_m[r]).all()
        assert not np.isin(ci[r, np.setdiff1d(cols, kept)], i_m[r]).any()
        assert np.isin(ci[r, cols], i_p[r]).all()


def test_ties_at_the_boundary_keep_the_bound_at_the_tied_score():
    """Equal scores across the (m+1)-th / (m+2)-th place: which of the
    tied candidates is selected may differ from the plain select's, the
    bound may not, and it never exceeds a score left out."""
    width = 20_224
    q, t, cd, ci, bounds = candidates(width)
    order = np.argsort(cd, axis=1)
    tied = order[:, M - 3: M + 6]         # nine candidates share a score
    np.put_along_axis(cd, tied, np.take_along_axis(
        cd, order[:, M - 3: M - 2], axis=1), axis=1)
    (d_m, i_m, lb_m), (d_p, i_p, lb_p) = both(q, t, cd, ci, bounds)
    np.testing.assert_array_equal(lb_m, lb_p)
    np.testing.assert_array_equal(
        lb_m, np.take_along_axis(cd, order[:, M - 3: M - 2], axis=1)[:, 0])
    check_sound(cd, ci, i_m, lb_m)
    # below the tie both selects hold the same rows
    below = np.take_along_axis(ci, order[:, : M - 3], axis=1)
    for r in range(N_Q):
        assert np.isin(below[r], i_m[r]).all()
        assert np.isin(below[r], i_p[r]).all()


@pytest.mark.parametrize("finite", (40, 129, 300))
def test_inf_padding_and_sentinels_never_become_rows(finite):
    """A candidate array that is mostly +inf with sentinel indices (the
    fused kernel's skipped tiles, kernel padding): +inf never enters a
    merge bin, its slots read the sentinel, and a select that runs out
    of finite candidates pads with (+inf, sentinel) as the plain one."""
    width = 20_224
    q, t, cd, ci, bounds = candidates(width)
    order = np.argsort(cd, axis=1)
    np.put_along_axis(cd, order[:, finite:], np.inf, axis=1)
    np.put_along_axis(ci, order[:, finite:], I32MAX, axis=1)
    (d_m, i_m, lb_m), (d_p, i_p, lb_p) = both(q, t, cd, ci, bounds)
    check_sound(cd, ci, i_m, lb_m)
    assert (lb_m <= lb_p).all()
    n_rows = (i_m != I32MAX).sum(axis=1)
    assert (n_rows == min(finite, M + 1)).all()
    assert np.isinf(d_m[i_m == I32MAX]).all()
    assert np.isfinite(d_m[i_m != I32MAX]).all()
    np.testing.assert_array_equal(i_m, i_p)
    np.testing.assert_array_equal(d_m, d_p)


def test_approx_arm_runs_over_the_merged_candidates():
    q, t, cd, ci, bounds = candidates(39_168)
    d, i, lb = (np.asarray(x) for x in pk.local_select_rescore(
        q, t, cd, ci, bounds, M, final_select="approx"))
    check_sound(cd, ci, i, lb)
    assert (np.asarray(i) != I32MAX).all()


# --- engagement, from shapes alone -------------------------------------------
def traced_stage(width: int, m: int, n_q: int = 4096):
    """(number of bin-merge Pallas calls, width the top-(m+2) scans) of
    the traced stage; nothing is computed.  The top-(m+2) is XLA's
    ``top_k`` or, where ``final_select_geometry`` engages, the Pallas
    call ``select_final`` (tests/test_final_select.py)."""
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(
        functools.partial(pk.local_select_rescore, m=m))(
        sds((n_q, 16), jnp.float32), sds((1000, 16), jnp.float32),
        sds((n_q, width), jnp.float32), sds((n_q, width), jnp.int32),
        sds((n_q, width // 2), jnp.float32))
    found = {"pallas_call": 0, "top_k": []}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"] == "select_final":
                    found["top_k"].append(eqn.invars[0].aval.shape[-1])
                else:
                    assert eqn.params["name"] == "select_merge"
                    found["pallas_call"] += 1
                continue
            if eqn.primitive.name == "top_k":
                found["top_k"].append(eqn.invars[0].aval.shape[-1])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found["pallas_call"], found["top_k"]


#: (rows, tile_n, m) of tests/test_pallas_knn.py's certified searches
#: (k + margin, capped by the width) and the library's default at 10,000
SMALL_SHAPES = ((1951, 512, 17), (1197, 512, 15), (1545, 512, 16),
                (8269, 4096, 10), (10_000, 8192, 60), (512, 512, 9))


@pytest.mark.parametrize("rows,tile_n,m", SMALL_SHAPES + (
    (1_000_000, 16_384, M),))   # gist1m: 62 tiles, 15,872 columns
def test_narrow_shapes_hold_no_merge(rows, tile_n, m):
    width = -(-rows // tile_n) * 2 * pk.BIN_W
    assert pk.select_merge_geometry(width, m) is None
    assert traced_stage(width, m, n_q=64) == (0, [width])


def test_the_bigann5m_geometry_merges_to_8704_columns():
    width = -(-5_000_000 // pk.TILE_N) * 2 * pk.BIN_W
    assert width == 306 * 256 == 78_336
    assert pk.select_merge_geometry(width, M) == (GROUPS, 36, MERGED)
    assert traced_stage(width, M) == (1, [MERGED])
    out = jax.eval_shape(
        lambda cd, ci: pk._select_merge(cd, ci, GROUPS, 36, interpret=True),
        jax.ShapeDtypeStruct((4096, width), jnp.float32),
        jax.ShapeDtypeStruct((4096, width), jnp.int32))
    assert [o.shape for o in out] == [
        (4096, MERGED), (4096, MERGED), (4096, GROUPS * pk.BIN_W)]


@pytest.mark.parametrize("slots,groups", ((18, 3), (130, 17), (1030, 129)))
def test_geometry_arithmetic(slots, groups):
    m = slots - 2
    merged = groups * pk.SELECT_MERGE_SURVIVORS * pk.BIN_W
    assert groups * pk.BIN_W >= pk.SELECT_MERGE_BINS_PER_SLOT * slots
    assert (groups - 1) * pk.BIN_W < pk.SELECT_MERGE_BINS_PER_SLOT * slots
    # bypassed below twice the merged width, and off the lane grid
    assert pk.select_merge_geometry(2 * merged - pk.BIN_W, m) is None
    assert pk.select_merge_geometry(2 * merged + 64, m) is None
    assert pk.select_merge_geometry(2 * merged, m) == (groups, 8, merged)
    lane_rows = 10 * groups + 1           # one over: every group grows
    assert pk.select_merge_geometry(lane_rows * pk.BIN_W, m) == (
        groups, 11, merged)


# --- an engaging corpus, end to end ------------------------------------------
K = 100
TILE = 256
SHARD_ROWS = 20_000           # 79 tiles of 256: 20,224 columns a shard
COLLIDING = 3                 # the query whose five nearest share a bin


@functools.lru_cache(maxsize=None)
def colliding_corpus(shards: int):
    """Byte-valued rows; query ``COLLIDING``'s five nearest rows sit in
    lane 5 of tiles 0...4 of shard 0, 256 rows apart: each wins its
    kernel bin, all five land in merge bin (group 0, lane 5), which
    keeps four."""
    rng = np.random.default_rng([28, shards])
    db = rng.integers(0, 256, (shards * SHARD_ROWS, 16)).astype(np.float32)
    q = rng.integers(0, 256, (8, 16)).astype(np.float32)
    for j in range(5):
        db[j * TILE + 5] = q[COLLIDING]
        db[j * TILE + 5, 0] += j + 1      # distances 1, 4, 9, 16, 25
    return db, q


def merge_batches():
    """``knn_tpu_select_merge_calls_total`` by its ``engaged`` label."""
    series = obs.snapshot().get(
        mn.SELECT_MERGE_CALLS, {"series": []})["series"]
    return {flag: sum(s["value"] for s in series
                      if s["labels"] == {"engaged": flag})
            for flag in ("true", "false")}


@pytest.mark.parametrize("shards,kernel", (
    (1, "tiled"), (4, "tiled"), (1, "fused")))
def test_engaged_search_is_exact_and_repairs_a_collision(shards, kernel):
    db, q = colliding_corpus(shards)
    prog = ShardedKNN(
        db, mesh=make_mesh(1, shards, devices=jax.devices()[:shards]), k=K)
    before = merge_batches()
    d, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE,
                                        kernel=kernel)
    d64 = ((q[:, None, :].astype(np.float64) - db[None].astype(np.float64))
           ** 2).sum(-1)
    want = np.lexsort((np.broadcast_to(np.arange(db.shape[0]), d64.shape),
                       d64), axis=1)[:, :K]
    np.testing.assert_array_equal(i, want)
    np.testing.assert_array_equal(want[COLLIDING, :5], np.arange(5) * TILE + 5)
    assert stats["select_width"] == 79 * 256 == 20_224
    assert stats["select_merged_width"] == MERGED
    after = merge_batches()
    assert (after["true"] - before["true"],
            after["false"] - before["false"]) == (1, 0)
    # the collision is detected and repaired, not answered wrongly
    assert stats["fallback_queries"] >= 1
    assert stats["certified"] + stats["fallback_queries"] == q.shape[0]


def test_bypassed_search_reports_equal_widths():
    db, q = colliding_corpus(1)
    prog = ShardedKNN(db[:5000], mesh=make_mesh(
        1, 1, devices=jax.devices()[:1]), k=K)
    before = merge_batches()
    _, _, stats = prog.search_certified(q, selector="pallas", tile_n=TILE)
    # 20 tiles of 128 bins at m+2 = 130: depth 2 models 5.2 % of queries
    # on a full bin, so the rule keeps 3 (ops.pallas_knn.survivor_depth)
    assert stats["survivor_depth"] == 3
    assert stats["select_width"] == stats["select_merged_width"] == 20 * 384
    after = merge_batches()
    assert (after["true"] - before["true"],
            after["false"] - before["false"]) == (0, 1)


def test_a_query_count_off_the_block_grid_merges_every_row():
    """130 queries in blocks of 128: the last block is two rows deep."""
    rng = np.random.default_rng(28)
    n_q, width = 130, 17_408
    cd = rng.permutation(n_q * width).reshape(n_q, width).astype(np.float32)
    ci = np.broadcast_to(np.arange(width, dtype=np.int32), cd.shape)
    v, i, b = (np.asarray(x) for x in pk._select_merge(
        jnp.asarray(cd), jnp.asarray(ci), GROUPS, 8, interpret=True))
    bins = cd.reshape(n_q, GROUPS, 8, pk.BIN_W)
    want = np.sort(bins, axis=2)
    got = v.reshape(n_q, GROUPS, pk.SELECT_MERGE_SURVIVORS, pk.BIN_W)
    np.testing.assert_array_equal(
        got, want[:, :, : pk.SELECT_MERGE_SURVIVORS])
    np.testing.assert_array_equal(
        b.reshape(n_q, GROUPS, pk.BIN_W),
        want[:, :, pk.SELECT_MERGE_SURVIVORS])
    np.testing.assert_array_equal(np.take_along_axis(cd, i, axis=1), v)


# --- the arrays read where the kernel wrote them (PR 52) ---------------------
#: (lane-rows of the kernel's width, m, what has that remainder)
OFF_THE_GRID = (
    (612, 38, "deep5m-knng: 5M rows, 5 groups of 123, 3 short"),
    (306, 38, "text2image2m5, yfcc2m5: 2.5M rows, 5 x 62, 4 short"),
    (158, 48, "imagenet-knn768: 1.28M rows, 7 x 23, 3 short"),
    (612, 128, "the k = 100 cells: 17 x 36, the grid fits"),
    (270, 128, "2.2M rows at k = 100: 17 x 16, 2 short"),
    (137, 128, "17 x 9, 16 short: the 16th group holds 2, the 17th none"))


def padded_by_hand(cd, ci, groups, rows):
    """The parent's form: both arrays padded to the group grid, scores
    with +inf and indices with the sentinel, then the merge on a width
    its groups tile (the plain kernel, the parent's program)."""
    pad = groups * rows * pk.BIN_W - cd.shape[1]
    return pk._select_merge(
        jnp.pad(cd, ((0, 0), (0, pad)), constant_values=jnp.inf),
        jnp.pad(ci, ((0, 0), (0, pad)), constant_values=I32MAX),
        groups, rows, interpret=True)


@pytest.mark.parametrize("masked", (True, False))
@pytest.mark.parametrize("lane_rows,m", [c[:2] for c in OFF_THE_GRID],
                         ids=[c[2].split(":")[0] for c in OFF_THE_GRID])
def test_the_merge_reads_the_arrays_where_the_kernel_wrote_them(
        lane_rows, m, masked, monkeypatch):
    """Bit for bit the parent's three arrays, with no copy of ``cd`` or
    ``ci``.  The overhang of the last group's block is poison here: the
    interpreter fills what a block reads past an array with NaN and the
    least int32, and a group that lies past the array altogether reads
    the last block's real scores and real-looking indices.  So the
    unmasked run (the plain kernel on the same overhanging blocks) is
    the control: it is NOT the parent's answer wherever a group is
    short, which is what says the mask and not luck keeps them out."""
    width = lane_rows * pk.BIN_W
    groups, rows, merged = pk.select_merge_geometry(width, m)
    short = groups * rows - lane_rows
    rng = np.random.default_rng([52, lane_rows, m])
    n_q = 16
    cd = rng.permutation(n_q * width).reshape(n_q, width).astype(np.float32)
    ci = np.stack([rng.permutation(N_ROWS * 4)[:width]
                   for _ in range(n_q)]).astype(np.int32)
    # the kernel's own padding, and ties across the last group's edge
    gone = rng.random(cd.shape) < 0.02
    cd[gone], ci[gone] = np.inf, I32MAX
    cd[:, -3 * pk.BIN_W:] = np.floor(cd[:, -3 * pk.BIN_W:] / 64)
    if not masked:
        plain = pk._select_merge_cell(groups, rows, 0)[0]
        real = pk._select_merge_cell
        monkeypatch.setattr(pk, "_select_merge_cell", lambda *a: (
            plain, real(*a)[1]))
    got = [np.asarray(x) for x in pk._select_merge(
        jnp.asarray(cd), jnp.asarray(ci), groups, rows, interpret=True)]
    want = [np.asarray(x) for x in padded_by_hand(
        jnp.asarray(cd), jnp.asarray(ci), groups, rows)]
    assert [x.shape for x in got] == [
        (n_q, merged), (n_q, merged), (n_q, groups * pk.BIN_W)]
    same = [np.array_equal(g, w) and g.dtype == w.dtype
            for g, w in zip(got, want)]
    if masked or not short:
        assert same == [True, True, True]
        assert not np.isnan(got[0]).any() and not np.isnan(got[2]).any()
        assert (got[1] >= 0).all()
    else:
        assert not same[0] and not same[2]
        # every group that holds all its lane-rows is still the parent's
        out_w = pk.SELECT_MERGE_SURVIVORS * pk.BIN_W
        whole = lane_rows // rows
        np.testing.assert_array_equal(got[0][:, :whole * out_w],
                                      want[0][:, :whole * out_w])


def test_the_short_kernels_frame_is_the_plain_kernels():
    """A tripwire like tests/test_dim_chunking.py's: the merge's trace
    binds the emitter's unrolled loop (2,460 binds at 123 lane-rows a
    group) under the merge kernel's frame, and where CPython's 16 KiB
    frame-stack chunks end under that loop is drawn by the summed frame
    sizes above it.  The kernel that masks the overhang forms the mask
    in a call that has returned by then and keeps the plain kernel's
    frame to the slot, so a cell whose merge is off its grid keeps the
    parent's draw: a first form 14 slots larger cost
    ``text2image2m5.sweep_ip`` 0.6 s of its ``first batch`` (root
    PERF.md section 6, PR 52)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "frame_sizes", os.path.join(os.path.dirname(HERE), "scripts",
                                    "frame_sizes.py"))
    frame_sizes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(frame_sizes)
    assert (frame_sizes.slots(pk._select_merge_short_kernel.__code__)
            == frame_sizes.slots(pk._select_merge_kernel.__code__))


def pads_and_kernels(jaxpr, pads, kernels):
    """Result shapes of every ``pad`` of a jaxpr and the names of its
    Pallas calls, its sub-jaxprs' (the shard_map's, the jits') included;
    a kernel's body is not walked (it holds no array of the call)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad":
            pads.append(tuple(eqn.outvars[0].aval.shape))
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"])
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    pads_and_kernels(inner, pads, kernels)
    return pads, kernels


@pytest.mark.parametrize("cell,grid", (
    ("text2image2m5.sweep_ip", (5, 62)), ("deep5m-knng.build", (5, 123)),
    ("imagenet-knn768.sweep_vote", (7, 23))))
def test_the_certified_program_pads_no_candidate_array(cell, grid):
    """The whole program of a cell whose merge is off its grid, traced
    as the chip runs it (the kernels not interpreted: the interpreter
    pads every array it blocks): between the kernel and
    ``select_merge`` nothing makes an array of the group grid's width
    (the parent's two ``pad`` did: 2 x 643 MB of HBM traffic a launch
    at ``deep5m-knng``)."""
    pads, kernels = pads_and_kernels(
        program_digest.traced(cell, 1024, interpret=False).jaxpr, [], [])
    assert "select_merge" in kernels and "select_final" in kernels
    assert grid[0] * grid[1] * pk.BIN_W not in [p[-1] for p in pads]


#: (rows, lane-rows short): 21, 23 and 25 tiles of 256 at k = 10
#: (m + 2 = 40, 5 groups: 42 lane-rows in 5 x 9, 46 in 5 x 10, 50 fit),
#: and a shard under the merge's engagement width
SHORT_CASES = ((5_300, 3), (5_800, 4), (6_300, 0), (2_000, 0))


@pytest.fixture
def fresh_registry():
    obs.reset(enabled=True)
    obs.reset_event_log(None)
    yield
    obs.reset()
    obs.reset_event_log(from_env=True)


@pytest.mark.parametrize("n_rows,short", SHORT_CASES)
def test_the_call_says_how_short_the_last_group_is(fresh_registry, n_rows,
                                                   short):
    rng = np.random.default_rng([52, n_rows])
    db = rng.integers(0, 256, (n_rows, 16)).astype(np.float32)
    q = rng.integers(0, 256, (8, 16)).astype(np.float32)
    prog = ShardedKNN(
        db, mesh=make_mesh(1, 1, devices=jax.devices()[:1]), k=10)
    _, i, stats = prog.search_certified(q, selector="pallas", tile_n=TILE)
    (call,) = [e for e in obs.get_event_log().recent()
               if e.get("span") == "certified.call"]
    d64 = ((q[:, None, :].astype(np.float64) - db[None].astype(np.float64))
           ** 2).sum(-1)
    np.testing.assert_array_equal(i, np.lexsort((np.broadcast_to(
        np.arange(n_rows), d64.shape), d64), axis=1)[:, :10])
    width = -(-n_rows // TILE) * 2 * pk.BIN_W
    engaged = n_rows > 5_000
    assert stats["select_width"] == call["select_width"] == width
    assert stats["select_merged_width"] == (2560 if engaged else width)
    assert (stats["select_merge_short"], call["select_merge_short"],
            stats["pallas_knobs"]["select_merge_short"]) == (short,) * 3
