"""Host-RAM shard tier (ISSUE 12 tentpole b): a corpus whose placement
exceeds the per-host HBM budget serves from host memory, streamed
budget-sized segment by segment through the device placement with
dispatch-ahead overlap — bitwise-identical to the all-in-HBM path.

The boundary matrix is the acceptance surface: corpus exactly AT the
budget (resident, no tier), ONE ROW over (2 sweeps), and many-x over
(sweep count pinned against the analysis.hbm byte model)."""

import numpy as np
import pytest
from oracles import assert_same_neighbors

from knn_tpu.analysis import hbm
from knn_tpu.parallel import ShardedKNN, make_mesh
from knn_tpu.parallel.mesh import make_host_mesh

DIM = 16
DB_SHARDS = 2
MESH = (4, DB_SHARDS)


def _budget_for_rows(rows: int) -> int:
    """The per-host budget that holds exactly ``rows`` placed rows."""
    return hbm.placement_bytes(rows, DIM)


def _db(rng, n):
    return (rng.random((n, DIM)) * 10).astype(np.float32)


def test_corpus_exactly_at_budget_stays_resident(rng):
    db = _db(rng, 128)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=_budget_for_rows(128))
    assert prog.hosttier_stats() is None  # fits: everything resident
    assert prog._tp is not None


def test_one_row_over_budget_streams_two_sweeps(rng):
    db = _db(rng, 128)
    q = _db(rng, 9)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(*MESH), k=5).search(q)
    # budget holds 127 of the 128 padded rows -> the tier engages and
    # the plan needs 2 sweeps (segment = largest shard-multiple fitting)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=_budget_for_rows(127))
    st = prog.hosttier_stats()
    assert st is not None and st["sweeps"] == 2
    d, i = prog.search(q)
    # the tier streams the rows at the width given, the resident
    # placement lays them out in whole lane tiles (16 columns in 128):
    # same neighbours, f32 distances within rounding
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)


def test_many_times_over_budget_matches_byte_model_and_is_bitwise(rng):
    """ACCEPTANCE (ISSUE 12): a corpus many-x the (env-forced) per-host
    HBM budget serves END-TO-END through the host-RAM tier — executed
    sweep count equals the analysis.hbm byte model's plan, every sweep
    runs the ONE compiled program shape (the structural form of flat
    per-sweep latency: identical padded operands, identical
    executable), per-sweep walls are recorded, and results are
    bitwise-identical to the all-in-HBM placement."""
    import os

    db = _db(rng, 400)
    q = _db(rng, 17)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(*MESH), k=7).search(q)
    budget = _budget_for_rows(64)
    os.environ["KNN_TPU_HOSTTIER_BUDGET_BYTES"] = str(budget)
    try:
        prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=7)
    finally:
        os.environ.pop("KNN_TPU_HOSTTIER_BUDGET_BYTES", None)
    st = prog.hosttier_stats()
    expect = hbm.n_sweeps(400, DIM, budget, shard_multiple=DB_SHARDS)
    assert expect >= 6  # genuinely many-x over
    assert st["sweeps"] == expect
    d, i = prog.search(q)
    # the tier's segment program and the resident program are different
    # shapes: same neighbours, f32 distances within rounding
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)
    last = prog.hosttier_stats()["last_search"]
    assert last["sweeps"] == expect
    assert len(last["sweep_walls_s"]) == expect
    # one compiled shape serves every sweep, ragged tail included
    assert len(prog._dispatch_shapes) == 1


def test_host_tier_on_hierarchical_mesh(rng):
    # tier-vs-resident on the SAME hierarchical mesh: the segment
    # program's operands are shaped differently from the resident
    # program's, so the contract is equal neighbours and f32 distances
    # within rounding (XLA's gemm strategy varies with operand shape in
    # the last float bits — serving.engine docstring)
    db = _db(rng, 240)
    q = _db(rng, 8)
    ref_d, ref_i = ShardedKNN(db, mesh=make_host_mesh(2, 2, 2),
                              k=4).search(q)
    prog = ShardedKNN(db, mesh=make_host_mesh(2, 2, 2), k=4,
                      hbm_budget_bytes=_budget_for_rows(80) // 2)
    st = prog.hosttier_stats()
    assert st is not None and st["sweeps"] >= 2
    d, i = prog.search(q)
    assert_same_neighbors(d, i, ref_d, ref_i, q, db)


def test_host_tier_k_override_and_cosine(rng):
    db = _db(rng, 160)
    q = _db(rng, 6)
    ref = ShardedKNN(db, mesh=make_mesh(*MESH), k=3, metric="cosine")
    tier = ShardedKNN(db, mesh=make_mesh(*MESH), k=3, metric="cosine",
                      hbm_budget_bytes=_budget_for_rows(48))
    assert tier.hosttier_stats()["sweeps"] >= 3
    rd, ri = ref.search(q, k=5)
    d, i = tier.search(q, k=5)
    np.testing.assert_array_equal(i, np.asarray(ri))
    # two widths (above): cosine distances of unit rows, within rounding
    np.testing.assert_allclose(d, np.asarray(rd), rtol=0,
                               atol=8 * np.finfo(np.float32).eps)


def test_resident_only_paths_refuse_host_tier(rng):
    db = _db(rng, 128)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=_budget_for_rows(40))
    for call in (
        lambda: prog.search_certified(_db(np.random.default_rng(1), 4)),
        lambda: prog.radius_search(_db(np.random.default_rng(1), 4), 1.0,
                                   max_neighbors=3),
        lambda: prog.search_bucketed(_db(np.random.default_rng(1), 4)),
    ):
        with pytest.raises(ValueError, match="host-RAM shard tier"):
            call()


def test_bad_budget_values_raise(rng):
    db = _db(rng, 64)
    with pytest.raises(ValueError, match="hbm_budget_bytes"):
        ShardedKNN(db, mesh=make_mesh(*MESH), k=3, hbm_budget_bytes=0)
    # a budget too small for even one shard-multiple of rows is loud
    with pytest.raises(ValueError, match="cannot hold"):
        ShardedKNN(db, mesh=make_mesh(*MESH), k=3, hbm_budget_bytes=8)


def test_plan_segments_model():
    # equal segments, shard-multiple widths, full coverage
    segs = hbm.plan_segments(1000, 32, hbm.placement_bytes(256, 32),
                             shard_multiple=8)
    assert segs[0] == (0, 256)
    assert segs[-1][1] == 1000
    assert all((hi - lo) <= 256 for lo, hi in segs)
    assert hbm.n_sweeps(1000, 32, hbm.placement_bytes(256, 32),
                        shard_multiple=8) == len(segs) == 4
    # hosts multiply the per-sweep capacity
    assert hbm.rows_for_budget(hbm.placement_bytes(100, 32), 32,
                               hosts=2) == 200


def test_hosttier_metrics_registered(rng):
    from knn_tpu import obs
    from knn_tpu.obs import names as mn

    db = _db(rng, 128)
    prog = ShardedKNN(db, mesh=make_mesh(*MESH), k=3,
                      hbm_budget_bytes=_budget_for_rows(40))
    before = obs.counter(mn.HOSTTIER_SWEEPS).get()
    prog.search(_db(rng, 4))
    after = obs.counter(mn.HOSTTIER_SWEEPS).get()
    assert after - before == prog.hosttier_stats()["sweeps"]


def test_budget_on_device_resident_array_refuses_loudly(rng):
    # the tier streams from host memory; a device/pre-placed array that
    # cannot fit the budget must refuse, not silently place resident
    import jax.numpy as jnp

    db = _db(rng, 128)
    with pytest.raises(ValueError, match="host-array construction"):
        ShardedKNN(jnp.asarray(db), mesh=make_mesh(*MESH), k=5,
                   hbm_budget_bytes=_budget_for_rows(40))
    # ... but a device array that FITS the budget places normally
    prog = ShardedKNN(jnp.asarray(db), mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=_budget_for_rows(256))
    assert prog.hosttier_stats() is None


def test_serving_engine_refuses_host_tier_placement(rng):
    from knn_tpu.serving.engine import ServingEngine

    prog = ShardedKNN(_db(rng, 128), mesh=make_mesh(*MESH), k=5,
                      hbm_budget_bytes=_budget_for_rows(40))
    with pytest.raises(ValueError, match="host-RAM shard tier"):
        ServingEngine(prog)


def test_malformed_hosttier_depth_env_raises(rng):
    import os

    os.environ["KNN_TPU_HOSTTIER_DEPTH"] = "four"
    try:
        with pytest.raises(ValueError, match="KNN_TPU_HOSTTIER_DEPTH"):
            ShardedKNN(_db(rng, 128), mesh=make_mesh(*MESH), k=5,
                       hbm_budget_bytes=_budget_for_rows(40))
    finally:
        os.environ.pop("KNN_TPU_HOSTTIER_DEPTH", None)
