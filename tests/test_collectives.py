"""The MPI-collective mapping surface (parallel.collectives) — each entry
point the package docstring advertises (parallel/__init__.py), exercised
for real: placement collectives produce the promised shardings, compute
collectives reduce/assemble correctly inside shard_map.

Reference contract being mapped: the 11 MPI entry points of SURVEY.md §2.8
(knn_mpi.cpp:123-129,133-134,224-227,276-277,340,383,395-397)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from knn_tpu.parallel import (
    DB_AXIS,
    QUERY_AXIS,
    allreduce_max,
    allreduce_min,
    barrier,
    gather,
    make_mesh,
    replicate,
    shard,
)


def test_replicate_places_full_copy_everywhere(rng):
    mesh = make_mesh(4, 2)
    x = rng.normal(size=(16, 3)).astype(np.float32)
    r = replicate(x, mesh)
    assert r.sharding == NamedSharding(mesh, P())
    assert all(s.data.shape == x.shape for s in r.addressable_shards)
    np.testing.assert_array_equal(np.asarray(r), x)


def test_shard_splits_along_named_axis(rng):
    mesh = make_mesh(4, 2)
    x = rng.normal(size=(8, 5)).astype(np.float32)
    s = shard(x, mesh, QUERY_AXIS)
    assert s.sharding.is_equivalent_to(NamedSharding(mesh, P(QUERY_AXIS)), x.ndim)
    assert all(sh.data.shape == (2, 5) for sh in s.addressable_shards)
    np.testing.assert_array_equal(np.asarray(s), x)
    s2 = shard(x, mesh, (QUERY_AXIS, DB_AXIS))  # both axes, 8-way
    assert all(sh.data.shape == (1, 5) for sh in s2.addressable_shards)


def test_gather_reassembles_shards(rng):
    mesh = make_mesh(8, 1)
    x = rng.normal(size=(24, 4)).astype(np.float32)

    fn = jax.jit(
        jax.shard_map(
            lambda q: gather(q, QUERY_AXIS),
            mesh=mesh,
            in_specs=P(QUERY_AXIS),
            out_specs=P(),
            check_vma=False,
        )
    )
    np.testing.assert_array_equal(np.asarray(fn(shard(x, mesh, QUERY_AXIS))), x)


def test_gather_stacked_gives_device_axis(rng):
    mesh = make_mesh(8, 1)
    x = np.arange(8, dtype=np.float32)[:, None]

    fn = jax.jit(
        jax.shard_map(
            lambda q: gather(q, QUERY_AXIS, tiled=False),
            mesh=mesh,
            in_specs=P(QUERY_AXIS),
            out_specs=P(),
            check_vma=False,
        )
    )
    assert np.asarray(fn(shard(x, mesh, QUERY_AXIS))).shape == (8, 1, 1)


def test_allreduce_extrema_match_global(rng):
    mesh = make_mesh(4, 2)
    x = rng.normal(size=(16, 6)).astype(np.float32)

    def spmd(a):
        lo = allreduce_min(jnp.min(a, axis=0), (QUERY_AXIS, DB_AXIS))
        hi = allreduce_max(jnp.max(a, axis=0), (QUERY_AXIS, DB_AXIS))
        return lo, hi

    fn = jax.jit(
        jax.shard_map(
            spmd, mesh=mesh,
            in_specs=P((QUERY_AXIS, DB_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )
    lo, hi = fn(shard(x, mesh, (QUERY_AXIS, DB_AXIS)))
    np.testing.assert_array_equal(np.asarray(lo), x.min(0))
    np.testing.assert_array_equal(np.asarray(hi), x.max(0))


def test_barrier_blocks_on_device_values(rng):
    mesh = make_mesh(8, 1)
    x = shard(rng.normal(size=(8, 2)).astype(np.float32), mesh, QUERY_AXIS)
    y = jax.jit(lambda a: a * 2)(x)
    barrier(y, [x, {"k": y}], None, 3.0)  # arbitrary trees + non-arrays ok
    assert np.asarray(y).shape == (8, 2)


# --- measured ring/allgather crossover (parallel.crossover) -------------

def _scaling_rows():
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "SCALING.json")
    return json.load(open(path))["rows"]


def test_crossover_table_matches_scaling_json():
    """The persisted MEASURED_CROSSOVER table must be the argmin-wall
    strategy at every measured SCALING.json (k, shards) point — edit
    the measurement and this pin forces the table to follow."""
    from knn_tpu.parallel import crossover

    best = {}
    for row in _scaling_rows():
        if row["merge"] == "none":
            continue
        shards = int(row["mesh"].split("x")[1])
        key = (row["k"], shards)
        if key not in best or row["wall_s"] < best[key][1]:
            best[key] = (row["merge"], row["wall_s"])
    derived = {k: v[0] for k, v in best.items()}
    assert derived == crossover.MEASURED_CROSSOVER


def test_merge_bytes_model_reproduces_scaling_column():
    """merge_bytes must reproduce SCALING.json's measured
    merge_bytes_per_sweep column exactly (Q=2048 queries per sweep)."""
    from knn_tpu.parallel import crossover

    for row in _scaling_rows():
        if row["merge"] == "none":
            continue
        shards = int(row["mesh"].split("x")[1])
        assert crossover.merge_bytes(2048, row["k"], shards,
                                     row["merge"]) == \
            row["merge_bytes_per_sweep"], row


def test_choose_merge_nearest_point_and_trivial_shards():
    from knn_tpu.parallel import crossover

    # measured points verbatim
    assert crossover.choose_merge(10, 4) == "ring"
    assert crossover.choose_merge(100, 2) == "ring"
    assert crossover.choose_merge(100, 8) == "allgather"
    # nearest-in-log lookups off the grid
    # 3 shards sits nearer 4 than 2 in log space
    assert crossover.choose_merge(12, 3) == \
        crossover.MEASURED_CROSSOVER[(10, 4)]
    assert crossover.choose_merge(1000, 16) == \
        crossover.MEASURED_CROSSOVER[(100, 8)]
    assert crossover.choose_merge(5, 1) == "allgather"  # no merge at all


def test_sharded_default_merge_follows_measured_table(rng):
    """REGRESSION (ISSUE 12 satellite): ShardedKNN's default merge is
    no longer caller folklore — merge=None resolves to the measured
    crossover per (k, db_shards), an env switch overrides the table,
    and an explicit argument still beats both."""
    import os

    from knn_tpu.parallel import ShardedKNN, crossover

    db = rng.normal(size=(512, 6)).astype(np.float32)
    for k, shards in ((10, 2), (100, 4), (7, 8)):
        mesh = make_mesh(8 // shards, shards)
        prog = ShardedKNN(db, mesh=mesh, k=k)
        assert prog.merge == crossover.choose_merge(k, shards)
        assert prog.merge_source == "measured"
    db = rng.normal(size=(64, 6)).astype(np.float32)
    # env beats the table ...
    os.environ["KNN_TPU_MERGE"] = "ring"
    try:
        prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=10)
        assert (prog.merge, prog.merge_source) == ("ring", "env")
        # ... and an explicit argument beats the env
        prog = ShardedKNN(db, mesh=make_mesh(4, 2), k=10,
                          merge="allgather")
        assert (prog.merge, prog.merge_source) == ("allgather", "explicit")
    finally:
        os.environ.pop("KNN_TPU_MERGE", None)
    # malformed env values raise instead of silently steering
    os.environ["KNN_TPU_MERGE"] = "bogus"
    try:
        import pytest

        with pytest.raises(ValueError, match="KNN_TPU_MERGE"):
            ShardedKNN(db, mesh=make_mesh(4, 2), k=10)
    finally:
        os.environ.pop("KNN_TPU_MERGE", None)


def test_validate_multihost_block_contract():
    from knn_tpu.parallel.crossover import validate_multihost_block

    good = {"hosts": 2, "chips_per_host": 2,
            "merge": {"intra": {"strategy": "allgather",
                                "source": "measured"},
                      "dcn": {"strategy": "ring", "source": "env"}},
            "dcn_merge_bytes": 1024,
            "hosttier": {"sweeps": 3, "budget_bytes": 4096,
                         "segment_rows": 64}}
    assert validate_multihost_block(good) == []
    assert validate_multihost_block("nope")
    assert validate_multihost_block({"hosts": 0, "merge": {}})
    bad = dict(good, hosttier={"sweeps": 0, "budget_bytes": -1,
                               "segment_rows": None})
    assert len(validate_multihost_block(bad)) == 3
