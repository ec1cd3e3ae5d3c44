"""Multi-host helpers (parallel.multihost) on the single-process 8-device
CPU mesh: process-spanning semantics degenerate to the local case, which
pins the contracts (global shapes, shardings, ShardedKNN pre-placed path)
that a real pod run relies on.

The three REAL-multi-process tests additionally need a jaxlib whose CPU
backend can execute computations spanning jax.distributed processes;
not every jaxlib build can (the installed 0.9.0 can; one that cannot
raises "Multiprocess computations aren't implemented on the CPU
backend").  A one-shot capability probe
(``_multiprocess_cpu_supported``) decides ONCE per session and those
tests skip with the probe's actual error as the reason — tier-1 stays
green on such builds instead of carrying known-red entries, and the
tests reactivate by themselves on a jaxlib that grows the capability."""

import jax
import numpy as np
import pytest
from oracles import assert_same_neighbors
from jax.sharding import NamedSharding, PartitionSpec as P

from knn_tpu.parallel import DB_AXIS, ShardedKNN, make_mesh
from knn_tpu.parallel.multihost import (
    global_mesh,
    initialize,
    process_row_slice,
    shard_across_hosts,
)


def test_initialize_single_process_noop():
    initialize()  # num_processes None
    initialize(num_processes=1)  # explicit single process
    assert jax.process_count() == 1


def test_global_mesh_spans_all_devices():
    mesh = global_mesh(4, 2)
    assert mesh.devices.size == 8
    assert mesh.shape == {"query": 4, "db": 2}


def test_process_row_slice_covers_everything():
    sl = process_row_slice(64)
    assert sl == slice(0, 64)  # single process owns all rows


def test_shard_across_hosts_places_db_sharded(rng):
    mesh = global_mesh(4, 2)
    local = rng.normal(size=(16, 5)).astype(np.float32)
    arr = shard_across_hosts(local, mesh, DB_AXIS)
    assert arr.shape == (16, 5)  # 1 process: global == local
    assert arr.sharding.is_equivalent_to(NamedSharding(mesh, P(DB_AXIS)), 2)
    np.testing.assert_array_equal(np.asarray(arr), local)


@pytest.mark.parametrize("dim", [12, 128])
def test_sharded_knn_accepts_pre_placed_global_array(rng, dim):
    mesh = make_mesh(4, 2)
    db = rng.normal(size=(128, dim)).astype(np.float32)
    q = rng.normal(size=(20, dim)).astype(np.float32)
    ref = ShardedKNN(db, mesh=mesh, k=7)
    ref_d, ref_i = ref.search(q)

    placed = shard_across_hosts(db, mesh, DB_AXIS)
    prog = ShardedKNN(placed, mesh=mesh, k=7)
    # a pre-placed array is used as it is handed in; a host array is
    # laid out in whole 128-column lane tiles
    assert (prog._tp.shape[1], ref._tp.shape[1]) == (dim, 128)
    d, i = prog.search(q)
    if dim % 128:  # two widths, two programs: f32 within rounding
        assert_same_neighbors(d, i, ref_d, ref_i, q, db)
    else:
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
        np.testing.assert_array_equal(np.asarray(d), np.asarray(ref_d))


def test_replicated_placement_flows_through_normal_path(rng):
    mesh = make_mesh(4, 2)
    db = rng.normal(size=(15, 4)).astype(np.float32)
    placed = jax.device_put(
        db, NamedSharding(mesh, P())
    )  # replicated, not db-sharded -> treated as a plain array
    prog = ShardedKNN(placed, mesh=mesh, k=3)
    assert prog.n_train == 15


def test_pre_placed_n_train_masks_pad_rows(rng):
    # caller pads to the shard multiple before placing; n_train tells the
    # programs the true row count so zero-pad rows can never win.  Pads are
    # all-zero rows, which WOULD win under cosine-normalized data if
    # unmasked (distance ||q||^2 to everything).
    import pytest

    mesh = make_mesh(4, 2)
    db = rng.normal(size=(13, 6)).astype(np.float32)
    q = rng.normal(size=(9, 6)).astype(np.float32)
    ref_d, ref_i = ShardedKNN(db, mesh=mesh, k=4).search(q)

    padded = np.zeros((14, 6), np.float32)
    padded[:13] = db
    placed = jax.device_put(padded, NamedSharding(mesh, P(DB_AXIS)))
    prog = ShardedKNN(placed, mesh=mesh, k=4, n_train=13)
    d, i = prog.search(q)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
    assert (np.asarray(i) < 13).all()

    with pytest.raises(ValueError, match="outside"):
        ShardedKNN(placed, mesh=mesh, k=4, n_train=15)
    with pytest.raises(ValueError, match="only for pre-placed"):
        ShardedKNN(db, mesh=mesh, k=4, n_train=13)


import mh_harness


def _require_multiprocess_cpu():
    """Skip (with the probe's recorded error) when this jaxlib cannot
    run multi-process CPU collectives — probed once per session.  The
    KV-lane tests below do NOT use this gate: they need only
    jax.distributed init + the coordinator KV store
    (mh_harness.distributed_init_supported), which every supported
    jaxlib provides — they are pinned tests, not skips."""
    verdict = mh_harness.multiprocess_cpu_supported()
    if not verdict["ok"]:
        pytest.skip(
            "multi-process CPU collectives unsupported by this jaxlib: "
            f"{verdict['reason']}")


def _spawn_jax_procs(tmp_path, child_src: str, n_proc: int) -> dict:
    return mh_harness.spawn_jax_procs(tmp_path, child_src, n_proc)


def test_multihost_real_processes_bitwise_parity(rng, tmp_path):
    """VERDICT r3 item 3: execute the multi-host path with REAL OS
    processes — 2 jax.distributed CPU processes (Gloo collectives over
    DCN's stand-in), each holding only its own db slice — and assert the
    assembled ShardedKNN search is bitwise-equal to single-process.
    This is the analogue of the reference actually running under
    ``mpiexec -n N`` (knn_mpi.cpp:123-125)."""
    _require_multiprocess_cpu()
    results = _spawn_jax_procs(tmp_path, """
        import sys, json
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

        from knn_tpu.parallel import multihost
        from knn_tpu.parallel.mesh import DB_AXIS
        from knn_tpu.parallel.sharded import ShardedKNN

        multihost.initialize(coordinator_address=f"localhost:{port}",
                             num_processes=n_proc, process_id=pid)
        assert jax.process_count() == n_proc
        rng = np.random.default_rng(0)
        # 128 columns: whole lane tiles, so the pre-placed slices here
        # and the host array below are one placed width, one program
        db = (rng.random((64, 128)) * 10).astype(np.float32)
        q = (rng.random((6, 128)) * 10).astype(np.float32)
        mesh = multihost.global_mesh(1, n_proc)
        sl = multihost.process_row_slice(64)
        placed = multihost.shard_across_hosts(db[sl], mesh, DB_AXIS)
        prog = ShardedKNN(placed, mesh=mesh, k=5)
        d, i = prog.search(q)
        print("RESULT " + json.dumps({
            "pid": pid, "n_dev": len(jax.devices()),
            "i": np.asarray(i).tolist(), "d": np.asarray(d).tolist()}),
            flush=True)
    """, n_proc=2)

    # both processes span the global 2-device mesh and agree exactly
    assert results[0]["n_dev"] == results[1]["n_dev"] == 2
    assert results[0]["i"] == results[1]["i"]
    assert results[0]["d"] == results[1]["d"]

    # bitwise parity with the single-process placement (same seeded data)
    data_rng = np.random.default_rng(0)
    db = (data_rng.random((64, 128)) * 10).astype(np.float32)
    q = (data_rng.random((6, 128)) * 10).astype(np.float32)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(1, 2), k=5).search(q)
    np.testing.assert_array_equal(
        np.asarray(results[0]["i"]), np.asarray(ref_i))
    np.testing.assert_array_equal(
        np.asarray(results[0]["d"], dtype=np.float32), np.asarray(ref_d))


def test_multihost_certified_pallas_bitwise_parity(rng, tmp_path):
    """The FLAGSHIP path under REAL multi-host: 2 jax.distributed CPU
    processes, the db constructed from the full host array on each host
    (the reference's replicated-host-data pattern, knn_mpi.cpp:224 —
    required because the certified pipeline's float64 refine needs a
    host copy), ``search_certified`` with the one-pass pallas selector
    sharding the db axis across the process boundary.  Both processes
    must agree bitwise and match the single-process run — indices,
    float64 distances, AND certification stats."""
    _require_multiprocess_cpu()
    results = _spawn_jax_procs(tmp_path, """
        import sys, json
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

        from knn_tpu.parallel import multihost
        from knn_tpu.parallel.sharded import ShardedKNN

        multihost.initialize(coordinator_address=f"localhost:{port}",
                             num_processes=n_proc, process_id=pid)
        rng = np.random.default_rng(0)
        db = (rng.random((96, 8)) * 10).astype(np.float32)
        q = (rng.random((6, 8)) * 10).astype(np.float32)
        mesh = multihost.global_mesh(1, n_proc)
        prog = ShardedKNN(db, mesh=mesh, k=5)
        d, i, stats = prog.search_certified(q, selector="pallas", margin=8)
        print("RESULT " + json.dumps({
            "pid": pid, "i": np.asarray(i).tolist(),
            "d": np.asarray(d).tolist(), "stats": stats}), flush=True)
    """, n_proc=2)

    assert results[0]["i"] == results[1]["i"]
    assert results[0]["d"] == results[1]["d"]
    assert results[0]["stats"] == results[1]["stats"]

    data_rng = np.random.default_rng(0)
    db = (data_rng.random((96, 8)) * 10).astype(np.float32)
    q = (data_rng.random((6, 8)) * 10).astype(np.float32)
    ref_d, ref_i, ref_stats = ShardedKNN(
        db, mesh=make_mesh(1, 2), k=5).search_certified(
            q, selector="pallas", margin=8)
    np.testing.assert_array_equal(np.asarray(results[0]["i"]), ref_i)
    np.testing.assert_array_equal(
        np.asarray(results[0]["d"], dtype=np.float64), ref_d)
    assert results[0]["stats"] == ref_stats


def test_multihost_2x2_mesh_four_processes(rng, tmp_path):
    """4 jax.distributed CPU processes on a (2, 2) mesh: BOTH the query
    and db axes span process boundaries, and each process assembles its
    addressable piece of the query-sharded result — the per-host
    assembly pattern a real pod run uses.  Assembled pieces must equal
    the single-process reference bitwise."""
    _require_multiprocess_cpu()
    results = _spawn_jax_procs(tmp_path, """
        import sys, json
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

        from knn_tpu.parallel import multihost
        from knn_tpu.parallel.sharded import ShardedKNN

        multihost.initialize(coordinator_address=f"localhost:{port}",
                             num_processes=n_proc, process_id=pid)
        rng = np.random.default_rng(0)
        db = (rng.random((64, 8)) * 10).astype(np.float32)
        q = (rng.random((8, 8)) * 10).astype(np.float32)
        mesh = multihost.global_mesh(2, 2)
        prog = ShardedKNN(db, mesh=mesh, k=5)
        d, i = prog.search(q)
        pieces = sorted(
            ((s.index[0].start or 0, np.asarray(s.data))
             for s in i.addressable_shards), key=lambda t: t[0])
        print("RESULT " + json.dumps({
            "pid": pid,
            "pieces": [[int(lo), p.tolist()] for lo, p in pieces]}),
            flush=True)
    """, n_proc=4)

    # single-process reference on the same seeded data
    data_rng = np.random.default_rng(0)
    db = (data_rng.random((64, 8)) * 10).astype(np.float32)
    q = (data_rng.random((8, 8)) * 10).astype(np.float32)
    _, ref_i = ShardedKNN(db, mesh=make_mesh(2, 2), k=5).search(q)
    ref_i = np.asarray(ref_i)

    # every process's addressable pieces must match the reference rows
    seen_rows = set()
    for p, res in results.items():
        for lo, piece in res["pieces"]:
            piece = np.asarray(piece)
            np.testing.assert_array_equal(
                piece, ref_i[lo : lo + piece.shape[0]])
            seen_rows.update(range(lo, lo + piece.shape[0]))
    assert seen_rows == set(range(8))  # the 4 hosts cover every query row


# --- hierarchical mesh: per-chip -> per-host -> global merge tree ------
# Single-process over the 8 virtual CPU devices: the 3-axis
# make_host_mesh placement runs the SAME SPMD programs a real pod runs,
# and every result must name the same neighbours as the flat mesh — the
# merge tree is associative, so the hierarchy is free.  The local shard
# shapes differ between the two meshes, so f32 distances are held to
# rounding (oracles.assert_same_neighbors), not to the bit.

def test_host_mesh_search_bitwise_vs_flat(rng):
    from knn_tpu.parallel.mesh import make_host_mesh

    db = (rng.random((128, 12)) * 10).astype(np.float32)
    q = (rng.random((20, 12)) * 10).astype(np.float32)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(4, 2), k=7).search(q)
    for hosts, chips in ((2, 2), (4, 1), (2, 1)):
        prog = ShardedKNN(db, mesh=make_host_mesh(2, hosts, chips), k=7)
        d, i = prog.search(q)
        assert_same_neighbors(d, i, ref_d, ref_i, q, db)


def test_host_mesh_merge_strategy_combinations_bitwise(rng):
    from knn_tpu.parallel.mesh import make_host_mesh

    db = (rng.random((96, 8)) * 10).astype(np.float32)
    q = (rng.random((12, 8)) * 10).astype(np.float32)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(8, 1), k=5).search(q)
    mesh = make_host_mesh(2, 2, 2)
    for intra in ("ring", "allgather"):
        for dcn in ("ring", "allgather"):
            prog = ShardedKNN(db, mesh=mesh, k=5, merge=intra,
                              dcn_merge=dcn)
            assert (prog.merge, prog.dcn_merge) == (intra, dcn)
            assert prog.merge_source == prog.dcn_merge_source == "explicit"
            d, i = prog.search(q)
            assert_same_neighbors(d, i, ref_d, ref_i, q, db)


def test_host_mesh_certified_bitwise_across_selectors(rng):
    from knn_tpu.parallel.mesh import make_host_mesh

    db = (rng.random((96, 8)) * 10).astype(np.float32)
    q = (rng.random((10, 8)) * 10).astype(np.float32)
    flat = ShardedKNN(db, mesh=make_mesh(2, 4), k=5)
    hier = ShardedKNN(db, mesh=make_host_mesh(2, 2, 2), k=5)
    for selector in ("exact", "approx", "pallas"):
        rd, ri, _ = flat.search_certified(q, selector=selector, margin=8)
        d, i, _ = hier.search_certified(q, selector=selector, margin=8)
        np.testing.assert_array_equal(i, ri)
        np.testing.assert_array_equal(d, rd)


def test_host_mesh_predict_and_count_paths(rng):
    from knn_tpu.parallel.mesh import make_host_mesh

    db = (rng.random((64, 6)) * 10).astype(np.float32)
    q = (rng.random((9, 6)) * 10).astype(np.float32)
    labels = rng.integers(0, 4, 64).astype(np.int32)
    flat = ShardedKNN(db, mesh=make_mesh(4, 2), k=5, labels=labels,
                      num_classes=4)
    hier = ShardedKNN(db, mesh=make_host_mesh(2, 2, 2), k=5,
                      labels=labels, num_classes=4)
    np.testing.assert_array_equal(
        np.asarray(flat.predict(q)), np.asarray(hier.predict(q)))
    rd, ri, rc = flat.radius_search(q, 5.0, max_neighbors=6)
    d, i, c = hier.radius_search(q, 5.0, max_neighbors=6)
    np.testing.assert_array_equal(c, rc)
    np.testing.assert_array_equal(i, ri)


# --- MultiHostKNN: the host-mediated DCN merge replica ------------------

def test_multihostknn_single_process_degenerates(rng):
    from knn_tpu.parallel.multihost import MultiHostKNN, last_report

    db = (rng.random((80, 10)) * 10).astype(np.float32)
    q = (rng.random((7, 10)) * 10).astype(np.float32)
    ref_d, ref_i = ShardedKNN(db, mesh=make_mesh(4, 2), k=6).search(q)
    prog = MultiHostKNN(db, k=6, db_shards=2)
    d, i = prog.search(q)
    np.testing.assert_array_equal(i, np.asarray(ref_i))
    np.testing.assert_array_equal(d, np.asarray(ref_d))
    rep = last_report()
    assert rep["hosts"] == 1 and rep["transport"] == "local"


def test_merge_topk_host_matches_device_merge(rng):
    from knn_tpu.ops.topk import merge_topk
    from knn_tpu.parallel.multihost import merge_topk_host

    d1 = np.sort(rng.random((5, 4)).astype(np.float32), axis=1)
    d2 = np.sort(rng.random((5, 4)).astype(np.float32), axis=1)
    i1 = rng.integers(0, 50, (5, 4)).astype(np.int32)
    i2 = rng.integers(50, 100, (5, 4)).astype(np.int32)
    hd, hi = merge_topk_host([d1, d2], [i1, i2], 4)
    dd, di = merge_topk(jax.numpy.asarray(d1), jax.numpy.asarray(i1),
                        jax.numpy.asarray(d2), jax.numpy.asarray(i2), 4)
    np.testing.assert_array_equal(hd, np.asarray(dd))
    np.testing.assert_array_equal(hi, np.asarray(di))


def _require_distributed_init():
    verdict = mh_harness.distributed_init_supported()
    if not verdict["ok"]:
        pytest.skip(
            "jax.distributed coordinator/KV store unsupported: "
            f"{verdict['reason']}")


def test_multihostknn_two_process_kv_lane_bitwise(rng, tmp_path):
    """ACCEPTANCE (ISSUE 12): the hierarchical merge certified
    bitwise-identical to the single-host ShardedKNN reference across
    k, metric, and precision, on a REAL 2-process CPU jax.distributed
    lane — per-host candidates computed on each process's own devices
    (ICI level inside the local program), the global merge crossing the
    process boundary over the coordinator's DCN side channel.  This
    lane needs only distributed INIT (green on every supported
    jaxlib), so unlike the collective-gated tests above it is a pinned
    test, not a skip."""
    _require_distributed_init()
    results = _spawn_jax_procs(tmp_path, """
        import sys, json
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

        from knn_tpu.parallel import multihost

        multihost.initialize(coordinator_address=f"localhost:{port}",
                             num_processes=n_proc, process_id=pid)
        rng = np.random.default_rng(0)
        db = (rng.random((96, 8)) * 10).astype(np.float32)
        q = (rng.random((6, 8)) * 10).astype(np.float32)
        rows = 96 // n_proc
        local = db[pid * rows : (pid + 1) * rows]
        out = {}
        for k in (3, 7):
            for metric in ("l2", "cosine"):
                prog = multihost.MultiHostKNN(local, k=k, metric=metric)
                d, i = prog.search(q)
                out[f"search/{k}/{metric}"] = {
                    "d": d.tolist(), "i": i.tolist()}
        # certified across precisions (the flagship selector) + counted
        for precision in ("highest", "bf16x3", "int8"):
            prog = multihost.MultiHostKNN(local, k=5)
            d, i, stats = prog.search_certified(
                q, selector="pallas", margin=8, precision=precision)
            out[f"certified/pallas/{precision}"] = {
                "d": d.tolist(), "i": i.tolist(),
                "gap": stats["straggler_gap_s"]}
        prog = multihost.MultiHostKNN(local, k=5)
        d, i, stats = prog.search_certified(q, selector="approx", margin=8)
        out["certified/approx"] = {"d": d.tolist(), "i": i.tolist(),
                                   "per_host": stats["per_host"]}
        rep = multihost.last_report()
        out["report"] = {"hosts": rep["hosts"],
                         "transport": rep["transport"],
                         "bytes": rep["dcn_merge_bytes"]}
        print("RESULT " + json.dumps(out), flush=True)
    """, n_proc=2)

    # both processes agree exactly on every combination
    for key in results[0]:
        assert results[0][key] == results[1][key], key

    # bitwise parity with the single-host reference on the same data
    data_rng = np.random.default_rng(0)
    db = (data_rng.random((96, 8)) * 10).astype(np.float32)
    q = (data_rng.random((6, 8)) * 10).astype(np.float32)
    for k in (3, 7):
        for metric in ("l2", "cosine"):
            ref_d, ref_i = ShardedKNN(
                db, mesh=make_mesh(8, 1), k=k, metric=metric).search(q)
            got = results[0][f"search/{k}/{metric}"]
            np.testing.assert_array_equal(
                np.asarray(got["i"]), np.asarray(ref_i))
            # plain-search f32 distances: neighbor identity and order are
            # exact; VALUES carry CPU XLA's documented gemm
            # shape-dependence (serving.engine docstring) — the per-host
            # matmul runs a different shape than the flat placement's,
            # so the last float bits move on CPU (TPU MXU is
            # batch-shape-invariant).  The certified paths below pin
            # bitwise: their returned distances are host-f64 refined
            # (counted) and placement-invariant.
            np.testing.assert_allclose(
                np.asarray(got["d"], np.float32), np.asarray(ref_d),
                rtol=1e-5)
    for precision in ("highest", "bf16x3", "int8"):
        ref_d, ref_i, _ = ShardedKNN(
            db, mesh=make_mesh(8, 1), k=5).search_certified(
                q, selector="pallas", margin=8, precision=precision)
        got = results[0][f"certified/pallas/{precision}"]
        np.testing.assert_array_equal(np.asarray(got["i"]), ref_i)
        np.testing.assert_array_equal(np.asarray(got["d"]), ref_d)
        assert got["gap"] >= 0
    ref_d, ref_i, _ = ShardedKNN(
        db, mesh=make_mesh(8, 1), k=5).search_certified(
            q, selector="approx", margin=8)
    got = results[0]["certified/approx"]
    np.testing.assert_array_equal(np.asarray(got["i"]), ref_i)
    np.testing.assert_array_equal(np.asarray(got["d"]), ref_d)
    assert len(got["per_host"]["walls_s"]) == 2
    # the report carries the modeled DCN volume of the 2-host allgather
    from knn_tpu.parallel.crossover import merge_bytes

    assert results[0]["report"]["hosts"] == 2
    assert results[0]["report"]["transport"] == "kv"
    assert results[0]["report"]["bytes"] == merge_bytes(6, 5, 2, "allgather")


def test_serving_engine_over_hierarchical_placement(rng):
    """The cluster-knee enabler (docs/serving.md): the bucketed serving
    engine + micro-batching queue run unchanged over a hierarchical
    placement — the knee harness pointed at this engine measures the
    CLUSTER's saturation, hierarchical merge tree and all."""
    from knn_tpu.parallel.mesh import make_host_mesh
    from knn_tpu.serving.engine import ServingEngine
    from knn_tpu.serving.queue import QueryQueue

    db = (rng.random((256, 12)) * 10).astype(np.float32)
    q = (rng.random((10, 12)) * 10).astype(np.float32)
    prog = ShardedKNN(db, mesh=make_host_mesh(2, 2, 2), k=5)
    ref_d, ref_i = prog.search(q)
    eng = ServingEngine(prog, min_bucket=8, max_bucket=32)
    eng.warmup()
    d, i = eng.search(q)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ref_i))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(ref_d))
    with QueryQueue(eng, max_wait_ms=2.0) as qq:
        d2, i2 = qq.submit(q[:3]).result()
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(ref_i)[:3])

def test_fleet_merges_two_process_telemetry(tmp_path):
    """ACCEPTANCE (ISSUE 20): the fleet plane over a REAL 2-process
    jax.distributed lane — each process runs MultiHostKNN searches with
    telemetry on, logs its ``multihost.merge`` spans to a JSONL sink,
    and writes an identity-stamped snapshot into a shared directory;
    the jax-free aggregator then merges offline:

    - merged counters equal the EXACT sum of both members' counters,
    - the stitched cross-host waterfall tiles (local + wait +
      dcn_merge per host, within stated tolerance) with the straggler
      host named,
    - the bucket-merged fleet p99 brackets both per-host windows
      (never an average of percentiles).

    Like the KV-lane bitwise test above this needs only distributed
    INIT, so it is a pinned test on every supported jaxlib."""
    _require_distributed_init()
    results = _spawn_jax_procs(tmp_path, """
        import os, sys, json, time
        snapdir = os.path.dirname(os.path.abspath(__file__))
        pid, n_proc, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
        os.environ["KNN_TPU_OBS_LOG"] = os.path.join(
            snapdir, f"events{pid}.jsonl")
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")

        from knn_tpu import obs
        from knn_tpu.obs import names as mn
        from knn_tpu.parallel import multihost

        multihost.initialize(coordinator_address=f"localhost:{port}",
                             num_processes=n_proc, process_id=pid)
        rng = np.random.default_rng(0)
        db = (rng.random((96, 8)) * 10).astype(np.float32)
        q = (rng.random((6, 8)) * 10).astype(np.float32)
        rows = 96 // n_proc
        prog = multihost.MultiHostKNN(
            db[pid * rows : (pid + 1) * rows], k=5)
        lat = obs.histogram(mn.SERVING_REQUEST_LATENCY, op="multihost")
        for _ in range(8):
            t0 = time.perf_counter()
            prog.search(q)
            lat.observe(time.perf_counter() - t0)
        payload = obs.write_json_snapshot(
            os.path.join(snapdir, f"member{pid}.json"))
        [lat_s] = payload["metrics"][
            mn.SERVING_REQUEST_LATENCY]["series"]
        out = {
            "identity": payload["identity"],
            "merge_bytes": sum(
                s["value"] for s in
                payload["metrics"][mn.MERGE_BYTES]["series"]),
            "window_p95": lat_s["value"]["p95"],
        }
        print("RESULT " + json.dumps(out), flush=True)
    """, n_proc=2)

    # identity stamps: each member is attributable (satellite 1)
    for pid in (0, 1):
        ident = results[pid]["identity"]
        assert ident["process_index"] == pid
        assert ident["process_count"] == 2

    from knn_tpu.obs import fleet
    from knn_tpu.obs import names as mn

    fleet.reset_fleet_engine()
    rep = fleet.fleet_report(snapshot_dir=str(tmp_path))
    assert rep["enabled"] and not rep["partial"]
    assert rep["member_count"] == 2

    # merged counters = the EXACT sum of both members'
    merged_bytes = sum(s["value"]
                       for s in rep["counters"][mn.MERGE_BYTES])
    assert merged_bytes == (results[0]["merge_bytes"]
                            + results[1]["merge_bytes"])
    per_host_total = sum(v for s in rep["counters"][mn.MERGE_BYTES]
                         for v in s["per_host"].values())
    assert per_host_total == merged_bytes

    # bucket-merged fleet p99 brackets BOTH per-host windows: the
    # merged distribution's upper tail sits at or above every host's
    # window p95 (8 of 16 samples each), and it came from summed
    # cumulative buckets — never from averaging percentiles
    [h] = rep["histograms"][mn.SERVING_REQUEST_LATENCY]
    assert h["count"] == 16.0
    fq = h["fleet_quantiles"]
    assert fq["source"] == "merged_buckets"
    assert len(h["window_quantiles_per_host"]) == 2
    for pid in (0, 1):
        assert fq["p99"] >= results[pid]["window_p95"]

    # the stitched cross-host waterfalls: one per request, each tiling
    # host-local + wait + dcn_merge against the measured total within
    # stated tolerance, straggler host named
    wfs = rep["waterfalls"]
    assert len(wfs) == 8
    for wf in wfs.values():
        assert wf["kind"] == "multihost" and wf["hosts"] == 2
        assert wf["straggler_host"] in (0, 1)
        assert wf["complete"], wf
        lane = sum(
            s["dur_s"] for s in wf["segments"]
            if s.get("host") == wf["straggler_host"]
            or s["name"] == "dcn_merge")
        assert abs(lane - wf["total_s"]) <= wf["tolerance_s"] + 1e-9

    # the members' /statusz multihost sections agree on the straggler
    mh = rep["multihost"]
    assert mh is not None and len(mh["host_walls_s"]) == 2
    assert mh["straggler_host"] in (0, 1)
