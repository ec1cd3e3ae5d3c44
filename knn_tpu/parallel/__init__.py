"""L0 communication runtime: the reference's MPI backend (knn_mpi.cpp:123-129,
133-134,224-227,276-277,340,383,395-397 — the 11 entry points in SURVEY.md
§2.8) rebuilt as sharding + XLA collectives over a `jax.sharding.Mesh`.

Mapping (rank ↔ mesh device):
  MPI_Bcast      -> replicated NamedSharding            (collectives.replicate)
  MPI_Scatter    -> sharded NamedSharding / shard_map   (collectives.shard)
  MPI_Allreduce  -> lax.pmin / lax.pmax / lax.psum      (collectives.allreduce_*)
  MPI_Gather     -> lax.all_gather / host fetch         (collectives.gather)
  MPI_Barrier    -> block_until_ready                   (collectives.barrier)
  MPI_Comm_rank  -> lax.axis_index                      (inside shard_map)
  MPI_Comm_size  -> mesh.shape[axis]
  MPI_Abort      -> pad-to-multiple instead             (mesh.pad_to_multiple)

Multi-host (``mpiexec`` across nodes -> one JAX process per host over DCN)
lives in :mod:`knn_tpu.parallel.multihost`: initialize / global_mesh /
shard_across_hosts / process_row_slice.
"""

# Attribute access is lazy (PEP 562, the knn_tpu/__init__ idiom) so the
# jax-free members — parallel.crossover's measured table, validators,
# and byte models — never pay (or break on) the JAX import the mesh/collective/
# SPMD members need.
import importlib

#: symbol -> defining submodule; resolved on first attribute access
_EXPORTS = {
    "make_mesh": "knn_tpu.parallel.mesh",
    "make_host_mesh": "knn_tpu.parallel.mesh",
    "default_mesh": "knn_tpu.parallel.mesh",
    "pad_to_multiple": "knn_tpu.parallel.mesh",
    "QUERY_AXIS": "knn_tpu.parallel.mesh",
    "DB_AXIS": "knn_tpu.parallel.mesh",
    "HOST_AXIS": "knn_tpu.parallel.mesh",
    "MEASURED_CROSSOVER": "knn_tpu.parallel.crossover",
    "choose_merge": "knn_tpu.parallel.crossover",
    "merge_bytes": "knn_tpu.parallel.crossover",
    "resolve_merge": "knn_tpu.parallel.crossover",
    "replicate": "knn_tpu.parallel.collectives",
    "shard": "knn_tpu.parallel.collectives",
    "gather": "knn_tpu.parallel.collectives",
    "allreduce_min": "knn_tpu.parallel.collectives",
    "allreduce_max": "knn_tpu.parallel.collectives",
    "barrier": "knn_tpu.parallel.collectives",
    "ShardedKNN": "knn_tpu.parallel.sharded",
    "sharded_knn": "knn_tpu.parallel.sharded",
    "sharded_knn_predict": "knn_tpu.parallel.sharded",
    "sharded_minmax": "knn_tpu.parallel.sharded",
    "sharded_normalize_transductive": "knn_tpu.parallel.sharded",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name):
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(
            f"module 'knn_tpu.parallel' has no attribute {name!r}"
        ) from None
    value = getattr(importlib.import_module(module), name)
    globals()[name] = value  # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
