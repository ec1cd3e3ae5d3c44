"""The reference's MPI collective surface (SURVEY.md §2.8, 11 entry points)
as XLA-native primitives.

Two levels:

- **Placement collectives** (`replicate`, `shard`): the Bcast/Scatter of
  knn_mpi.cpp:224-227 are not runtime calls on TPU — they are *shardings*.
  `device_put` with a `NamedSharding` moves the data once; every subsequent
  jitted program reads it in place.  XLA inserts the actual ICI transfers.

- **Compute collectives** (`allreduce_min/max`, inside-shard_map helpers):
  the Allreduce MAX/MIN of knn_mpi.cpp:276-277 become `lax.pmin`/`lax.pmax`
  over mesh axis names; Gather (knn_mpi.cpp:340,383) becomes
  `lax.all_gather` or simply an unsharded output spec.

`barrier` reproduces the Barrier+Wtime timing fence (knn_mpi.cpp:133-134,
395-396): JAX dispatch is async, so wall-clock timing without
`block_until_ready` measures dispatch, not compute.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The measured ring/allgather crossover (SCALING.json) lives jax-free in
# parallel.crossover so a jax-free reader
# can read it without a backend; re-exported here because strategy
# choice is a property of this collective surface.
from knn_tpu.parallel.crossover import (  # noqa: F401  (re-export)
    MEASURED_CROSSOVER,
    choose_merge,
    merge_bytes,
    resolve_merge,
)


def replicate(x, mesh: Mesh) -> jax.Array:
    """MPI_Bcast (knn_mpi.cpp:224-225): one copy of ``x`` on every device."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def shard(x, mesh: Mesh, axis_name: str, axis: int = 0) -> jax.Array:
    """MPI_Scatter (knn_mpi.cpp:226-227): split ``x`` along ``axis`` across
    the mesh axis ``axis_name``.  Size must divide the axis; callers pad
    first via mesh.pad_to_multiple."""
    spec = [None] * x.ndim
    spec[axis] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))


def gather(
    x: jax.Array,
    axis_name: Union[str, Sequence[str]],
    *,
    axis: int = 0,
    tiled: bool = True,
) -> jax.Array:
    """MPI_Gather (knn_mpi.cpp:340,383): assemble the per-device shards along
    ``axis``.  Every device receives the full array (i.e. MPI_Allgather —
    a root-only gather has no cheaper TPU analogue; the reference's root
    rank is just "whoever writes the file").  ``tiled=True`` concatenates
    shards; ``tiled=False`` stacks a new leading device axis.  Call inside
    shard_map."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def allreduce_min(x: jax.Array, axis_name: Union[str, Sequence[str]]) -> jax.Array:
    """MPI_Allreduce(MPI_MIN) (knn_mpi.cpp:277).  Call inside shard_map."""
    return lax.pmin(x, axis_name)


def allreduce_max(x: jax.Array, axis_name: Union[str, Sequence[str]]) -> jax.Array:
    """MPI_Allreduce(MPI_MAX) (knn_mpi.cpp:276).  Call inside shard_map."""
    return lax.pmax(x, axis_name)


def barrier(*arrays) -> None:
    """MPI_Barrier before MPI_Wtime (knn_mpi.cpp:133-134,395-396): block the
    host until every listed device computation has retired."""
    for a in jax.tree_util.tree_leaves(arrays):
        if isinstance(a, jax.Array):
            a.block_until_ready()
