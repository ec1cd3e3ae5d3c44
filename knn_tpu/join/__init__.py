"""Bulk kNN-join: offline top-k of EVERY row of a query set A against
a corpus B, with the db stream amortized over query superblocks.

Latency-bound serving re-streams the whole placed database per request
batch.  The join engine is
the one regime that can honor the reference's own design principle
("maximize compute-to-communication ratio — fewer, larger messages",
PDF p.7 §3.1): it sweeps A in large superblocks through the EXISTING
streaming/fused kernels and sharded programs unmodified, so db HBM
bytes per query fall as 1/superblock_rows.  Query-side double buffering — superblock i+1's host->device
transfer overlapping block i's device compute under the bounded-depth
drain-oldest discipline — turns the
h2d query stream into an amortized cost too.

Entry points: :func:`knn_join` (one call, any ShardedKNN placement —
resident or host-RAM tier — or an IVFIndex), :func:`default_plan`
(the superblock/nesting plan the engine would use, jax-free),
:func:`knn_self_join` (the exact k-NN graph of the placed rows: every
row a query of the corpus it is part of, its own row out by id, the
certified path's blocks in a bounded pipeline).
"""

from knn_tpu.join.artifact import JOIN_VERSION, validate_join_block
from knn_tpu.join.engine import (
    JOIN_MODES,
    default_plan,
    knn_join,
    knn_self_join,
)

__all__ = [
    "JOIN_MODES",
    "JOIN_VERSION",
    "default_plan",
    "knn_join",
    "knn_self_join",
    "validate_join_block",
]
