"""knn_tpu.index — the mutable-index subsystem: delta-shard inserts,
tombstone deletes, and snapshot-swap compaction over the immutable
placement machinery (docs/INDEX.md).

Two layers:

- :mod:`~knn_tpu.index.artifact` — jax-free: the error vocabulary
  (:class:`MutationUnsupportedError`, :class:`MutationBudgetError`) and
  the ``mutation`` artifact-block validator;
- :mod:`~knn_tpu.index.mutable` — :class:`MutableIndex` (insert /
  delete / compact / search / search_certified over a ``ShardedKNN``
  placement + a bucket-laddered delta tail) and
  :class:`MutableServingEngine` (the QueryQueue-compatible serving
  frontend with writes as a first-class op).

``MutableIndex``/``MutableServingEngine`` import JAX, so they resolve
LAZILY here: the doctor CLI can import
``knn_tpu.index`` without paying (or breaking on) a backend init.
"""

from knn_tpu.index.artifact import (  # noqa: F401
    MUTATION_VERSION,
    MutationBudgetError,
    MutationUnsupportedError,
    validate_mutation_block,
)

__all__ = [
    "MUTATION_VERSION",
    "MutableIndex",
    "MutableServingEngine",
    "MutationBudgetError",
    "MutationUnsupportedError",
    "validate_mutation_block",
]


def __getattr__(name):
    if name in ("MutableIndex", "MutableServingEngine"):
        from knn_tpu.index import mutable

        return getattr(mutable, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
