"""Jax-free pieces of the mutable-index subsystem: the error vocabulary
and the ``mutation`` artifact-block validator.

These live apart from :mod:`knn_tpu.index.mutable` (which imports JAX at
module load) so a jax-free reader and the
multi-host refusal path can import them without paying — or breaking on
— a backend init.  Same split as ``loadgen.knee``:
whatever validates artifacts must run on a box without the
accelerator too.
"""

from __future__ import annotations

from typing import List

#: version stamp of the ``mutation`` block; bump on any schema change
#: so a half-migrated block is refused — the version token the
#: artifact-schema catalog's ``mutation`` entry consumes
MUTATION_VERSION = 1


def _required_fields():
    from knn_tpu.analysis.artifacts import required_keys

    return required_keys("mutation")


#: fields every valid mutation block must carry; ``admitted_p99_ms`` may be null (an honest "no
#: admitted reads completed" beats a fabricated number) — DERIVED from
#: the artifact-schema catalog (knn_tpu.analysis.artifacts), the one
#: declaration the validator and the lockstep checker both read
MUTATION_REQUIRED = _required_fields()


class MutationUnsupportedError(ValueError):
    """Raised by ``insert``/``delete`` on placements that cannot be
    mutated yet — host-RAM-tier (the database is not resident to search
    a delta against) and multi-host (no cross-process write replication
    protocol exists).  A LOUD refusal: the alternative is silently
    serving stale results from a replica that believes it applied the
    write (docs/INDEX.md)."""


class MutationBudgetError(RuntimeError):
    """Raised when a write exceeds the index's delta budget — the tail
    past its top ladder rung, or tombstones past the certify-widening
    reserve.  The fix is always :meth:`~knn_tpu.index.mutable.
    MutableIndex.compact` (or auto-compaction thresholds that fire
    before the budget fills; docs/INDEX.md)."""


def validate_mutation_block(block) -> List[str]:
    """Structural validation of a ``mutation`` block: returns the list of
    violations (empty = valid).  Blocks that recorded their own failure
    (an ``error`` key) are exempt — an honest error field beats a
    refused block (the loadgen_knee discipline).  A shim over the
    artifact-schema catalog (:mod:`knn_tpu.analysis.artifacts`, the
    ``mutation`` entry) with the legacy error strings byte-identical."""
    from knn_tpu.analysis.artifacts import validate

    return validate("mutation", block, style="legacy")
