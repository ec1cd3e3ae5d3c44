"""Jax-free pieces of the PQ compressed tier: the version token and the
``pq`` artifact-block validator.

These live apart from :mod:`knn_tpu.ops.pq` (which imports JAX at
module load) so a jax-free reader can import
them without paying — or breaking on — a backend init.  Same split as
``knn_tpu.ivf.artifact`` over ``knn_tpu.ivf.index``: whatever validates
artifacts must run on a box without the accelerator too.
"""

from __future__ import annotations

from typing import List

#: version stamp of the ``pq`` block (the codebook-geometry
#: provenance of a ``precision="pq"`` placement); bump on any
#: schema change so a half-migrated block is refused — the version
#: token the artifact-schema catalog's ``pq`` entry consumes
PQ_VERSION = 1


def _required_fields():
    from knn_tpu.analysis.artifacts import required_keys

    return required_keys("pq")


#: fields every valid pq block must carry — DERIVED from the artifact-schema catalog
#: (knn_tpu.analysis.artifacts), the one declaration the validator and
#: the lockstep checker both read
PQ_REQUIRED = _required_fields()


def validate_pq_block(block) -> List[str]:
    """Structural validation of a ``pq`` block: returns the list of violations
    (empty = valid).  Blocks that recorded their own failure (an
    ``error`` key) are exempt — an honest error field beats a refused
    block.  A shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``pq`` entry)."""
    from knn_tpu.analysis.artifacts import validate

    return validate("pq", block, style="legacy")
