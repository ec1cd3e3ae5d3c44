"""Jax-free pieces of the IVF subsystem: the version token and the
``ivf`` artifact-block validator.

These live apart from :mod:`knn_tpu.ivf.index` (which imports JAX at
module load) so a jax-free reader can import
them without paying — or breaking on — a backend init.  Same split as
``knn_tpu.index.artifact`` over ``knn_tpu.index.mutable``: whatever
validates artifacts must run on a box without the accelerator too.
"""

from __future__ import annotations

from typing import List

#: version stamp of the ``ivf`` block; bump on any schema change so a
#: half-migrated block is refused — the version token the
#: artifact-schema catalog's ``ivf`` entry consumes
IVF_VERSION = 1


def _required_fields():
    from knn_tpu.analysis.artifacts import required_keys

    return required_keys("ivf")


#: fields every valid ivf block must carry — DERIVED from the artifact-schema catalog
#: (knn_tpu.analysis.artifacts), the one declaration the validator and
#: the lockstep checker both read
IVF_REQUIRED = _required_fields()


def validate_ivf_block(block) -> List[str]:
    """Structural validation of an ``ivf`` block: returns the list of violations
    (empty = valid).  Blocks that recorded their own failure (an
    ``error`` key) are exempt — an honest error field beats a refused
    block.  A shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``ivf`` entry)."""
    from knn_tpu.analysis.artifacts import validate

    return validate("ivf", block, style="legacy")
