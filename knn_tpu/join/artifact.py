"""Jax-free pieces of the join subsystem: the version token and the
``join`` artifact-block validator.

These live apart from :mod:`knn_tpu.join.engine` (whose entry points
import JAX lazily but whose callers usually don't want a backend at
all) for the same reason ``knn_tpu.ivf.artifact`` splits off
``knn_tpu.ivf.index``: whatever validates artifacts must run on
a box without the accelerator too.
"""

from __future__ import annotations

from typing import List

#: version stamp of the ``join`` block; bump on any schema change so
#: a half-migrated block is refused — the version token the
#: artifact-schema catalog's ``join`` entry consumes
JOIN_VERSION = 1


def _required_fields():
    from knn_tpu.analysis.artifacts import required_keys

    return required_keys("join")


#: fields every valid join block must carry — DERIVED from the artifact-schema catalog
#: (knn_tpu.analysis.artifacts), the one declaration the validator and
#: the lockstep checker both read
JOIN_REQUIRED = _required_fields()


def validate_join_block(block) -> List[str]:
    """Structural validation of a ``join`` block: returns the list of violations
    (empty = valid).  Blocks that recorded their own failure (an
    ``error`` key) are exempt — an honest error field beats a refused
    block.  A shim over the artifact-schema catalog
    (:mod:`knn_tpu.analysis.artifacts`, the ``join`` entry)."""
    from knn_tpu.analysis.artifacts import validate

    return validate("join", block, style="legacy")
