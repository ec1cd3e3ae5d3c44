"""The measured ring/allgather crossover — merge-strategy selection as
DATA, not caller folklore.

``SCALING.json`` (a study on virtual CPU devices) measured both db-axis
merge strategies at equal total work across mesh shapes and k.  The
verdict is a crossover, not a winner: allgather's one-collective P·k candidate
volume wins at small shard counts and large ones whose ring would pay
P-1 latency hops, while the ring's constant-memory (P-1)·k pipeline
wins in between and at large k where the gathered volume dominates.
Until this module, that measurement drove nothing — ``merge=`` was a
caller-chosen kwarg defaulting to allgather everywhere.

This is the jax-free home of

- :data:`MEASURED_CROSSOVER` — the argmin-wall strategy per measured
  ``(k, shards)`` point, pinned against ``SCALING.json`` itself by
  tests/test_collectives.py (edit the JSON and the table must follow);
- :func:`choose_merge` / :func:`resolve_merge` — nearest-measured-point
  lookup with the precedence **explicit caller > env switch
  (``KNN_TPU_MERGE`` / ``KNN_TPU_DCN_MERGE``) > measured table**;
- :func:`merge_bytes` — the collective-volume model behind the
  ``merge_bytes_per_sweep`` column (allgather moves ``Q·k·8·P`` bytes,
  ring ``Q·k·8·(P-1)``; 8 = f32 distance + i32 index per candidate);
- :func:`validate_multihost_block` — structural validation of the
  ``multihost`` block.

Everything here is plain arithmetic on plain numbers, importable
without JAX.
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

#: the two db-axis merge strategies (mirrors parallel.sharded._MERGES)
STRATEGIES = ("allgather", "ring")

#: where a resolved strategy came from, in precedence order
SOURCES = ("explicit", "env", "measured")

#: env switches overriding the measured default at each merge level
#: (the flat/intra-host ICI level and the cross-host DCN level) —
#: cataloged in knn_tpu.analysis.switches
MERGE_ENV = "KNN_TPU_MERGE"
DCN_MERGE_ENV = "KNN_TPU_DCN_MERGE"

#: bytes one (distance f32, index i32) candidate pair moves
CANDIDATE_BYTES = 8

#: ``(k, shards) -> strategy``: the argmin-wall_s strategy at every
#: measured SCALING.json point (mesh column "QxP" contributes P).
#: tests/test_collectives.py re-derives this from the JSON — the table
#: can never silently drift from the measurement it claims to persist.
MEASURED_CROSSOVER: Dict[Tuple[int, int], str] = {
    (10, 2): "allgather",
    (10, 4): "ring",
    (10, 8): "allgather",
    (100, 2): "ring",
    (100, 4): "ring",
    (100, 8): "allgather",
}


def _nearest(value: int, measured) -> int:
    """The measured grid point nearest ``value`` in log space (both
    axes are geometric: k 10/100, shards 2/4/8); ties take the smaller
    point — the conservative, lower-volume regime."""
    v = math.log(max(1, int(value)))
    return min(sorted(set(measured)), key=lambda m: (abs(math.log(m) - v), m))


def choose_merge(k: int, shards: int) -> str:
    """The measured-table strategy for a ``(k, shards)`` merge — the
    nearest measured point's argmin.  ``shards <= 1`` needs no merge;
    allgather (a no-op there) is returned for uniformity."""
    if shards <= 1:
        return "allgather"
    ks = {mk for mk, _ in MEASURED_CROSSOVER}
    ps = {mp for _, mp in MEASURED_CROSSOVER}
    return MEASURED_CROSSOVER[(_nearest(k, ks), _nearest(shards, ps))]


def resolve_merge(
    explicit: Optional[str], k: int, shards: int, *,
    env_name: str = MERGE_ENV,
) -> Tuple[str, str]:
    """``(strategy, source)`` under the precedence explicit > env >
    measured table.  A malformed env value raises rather than silently
    steering a merge (the admission-control strict-env discipline)."""
    if explicit is not None:
        if explicit not in STRATEGIES:
            raise ValueError(
                f"unknown merge {explicit!r}; expected one of {STRATEGIES}")
        return explicit, "explicit"
    env = os.environ.get(env_name, "").strip().lower()
    if env:
        if env not in STRATEGIES:
            raise ValueError(
                f"{env_name}={env!r} is not one of {STRATEGIES}")
        return env, "env"
    return choose_merge(k, shards), "measured"


def merge_bytes(n_queries: int, k: int, shards: int, strategy: str) -> int:
    """Total candidate bytes one merge moves across the axis for a
    ``[n_queries, k]`` result: allgather ships every shard's list to
    every shard (``Q·k·8·P``), the ring passes a constant buffer P-1
    hops (``Q·k·8·(P-1)``).  Reproduces SCALING.json's
    ``merge_bytes_per_sweep`` column exactly (pinned in tests)."""
    if strategy not in STRATEGIES:
        raise ValueError(
            f"unknown merge {strategy!r}; expected one of {STRATEGIES}")
    if shards <= 1:
        return 0
    hops = shards if strategy == "allgather" else shards - 1
    return int(n_queries) * int(k) * CANDIDATE_BYTES * hops


def validate_multihost_block(block) -> list:
    """Structural validation of a ``multihost`` block.  Returns a
    list of error strings, empty when well-formed.  A shim
    over the artifact-schema catalog (:mod:`knn_tpu.analysis.
    artifacts`, the ``multihost`` entry) with the legacy error strings
    byte-identical."""
    from knn_tpu.analysis.artifacts import validate

    return validate("multihost", block, style="legacy")


__all__ = [
    "STRATEGIES",
    "SOURCES",
    "MERGE_ENV",
    "DCN_MERGE_ENV",
    "MEASURED_CROSSOVER",
    "choose_merge",
    "resolve_merge",
    "merge_bytes",
    "validate_multihost_block",
]
