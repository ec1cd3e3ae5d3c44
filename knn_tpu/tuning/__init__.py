"""Persistent kernel autotuning (the knob search that used to die with
each TPU session, made reproducible and cached).

Every Pallas-kernel knob consumer resolves through ONE call::

    from knn_tpu import tuning
    knobs = tuning.resolve(n, d, k, metric="l2", dtype=None,
                           overrides={"tile_n": explicit_or_None, ...})

Precedence: explicit overrides > the persisted winner for this exact
``(device_kind, n, d, k, metric, dtype)`` > library defaults
(``DEFAULT_KNOBS``; the streaming and fused kernels take
``FULL_WIDTH_BLOCK_Q`` for a block_q nobody chose).  Winners
come from :func:`autotune` (``python -m knn_tpu.cli tune`` on a TPU
session) and live in one JSON file (:mod:`knn_tpu.tuning.cache`;
``KNN_TPU_TUNE_CACHE`` overrides the location).  Candidates must pass a
bitwise end-result gate against the reference grouped kernel before
they may win — a fast wrong kernel can never be selected.
"""

from knn_tpu.tuning.autotune import (
    DEFAULT_KNOBS,
    FULL_WIDTH_BLOCK_Q,
    PRUNE_ENV,
    autotune,
    autotune_ivf,
    counters,
    ivf_grid,
    knob_grid,
    prune_candidates,
    prune_threshold_from_env,
    reset_counters,
    resolve,
    resolve_full,
)
from knn_tpu.tuning.cache import (
    CACHE_ENV,
    PROFILES,
    TuneCache,
    cache_key,
    default_cache_path,
)

__all__ = [
    "DEFAULT_KNOBS",
    "FULL_WIDTH_BLOCK_Q",
    "PRUNE_ENV",
    "autotune",
    "autotune_ivf",
    "counters",
    "ivf_grid",
    "knob_grid",
    "prune_candidates",
    "prune_threshold_from_env",
    "reset_counters",
    "resolve",
    "resolve_full",
    "CACHE_ENV",
    "PROFILES",
    "TuneCache",
    "cache_key",
    "default_cache_path",
]
