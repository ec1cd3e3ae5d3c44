"""The one home of the Pallas kernel's knobs: a knob comes from the call
or from ``DEFAULT_KNOBS``, and from nowhere else.

Every knob consumer (``ShardedKNN.search_certified``, ``certified_plan``,
``predict_certified``, the self-join, the serving engine's stats)
resolves through ONE call::

    from knn_tpu import tuning
    knobs, info = tuning.resolve_full(
        n, d, k, metric="l2", dtype=None,
        overrides={"tile_n": explicit_or_None, ...})

This module imports nothing of ``knn_tpu``, opens no file and reads no
environment variable.  What a call's shape decides (row cut, sub-batch,
survivor depth, operand residency) is decided by the rules in
``knn_tpu.analysis`` and ``ops.pallas_knn``, not here.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

#: the knob names resolve() returns — exactly the kernel-shaping
#: keyword arguments of ShardedKNN.search_certified's pallas selector.
#: Values are the library defaults (None = the ops.pallas_knn
#: module-constant default at the use site).  ``block_q=256`` only
#: re-blocks the query grid, the per-row arithmetic is untouched.
DEFAULT_KNOBS: Dict[str, object] = {
    "kernel": "tiled",
    "tile_n": None,
    "block_q": 256,
    "survivors": None,
    "precision": "bf16x3",
    "final_select": "exact",
    "grid_order": "query_major",
    "final_recall_target": None,
}

#: block_q where ``kernel`` is "streaming" or "fused" and the caller
#: chose none.  Those kernels hold EVERY db tile's candidate block in
#: VMEM at once, so the tiled kernel's 256 does not carry over: Mosaic
#: (libtpu 0.0.34, v5e, deviceless) puts streaming bq256 at 126.55 of
#: 128 MiB at SIFT and over the device at GIST/GloVe, and fused bq256
#: over it everywhere; at 128 both compile at all three shapes
#: (71.65-86.63 MiB).
FULL_WIDTH_BLOCK_Q = 128


def resolve_full(
    n: int, d: int, k: int, *, metric: str = "l2",
    dtype: Optional[str] = None, device_kind: Optional[str] = None,
    overrides: Optional[Dict[str, object]] = None,
    cache_path: Optional[str] = None, profile: str = "latency",
) -> Tuple[Dict[str, object], Dict[str, object]]:
    """(knobs, info): ``DEFAULT_KNOBS`` with the call's non-None
    ``overrides`` on top (``FULL_WIDTH_BLOCK_Q`` in place of the default
    block_q for the streaming and fused kernels), and which knobs the
    call named.  ``info["source"]`` is always ``"default"``: no shape,
    device or file chooses a knob, so the shape arguments are read by
    nobody; a ``cache_path`` is refused, never ignored."""
    if cache_path is not None:
        raise ValueError(
            "the winner cache was removed in PR 59; name the knobs in "
            f"the call (got tune_cache/cache_path={cache_path!r})")
    knobs = dict(DEFAULT_KNOBS)
    overridden = []
    for kk, v in (overrides or {}).items():
        if kk not in DEFAULT_KNOBS:
            raise ValueError(f"unknown pallas knob {kk!r}; "
                             f"expected one of {sorted(DEFAULT_KNOBS)}")
        if v is not None:
            knobs[kk] = v
            overridden.append(kk)
    if (knobs["kernel"] in ("streaming", "fused")
            and "block_q" not in overridden):
        knobs["block_q"] = FULL_WIDTH_BLOCK_Q
    return knobs, {"source": "default", "overridden": sorted(overridden)}


def resolve(n: int, d: int, k: int, **kwargs) -> Dict[str, object]:
    """The knob set alone — see :func:`resolve_full`."""
    return resolve_full(n, d, k, **kwargs)[0]
